"""Kernel micro-benchmarks: Pallas vs pure-jnp oracle wall time.  The
kernels run compiled on a TPU and in the Pallas interpreter on the CPU
(``ops.interpret_mode``); CPU timings are not TPU-representative — there the
quantity that matters is the FLOP/byte skip encoded in the kernel shapes,
which is also reported.

The cavity/graph inputs come from the same ExecutionPlan compiler the model
uses (engine.build_execution_plan) instead of hand-packing, so the bench
exercises exactly the layouts the serving path runs; the final rows compare
full-model forward time per backend (``--backend`` selects which; these are
the rows that land in BENCH_kernels_bench.json via
``benchmarks.run --only kernels``).
"""
from __future__ import annotations

import sys

import jax

from benchmarks.common import demo_prune_plan, emit, parse_backends, time_fn
from repro.configs import get_config
from repro.core.agcn import engine
from repro.core.agcn import model as M
from repro.kernels import ops, ref


def _block_inputs():
    """Compile the canonical reduced plan for both backends: the pallas one
    supplies the packed/padded kernel inputs, the reference one the dense
    oracle forms (pallas plans deliberately drop them)."""
    cfg = get_config("agcn-2s", reduced=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prune = demo_prune_plan(cfg, params)
    pallas_plan = engine.build_execution_plan(params, cfg, prune,
                                              backend="pallas")
    ref_plan = engine.build_execution_plan(params, cfg, prune,
                                           backend="reference")
    return cfg, params, prune, pallas_plan, ref_plan


def main():
    backends = parse_backends(sys.argv[1:])
    interp = ops.interpret_mode()

    # RFC encode/decode
    x = jax.random.normal(jax.random.PRNGKey(0), (512, 256))
    t_enc = time_fn(lambda a: ops.rfc_encode(a, interpret=interp), x, iters=3)
    t_ref = time_fn(lambda a: ref.rfc_encode_ref(a), x, iters=3)
    emit("kernels/rfc_encode_pallas", t_enc, "")
    emit("kernels/rfc_encode_ref", t_ref, "")

    cfg, params, prune, pplan, rplan = _block_inputs()

    # cavity tconv: packed shapes straight from the ExecutionPlan (block 1:
    # pruned filters + cavity taps), vs the dense masked-conv oracle
    pa, bs = pplan.arrays["blocks"][1], pplan.static.blocks[1]
    ra = rplan.arrays["blocks"][1]
    C = ra["tw"].shape[1]
    xt = jax.random.normal(jax.random.PRNGKey(2), (16, 128, C))
    t_k = time_fn(
        lambda a: ops.cavity_tconv(a, pa["wp"], pa["taps"], pa["inv_perm"],
                                   bs.n_kept_filters, interpret=interp),
        xt, iters=3)
    t_r = time_fn(lambda a: ref.cavity_tconv_ref(a, ra["tw"]), xt, iters=3)
    n_keep, K = pa["wp"].shape[1], bs.tkernel
    emit("kernels/cavity_tconv_pallas", t_k,
         f"taps={n_keep}/{K} flop_skip={(1 - n_keep / K) * 100:.0f}%")
    emit("kernels/cavity_tconv_ref", t_r, "")

    # fused graph+spatial conv: plan-precomputed padded graph + gathered W
    xg = jax.random.normal(jax.random.PRNGKey(3),
                           (4, 64, cfg.gcn_joints, pa["Wk"].shape[1]))
    t_k = time_fn(lambda a: ops.graph_sconv(a, pa["Gp"], pa["Wk"],
                                            interpret=interp), xg,
                  iters=3)
    t_r = time_fn(
        lambda a: ref.graph_sconv_ref(
            a.reshape(-1, cfg.gcn_joints, ra["Wk"].shape[1]),
            ra["G"], ra["Wk"]), xg, iters=3)
    emit("kernels/graph_sconv_pallas", t_k, "fused G-matmul+1x1 (1 HBM pass)")
    emit("kernels/graph_sconv_ref", t_r, "")

    # CSR vs dense spatial conv over skeleton widths × graph densities: the
    # variable-topology compiler picks CSR per block when the merged graph's
    # density falls below csr_density (0.5 default) — these rows measure the
    # crossover that threshold encodes.  The registry graphs give the
    # natural-skeleton density; the synthetic d25/d50 graphs sweep toward
    # the selector boundary.
    import numpy as np

    import jax.numpy as jnp

    from repro.core.agcn.graph import dense_to_csr, get_topology

    Cin = Cout = 16
    N, T = 2, 16
    rng = np.random.default_rng(7)
    for tname in ("ntu25", "ntu50"):
        topo = get_topology(tname)
        V, K = topo.num_joints, topo.num_subsets
        w = jnp.asarray(rng.standard_normal((K, Cin, Cout)), jnp.float32)
        xg = jnp.asarray(rng.standard_normal((N, T, V, Cin)), jnp.float32)
        xr = xg.reshape(-1, V, Cin)
        sweeps = [(f"d{int(round(topo.density * 100)):02d}", topo.adjacency)]
        for target in (0.25, 0.50):
            mask = rng.random((K, V, V)) < target
            sweeps.append((f"d{int(target * 100):02d}",
                           (rng.standard_normal((K, V, V)) * mask)
                           .astype(np.float32)))
        for tag, g in sweeps:
            dens = float((np.abs(g) > 0).mean())
            indptr, indices, values = dense_to_csr(g)
            vp = -(-V // 8) * 8
            idx, val = ops.pack_csr_ell(indptr, indices, values, vp)
            gj, ip, ix, vl, ej, ev = map(
                jnp.asarray, (g, indptr, indices, values, idx, val))
            t_d = time_fn(lambda a, g_=gj: ref.graph_sconv_ref(a, g_, w),
                          xr, iters=3)
            t_c = time_fn(
                lambda a, p=ip, i=ix, v=vl:
                    ref.graph_sconv_csr_ref(a, p, i, v, w), xr, iters=3)
            emit(f"kernels/sconv_csr/{tname}/{tag}/dense_ref", t_d,
                 f"V={V} density={dens:.2f}")
            emit(f"kernels/sconv_csr/{tname}/{tag}/csr_ref", t_c,
                 f"nnz_skip={(1 - dens) * 100:.0f}%")
            t_d = time_fn(lambda a, g_=gj: ops.graph_sconv(a, g_, w,
                                                             interpret=interp),
                          xg, iters=3)
            t_c = time_fn(
                lambda a, e=ej, v=ev: ops.graph_sconv_csr(a, e, v, w,
                                                     interpret=interp),
                xg, iters=3)
            emit(f"kernels/sconv_csr/{tname}/{tag}/dense_pallas", t_d,
                 f"V={V} density={dens:.2f}")
            emit(f"kernels/sconv_csr/{tname}/{tag}/csr_pallas", t_c,
                 f"ell_deg={idx.shape[-1]} "
                 f"nnz_skip={(1 - dens) * 100:.0f}%")

    # backend comparison: full-model forward through the engine, identical
    # ExecutionPlan flow for both backends (parity is locked by test_engine)
    xm = jax.random.normal(jax.random.PRNGKey(4), (8, cfg.gcn_frames, 25, 3))
    times = {}
    for backend in backends:
        ep = engine.build_execution_plan(params, cfg, prune, quant=True,
                                         backend=backend)
        fn = jax.jit(engine.execute)
        times[backend] = time_fn(fn, ep, xm, iters=3)
        emit(f"kernels/backend_forward_{backend}", times[backend],
             f"clips_per_s={8 / (times[backend] * 1e-6):.1f}")
    if len(times) > 1:
        emit("kernels/backend_forward_ratio", 0.0,
             f"pallas/reference={times['pallas'] / times['reference']:.2f}x "
             f"({jax.devices()[0].device_kind})")


if __name__ == "__main__":
    main()
