#!/usr/bin/env python3
"""Chip smoke test: the served 2s-AGCN at its published widths on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip paths

One chip runs three phases in this one process (a chip belongs to one
process at a time, so nothing here starts a child that touches JAX):

  a. device   — JAX must see a TPU; anything else exits non-zero before any
                result is printed (there is no CPU fallback).
  b. clip     — the two-stream clip step (``make_gcn_infer_step``) on the
                Pallas backend with ``quant=True`` plans, over 8 two-person
                300-frame clips of the full ``agcn-2s`` config.  The compiled
                step must hold a compiled kernel of every family on the clip
                path, and its logits must match the reference backend run at
                ``jax.default_matmul_precision("highest")`` within
                ``CLIP_TOL``.
  c. sessions — a ``GcnService`` on the Pallas backend (16-slot tier) serves
                8 sessions of those clips until every session closes; each
                session's post-drain logits must match the clip engine on
                the same plans within ``STREAM_TOL`` (the streaming↔clip
                parity contract ``serve stream`` checks).

``--chips 4`` runs only the multi-chip paths and what they are compared
with: the same 8 sessions through (1) one service whose slab is sharded
over a 4-device mesh, (2) a ``ReplicaRouter`` of four replicas each pinned
to its own chip, and (3) the single-chip service.  Both must match (3)
within ``MULTI_TOL``.

The last line of standard output is one JSON object naming the device as
JAX reports it; earlier lines carry compile and phase times, the compared
numbers and ``memory_stats()``.  No phase's exception is caught.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Written-down tolerances (also recorded in CHANGES.md), on logits of
# magnitude ~2.  The served path runs XLA's default TPU matmul precision
# (one bf16 pass) outside the kernels; on a v5e the first run measured
# 8.6e-3 and 3.9e-3 for the first two, so each bound has ~3.5-5x margin:
#   CLIP_TOL    max |Δlogit| of the Pallas clip step (served precision)
#               against the reference at "highest" matmul precision;
#   STREAM_TOL  max |Δlogit| of each session's post-drain logits against
#               the clip engine on the same plans;
#   MULTI_TOL   max |Δlogit| of the mesh-sharded and router services
#               against the single-chip service.
CLIP_TOL = 3e-2
STREAM_TOL = 2e-2
MULTI_TOL = 1e-3

BATCH = 8          # clips per batch (each two persons -> 16 rows)
SESSIONS = 8
SLOTS = 16
SEED = 0

KERNEL_FAMILIES = ("graph_sconv", "cavity_tconv", "rfc_encode", "rfc_decode")


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, msg: str) -> None:
    """A failed check ends the run (unlike ``assert``, never compiled out)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def require_tpu():
    """Phase a: the first device must be a TPU; return the device list."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); refusing to run on it")
    log(f"device: {devices[0].device_kind} x{len(devices)} "
        f"(jax {jax.__version__})")
    return devices


def memory_line(tag: str, devices) -> None:
    for d in devices:
        st = d.memory_stats() or {}
        log(f"memory[{tag}] {d}: in_use={st.get('bytes_in_use')} "
            f"peak={st.get('peak_bytes_in_use')} "
            f"limit={st.get('bytes_limit')}")


def build(cfg, seed: int = SEED):
    """Full-width inputs and ``quant=True`` plans for both backends, as
    ``serve clip`` builds them."""
    import jax
    import jax.numpy as jnp

    from repro.core.agcn import engine
    from repro.core.pruning.plan import plan_from_config
    from repro.data.pipeline import DataConfig, skeleton_batches
    from repro.models import registry

    prune_plan = plan_from_config(cfg)
    params = [registry.init_params(cfg, k)
              for k in jax.random.split(jax.random.PRNGKey(seed))]
    x = jnp.asarray(next(skeleton_batches(
        cfg, DataConfig(global_batch=BATCH, seq_len=cfg.gcn_frames,
                        seed=seed)))["x"])
    plans = {b: tuple(engine.build_execution_plan(p, cfg, prune_plan,
                                                  quant=True, backend=b)
                      for p in params)
             for b in ("pallas", "reference")}
    return x, plans


def clip_phase(cfg, x, plans, tol: float = CLIP_TOL):
    """Phase b: returns the Pallas clip logits (the session reference)."""
    import jax
    import numpy as np

    from repro.kernels.ops import kernel_counts
    from repro.train.steps import make_gcn_infer_step

    step = jax.jit(make_gcn_infer_step(cfg))
    t0 = time.monotonic()
    compiled = step.lower(plans["pallas"], x).compile()
    log(f"clip: pallas step compiled in {time.monotonic() - t0:.1f} s")
    counts = kernel_counts(compiled.as_text())
    log(f"clip: compiled kernels {counts}")
    missing = [k for k in KERNEL_FAMILIES if not counts.get(k)]
    check(not missing, f"no compiled kernel for {missing}")

    t0 = time.monotonic()
    got = np.asarray(jax.block_until_ready(compiled(plans["pallas"], x)))
    log(f"clip: pallas first run {time.monotonic() - t0:.3f} s")
    t0 = time.monotonic()
    jax.block_until_ready(compiled(plans["pallas"], x))
    log(f"clip: pallas warm run {time.monotonic() - t0:.4f} s "
        f"({x.shape[0]} rows, not a benchmark)")
    with jax.default_matmul_precision("highest"):
        t0 = time.monotonic()
        want = np.asarray(jax.jit(make_gcn_infer_step(cfg))(
            plans["reference"], x))
        log(f"clip: reference (highest) compiled+run in "
            f"{time.monotonic() - t0:.1f} s")
    compare("clip pallas vs reference@highest", got, want, tol)
    return got


def compare(tag: str, got, want, tol: float) -> None:
    """Assert max |Δ| ≤ tol and top-1 agreement wherever the reference's
    own top-2 margin exceeds the observed difference."""
    import numpy as np

    check(got.shape == want.shape, f"{tag}: {got.shape} vs {want.shape}")
    check(np.isfinite(got).all(), f"{tag}: non-finite logits")
    diff = float(np.max(np.abs(got - want)))
    top2 = np.sort(want, axis=-1)[:, -2:]
    margin = top2[:, 1] - top2[:, 0]
    agree = np.argmax(got, -1) == np.argmax(want, -1)
    log(f"{tag}: max|dlogit|={diff:.3e} (tol {tol:g}) "
        f"max|logit|={float(np.max(np.abs(want))):.3e} "
        f"top-1 agreement {int(agree.sum())}/{len(agree)} "
        f"min top-2 margin {float(margin.min()):.3e}")
    check(diff <= tol, f"{tag}: max|dlogit| {diff} > {tol}")
    check(np.all(agree | (margin <= 2 * diff)), f"{tag}: top-1 flipped")


def session_clips(x):
    """One body per session: rows 2i / 2i+1 are clip i's two persons."""
    import numpy as np

    return [np.asarray(x[2 * i]) for i in range(SESSIONS)]


def serve(svc, clips, tag: str):
    """Open one session per clip, submit it whole, tick until every
    session closes; return the (SESSIONS, classes) post-drain logits."""
    import numpy as np

    handles = []
    for clip in clips:
        h = svc.open_session()
        svc.submit_clip(h, clip)
        handles.append(h)
    t0 = time.monotonic()
    ticks = 0
    while not svc.idle():
        svc.tick()
        ticks += 1
    dt = time.monotonic() - t0
    states = {svc.poll(h).state for h in handles}
    check(states == {"done"}, f"{tag}: sessions ended as {states}")
    log(f"{tag}: {len(handles)} sessions drained in {ticks} ticks, "
        f"{dt:.2f} s wall")
    return np.stack([np.asarray(svc.poll(h).logits) for h in handles])


def sessions_phase(cfg, x, plans, clip_logits, tol: float = STREAM_TOL):
    """Phase c: the single-chip service against the clip engine."""
    from repro.serving import GcnService

    t0 = time.monotonic()
    svc = GcnService(cfg, backend="pallas", capacity_tiers=(SLOTS,),
                     plans=plans["pallas"], x_calib=x)
    log(f"sessions: service built (BN calibration + tick compiles) in "
        f"{time.monotonic() - t0:.1f} s")
    got = serve(svc, session_clips(x), "sessions")
    compare("sessions vs clip engine", got, clip_logits[0::2], tol)


def multi_chip_phase(cfg, x, plans, devices, tol: float = MULTI_TOL):
    """--chips 4: mesh-sharded slab and device-pinned router replicas
    against the single-chip service, on the same sessions."""
    import jax
    import numpy as np

    from repro.distributed.router import ReplicaRouter
    from repro.distributed.serving import make_batch_mesh
    from repro.serving import GcnService

    n = len(devices)
    clips = session_clips(x)
    t0 = time.monotonic()
    svc = GcnService(cfg, backend="pallas", capacity_tiers=(SLOTS,),
                     plans=plans["pallas"], x_calib=x)
    single = serve(svc, clips, "single-chip service")
    log(f"single-chip service total {time.monotonic() - t0:.1f} s")
    # the other two share the single-chip service's plans and calibration
    kw = dict(backend="pallas", capacity_tiers=(SLOTS,),
              plans=plans["pallas"], bn_stats=svc.bn_stats)

    t0 = time.monotonic()
    svc = GcnService(cfg, mesh=make_batch_mesh(n), **kw)
    sharded = serve(svc, clips, f"mesh-{n} service")
    log(f"mesh-{n} service total {time.monotonic() - t0:.1f} s; slab "
        f"devices {sorted(d.id for d in svc.slabs[0].t_raw.devices())}")
    compare(f"mesh-{n} vs single-chip", sharded, single, tol)

    t0 = time.monotonic()
    router = ReplicaRouter.build(cfg, replicas=n, **kw)
    handles = []
    for clip in clips:
        h = router.open_session()
        router.submit_clip(h, clip)
        handles.append(h)
    ticks = 0
    while not router.idle():
        router.tick()
        ticks += 1
    routed = np.stack([np.asarray(router.poll(h).logits) for h in handles])
    log(f"router: {len(handles)} sessions over {n} replicas drained in "
        f"{ticks} ticks, {time.monotonic() - t0:.1f} s total; sessions per "
        f"replica {[router.replica_of(h) for h in handles]}")
    for i, s in enumerate(router.services):
        held = {d.id for leaf in jax.tree_util.tree_leaves(
            (s.plans, s.slabs)) for d in leaf.devices()}
        log(f"router: replica {i} holds its plans and slabs on devices "
            f"{sorted(held)}")
        check(held == {devices[i].id}, f"replica {i} on devices {held}")
    compare(f"router-{n} vs single-chip", routed, single, tol)


def main(argv=None) -> None:
    from repro.launch.cache import use_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the mesh-sharded and router paths "
                         "and the single-chip service they are compared "
                         "with")
    args = ap.parse_args(argv)
    log(f"compile cache: {use_compile_cache()}")

    t_all = time.monotonic()
    devices = require_tpu()
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devices)} device(s)")
    devices = devices[:args.chips]

    from repro.configs import get_config

    cfg = get_config("agcn-2s", reduced=False)
    t0 = time.monotonic()
    x, plans = build(cfg)
    log(f"build: agcn-2s full width, x {tuple(x.shape)}, plans in "
        f"{time.monotonic() - t0:.1f} s")

    if args.chips == 1:
        t0 = time.monotonic()
        clip_logits = clip_phase(cfg, x, plans)
        log(f"phase clip: {time.monotonic() - t0:.1f} s")
        memory_line("clip", devices)
        t0 = time.monotonic()
        sessions_phase(cfg, x, plans, clip_logits)
        log(f"phase sessions: {time.monotonic() - t0:.1f} s")
        memory_line("sessions", devices)
    else:
        t0 = time.monotonic()
        multi_chip_phase(cfg, x, plans, devices)
        log(f"phase multi-chip: {time.monotonic() - t0:.1f} s")
        memory_line("multi-chip", devices)
    log(f"total: {time.monotonic() - t_all:.1f} s")

    import jax

    d0 = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
