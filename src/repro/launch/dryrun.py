import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS",
    "--xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: .lower().compile() every (arch × shape × mesh) cell.

MUST be run as its own process (the XLA_FLAGS line above executes before any
jax import, and jax locks the device count on first init):

    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh both --out experiments/dryrun

Each cell lowers the full production step function with real in/out
shardings (ShapeDtypeStruct inputs — no allocation), compiles it, and
records memory_analysis / cost_analysis / the collective schedule parsed
from the compiled HLO into one JSON per cell.
"""
import argparse
import json
import pathlib
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common.config import GCN_SHAPES, SHAPES, ModelConfig, TrainConfig
from repro.configs import (
    CONFIGS, applicable_shapes, get_config, input_specs, shape_applicable,
)
from repro.distributed import sharding as shd
from repro.distributed.params import param_shardings
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import roofline_terms
from repro.models import registry
from repro.optim import adamw
from repro.train.steps import make_prefill_step, make_serve_step, make_train_step

# the dry run compiles on fake host devices and projects onto v5e pods
PROJECTED_DEVICE_KIND = "TPU v5 lite"


def _rules_for(cfg: ModelConfig, shape_name: str, mesh) -> Dict:
    rules = dict(shd.DEFAULT_RULES)
    shp = (GCN_SHAPES | SHAPES)[shape_name]
    batch = shp.global_batch * (cfg.gcn_persons if cfg.family == "gcn" else 1)
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    if cfg.sharding == "dp_only":
        # weights replicated; every mesh axis carries batch
        rules["batch"] = ("pod", "data", "model")
        dp *= mesh.shape.get("model", 1)
    if batch % dp != 0:
        # tiny-batch decode (long_500k): shard the KV sequence instead
        rules["batch"] = None
        rules["kv_seq"] = "data"
    return rules


def _shardings_for_tree(tree_axes, mesh):
    return jax.tree_util.tree_map(
        lambda axes: NamedSharding(mesh, shd.logical_spec(*axes)),
        tree_axes,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def model_flops(cfg: ModelConfig, shape_name: str) -> float:
    shp = (GCN_SHAPES | SHAPES)[shape_name]
    n_active = cfg.active_param_count_estimate()
    if cfg.family == "gcn":
        tokens = shp.global_batch * cfg.gcn_persons * (
            cfg.gcn_frames // max(1, cfg.input_skip))
        return 2.0 * n_active * tokens * (3 if shp.kind == "train" else 1)
    if shp.kind == "train":
        return 6.0 * n_active * shp.global_batch * shp.seq_len
    if shp.kind == "prefill":
        return 2.0 * n_active * shp.global_batch * shp.seq_len
    return 2.0 * n_active * shp.global_batch        # decode: one token


def model_bytes(cfg: ModelConfig, shape_name: str) -> float:
    """Mandatory per-step HBM traffic (whole system): params must be read
    once (weights); decode additionally reads the KV/state cache; train
    reads params + writes grads + touches fp32 moments (~2+2+8+8 B/param)."""
    shp = (GCN_SHAPES | SHAPES)[shape_name]
    n = cfg.param_count_estimate()
    if shp.kind == "train":
        return n * 20.0
    base = n * 2.0
    if shp.kind == "decode" and cfg.family not in ("gcn",):
        # KV cache bytes (attention archs) or state bytes (ssm/hybrid)
        if cfg.family in ("dense", "moe", "vlm", "audio"):
            layers = cfg.num_layers
            kv_len = shp.seq_len
            if cfg.window_size > 0 and cfg.local_global_ratio == 0:
                kv_len = min(kv_len, cfg.window_size)   # SWA ring buffer
            base += (2 * layers * shp.global_batch * kv_len
                     * cfg.num_kv_heads * cfg.head_dim * 2.0)
        elif cfg.family == "hybrid":
            ng = cfg.num_layers // (cfg.shared_attn_every + 1)
            base += (2 * ng * shp.global_batch * shp.seq_len
                     * cfg.num_kv_heads * cfg.head_dim * 2.0)
            base += (cfg.num_layers * shp.global_batch
                     * (cfg.ssm_expand * cfg.d_model) * cfg.ssm_state * 4.0)
        elif cfg.family == "ssm":
            d_inner = cfg.ssm_expand * cfg.d_model
            dh = d_inner // cfg.num_heads
            base += (cfg.num_layers * shp.global_batch * cfg.num_heads
                     * dh * (dh + 1) * 4.0)
    return base


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: pathlib.Path, verbose: bool = True,
             dump_hlo: bool = False) -> Optional[Dict]:
    cfg = get_config(arch)
    ok, reason = shape_applicable(cfg, shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell_id = f"{cfg.name}__{shape_name}__{mesh_name}"
    out_path = out_dir / f"{cell_id}.json"
    if not ok:
        rec = {"cell": cell_id, "status": "skipped", "reason": reason}
        out_path.write_text(json.dumps(rec, indent=2))
        if verbose:
            print(f"[skip] {cell_id}: {reason}")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    shp = (GCN_SHAPES | SHAPES)[shape_name]
    tcfg = TrainConfig()
    t0 = time.time()

    with shd.axis_rules(mesh, _rules_for(cfg, shape_name, mesh)):
        dtype = jnp.bfloat16
        params_shape = jax.eval_shape(
            lambda: registry.init_params(cfg, jax.random.PRNGKey(0), dtype))
        # ZeRO-2 (TP-only params) exists to keep the fp32 optimizer states
        # 2D-sharded — inference cells have no optimizer, and 1D weights
        # push GSPMD into weight-gather + full-width compute inside scans
        # (EXPERIMENTS §Perf open item), so they keep 2D weights.
        if cfg.sharding == "2d":
            policy = "zero2" if shp.kind == "train" else "2d"
        else:
            policy = cfg.sharding
        p_shardings = param_shardings(
            params_shape, mesh,
            expert_dim=cfg.padded_experts or None, policy=policy)
        batch_shape, batch_axes = input_specs(cfg, shape_name)
        b_shardings = _shardings_for_tree(batch_axes, mesh)

        if shp.kind == "train":
            # gradient accumulation so the activation temp fits the
            # 16 GB/chip HBM budget (global batch preserved)
            tcfg = TrainConfig(microbatches=cfg.train_microbatches)
            opt_shape = jax.eval_shape(adamw.init, params_shape)
            # ZeRO-2: fp32 moments stay fully (2D) sharded even when the
            # bf16 params are TP-only — one reshard per step at the update;
            # gradients are constrained to the same 2D specs so the data-
            # parallel grad sync lowers as reduce-scatter, not all-reduce
            opt_policy = "2d" if cfg.sharding != "dp_only" else "dp_only"
            o_2d = param_shardings(
                params_shape, mesh,
                expert_dim=cfg.padded_experts or None, policy=opt_policy)
            step = make_train_step(cfg, tcfg, grad_shardings=o_2d)
            o_shardings = adamw.OptState(
                step=NamedSharding(mesh, P()), m=o_2d, v=o_2d)
            jitted = jax.jit(
                step,
                in_shardings=(p_shardings, o_shardings, b_shardings),
                donate_argnums=(0, 1),
            )
            args = (params_shape, opt_shape, batch_shape)
        elif shp.kind == "prefill":
            step = make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(p_shardings, b_shardings))
            args = (params_shape, batch_shape)
        else:                                        # decode
            step = make_serve_step(cfg)
            cache_shape = jax.eval_shape(
                lambda: registry.init_cache(
                    cfg, shp.global_batch, shp.seq_len, jnp.bfloat16))
            c_shardings = _shardings_for_tree(registry.cache_specs(cfg), mesh)
            # align spec tree ranks with cache tree (specs are per-leaf tuples)
            jitted = jax.jit(
                step,
                in_shardings=(p_shardings, c_shardings, b_shardings),
                donate_argnums=(1,),
            )
            args = (params_shape, cache_shape, batch_shape)

        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    hc = analyze_hlo(hlo)   # trip-count-aware static analysis (per chip)
    terms = roofline_terms(hc, model_flops(cfg, shape_name), chips,
                           PROJECTED_DEVICE_KIND,
                           model_bytes_total=model_bytes(cfg, shape_name))
    if dump_hlo:
        (out_dir / f"{cell_id}.hlo").write_text(hlo)

    mem_rec = {}
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                mem_rec[attr] = int(v)

    rec = {
        "cell": cell_id,
        "status": "ok",
        "arch": cfg.name,
        "shape": shape_name,
        "mesh": mesh_name,
        "chips": chips,
        "kind": shp.kind,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory_analysis": mem_rec or str(mem),
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "roofline": terms,
    }
    out_path.write_text(json.dumps(rec, indent=2))
    if verbose:
        dom = terms["dominant"]
        print(
            f"[ok]   {cell_id}: compile={t_compile:.1f}s "
            f"flops={terms['hlo_flops']:.3e} coll={terms['collective_bytes']:.3e}B "
            f"dominant={dom} roofline_frac={terms['roofline_fraction']:.3f}"
        )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--dump-hlo", action="store_true")
    args = ap.parse_args()

    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    archs = list(CONFIGS) if args.arch == "all" else [args.arch]
    meshes = (
        [False, True] if args.mesh == "both"
        else [args.mesh == "multi"]
    )

    failures = []
    for arch in archs:
        cfg = get_config(arch)
        if args.shape == "all":
            pool = GCN_SHAPES if cfg.family == "gcn" else SHAPES
            shapes = list(pool)          # run_cell records skips with reason
        else:
            shapes = [args.shape]
        for shape_name in shapes:
            for mp in meshes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                cell = f"{cfg.name}__{shape_name}__{mesh_name}"
                if args.skip_existing and (out_dir / f"{cell}.json").exists():
                    print(f"[keep] {cell}")
                    continue
                try:
                    run_cell(arch, shape_name, mp, out_dir,
                             dump_hlo=args.dump_hlo)
                except Exception as e:  # noqa: BLE001 — record and continue
                    failures.append((cell, repr(e)))
                    (out_dir / f"{cell}.json").write_text(json.dumps(
                        {"cell": cell, "status": "error", "error": repr(e),
                         "traceback": traceback.format_exc()}, indent=2))
                    print(f"[FAIL] {cell}: {e}")

    print(f"\n{len(failures)} failures")
    for c, e in failures:
        print(f"  {c}: {e[:200]}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
