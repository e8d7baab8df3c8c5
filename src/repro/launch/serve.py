"""Serving driver — subcommand CLI over the serving stack.

    PYTHONPATH=src python -m repro.launch.serve <mode> --arch ... [flags]

Modes:

  clip      — GCN batched two-stream clip inference through the execution
              engine (one ExecutionPlan per stream per backend, jitted
              ensemble step, clips/s per backend):

                  serve clip --arch agcn-2s --reduced [--backend both]

  stream    — GCN per-frame continual inference: one jitted ``step_frame``
              per backend consumes raw skeleton frames against a
              StreamState and reports frames/s, per-frame latency and
              post-drain clip-engine agreement:

                  serve stream --arch agcn-2s --reduced

  sessions  — multi-session live traffic through a
              :class:`repro.serving.GcnService`: Poisson (or bursty)
              arrivals, QoS policies (``--qos fifo|preempt|deadline``),
              and **elastic slot capacity** (``--capacity-tiers 2,4,8``:
              one pre-built slab per tier, hysteresis grow/shrink,
              session migration via snapshot/restore).  ``--mesh N``
              shards the slab tick over an N-device 1-D batch mesh (on
              a ``JAX_PLATFORMS=cpu`` run the fake-device flag is set
              automatically);
              ``--replicas R`` additionally serves the load through a
              :class:`repro.distributed.router.ReplicaRouter` over R
              service replicas with periodic drain-and-rebalance.
              Merges rows into ``BENCH_sessions.json``:

                  serve sessions --arch agcn-2s --reduced --slots 4 \\
                      [--qos preempt] [--capacity-tiers 2,4,8 --load burst] \\
                      [--mesh 4] [--replicas 2]

  lm        — LM families: batched prefill + decode with the KV cache:

                  serve lm --arch smollm-360m --reduced --prompt-len 16 --gen 32

``--batch 0`` (the default everywhere) resolves through
``ModelConfig.serve_batch`` — the one place family/mode defaults live."""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import registry
from repro.train.steps import (make_gcn_infer_step, make_gcn_stream_step,
                               make_serve_step)


def serve_gcn(arch: str, *, reduced: bool = True, batch: int = 8,
              clips: int = 64, seed: int = 0, backends=("reference", "pallas")):
    """Batched skeleton-clip inference: two-stream 2s-AGCN ensemble.

    Compiles one ExecutionPlan per (stream, backend) from the config's
    pruning plan, jits the ensemble step with the plans as pytree args, and
    measures steady-state clips/s per backend.  Returns
    {backend: {"clips_per_s": float, "top1": np.ndarray}}.
    """
    from repro.core.agcn import engine
    from repro.core.pruning.plan import plan_from_config
    from repro.data.pipeline import DataConfig, skeleton_batches

    cfg = get_config(arch, reduced=reduced)
    assert cfg.family == "gcn", f"{arch} is not a gcn-family arch"
    prune_plan = plan_from_config(cfg)
    kj, kb = jax.random.split(jax.random.PRNGKey(seed))
    params_joint = registry.init_params(cfg, kj)
    params_bone = registry.init_params(cfg, kb)

    dcfg = DataConfig(global_batch=batch, seq_len=cfg.gcn_frames, seed=seed)
    stream = skeleton_batches(cfg, dcfg)
    batches = [next(stream)["x"] for _ in range(max(1, clips // batch))]

    step = jax.jit(make_gcn_infer_step(cfg))
    results = {}
    for backend in backends:
        plans = tuple(
            engine.build_execution_plan(
                p, cfg, prune_plan, quant=True, backend=backend)
            for p in (params_joint, params_bone))
        logits = step(plans, jnp.asarray(batches[0]))   # compile
        jax.block_until_ready(logits)
        preds, n = [], 0
        t0 = time.monotonic()
        for xb in batches:
            logits = step(plans, jnp.asarray(xb))
            preds.append(np.asarray(jnp.argmax(logits, -1)))
            n += xb.shape[0]
        jax.block_until_ready(logits)
        dt = time.monotonic() - t0
        results[backend] = {
            "clips_per_s": n / dt,
            "top1": np.concatenate(preds),
        }
    return results


def serve_gcn_stream(arch: str, *, reduced: bool = True, batch: int = 4,
                     seed: int = 0, backends=("reference", "pallas")):
    """Per-frame continual inference: two-stream ensemble on a live stream.

    One ExecutionPlan per (stream, backend) is compiled from the config's
    pruning plan (quantized), the StreamStates are calibrated on the clip
    batch (frozen BN statistics), and a single jitted ``step_frame``
    consumes the clip frame-by-frame followed by the flush drain.  Returns
    {backend: {"frames_per_s", "latency_ms_p50", "latency_ms_mean",
    "clip_agreement", "top1"}} — ``clip_agreement`` is post-drain top-1
    agreement with the batched clip engine on the same plans (the streaming
    correctness contract)."""
    from repro.core.agcn import engine
    from repro.core.agcn.model import bone_stream
    from repro.core.pruning.plan import plan_from_config
    from repro.data.pipeline import DataConfig, skeleton_batches

    cfg = get_config(arch, reduced=reduced)
    assert cfg.family == "gcn", f"{arch} is not a gcn-family arch"
    prune_plan = plan_from_config(cfg)
    kj, kb = jax.random.split(jax.random.PRNGKey(seed))
    params_joint = registry.init_params(cfg, kj)
    params_bone = registry.init_params(cfg, kb)

    dcfg = DataConfig(global_batch=batch, seq_len=cfg.gcn_frames, seed=seed)
    clip = jnp.asarray(next(skeleton_batches(cfg, dcfg))["x"])
    T = clip.shape[1]
    zeros = jnp.zeros_like(clip[:, 0])

    step = jax.jit(make_gcn_stream_step(cfg))
    clip_step = jax.jit(make_gcn_infer_step(cfg))
    results = {}
    for backend in backends:
        plans = tuple(
            engine.build_execution_plan(
                p, cfg, prune_plan, quant=True, backend=backend)
            for p in (params_joint, params_bone))
        states = (
            engine.init_stream_state(plans[0], batch, x_calib=clip),
            engine.init_stream_state(plans[1], batch,
                                     x_calib=bone_stream(clip)),
        )
        total = T + engine.stream_flush_frames(plans[0], T)
        # compile both validity variants before timing
        _ = step(plans, states, clip[:, 0], jnp.asarray(True))
        warm, logits = step(plans, states, zeros, jnp.asarray(False))
        jax.block_until_ready(logits)
        lat = []
        for r in range(total):
            frame = clip[:, r] if r < T else zeros
            t0 = time.monotonic()
            states, logits = step(plans, states, frame, jnp.asarray(r < T))
            jax.block_until_ready(logits)
            lat.append(time.monotonic() - t0)
        lat_ms = np.sort(np.asarray(lat)) * 1e3
        stream_top1 = np.asarray(jnp.argmax(logits, -1))
        clip_top1 = np.asarray(jnp.argmax(clip_step(plans, clip), -1))
        results[backend] = {
            # one step advances every stream in the batch by one frame:
            # aggregate frame throughput, latency is the per-step wall time
            "frames_per_s": batch * total / float(np.sum(lat)),
            "latency_ms_p50": float(lat_ms[len(lat_ms) // 2]),
            "latency_ms_mean": float(lat_ms.mean()),
            "clip_agreement": float((stream_top1 == clip_top1).mean()),
            "top1": stream_top1,
        }
    return results


def serve_gcn_sessions(arch: str, *, reduced: bool = True, slots: int = 4,
                       n_sessions: int = 0, rate: float = 0.0, seed: int = 0,
                       backends=("reference", "pallas"), qos: str = "fifo",
                       preempt_ratio: float = 0.25, deadline_slack: int = 25,
                       capacity_tiers=None, load: str = "poisson",
                       mesh: int = 0, replicas: int = 1,
                       policy: str = "demand", slo_config=None,
                       trace: str = "", topology: str = "",
                       use_ck: bool = False, saliency_thresh: float = 0.0):
    """Multi-session stream serving through :class:`repro.serving.GcnService`.

    One service per backend (two-stream ensemble) under the ``qos`` policy
    (``fifo`` run-to-completion, ``preempt`` priority snapshot-eviction,
    ``deadline`` expiry drops).  ``capacity_tiers`` (e.g. ``(2, 4, 8)``)
    makes the service **elastic**: one pre-built slab per tier, hysteresis
    grow/shrink on queue depth + occupancy, and active-session migration
    across tiers via the engine's snapshot/restore; ``slots`` alone is a
    fixed-capacity run.  ``load`` picks the arrival process (``poisson``
    steady vs ``burst`` peaks-and-lulls — the elastic stress shape).
    ``mesh > 1`` shards the slab tick over a 1-D device mesh (the row
    gains ``mesh`` + ``collective_ms_per_tick``); ``replicas > 1`` also
    runs the load through a :class:`~repro.distributed.router.
    ReplicaRouter` and appends the merged routed row (``replicas`` +
    ``rebalances`` axes).

    ``trace`` replays a recorded :class:`~repro.serving.Trace` file
    byte-identically instead of generating load (``--trace FILE``): the
    arrivals, clip lengths, priorities and clip bytes are pinned by the
    trace, so two invocations differing only in ``policy`` A/B the
    controllers on identical traffic.  ``policy="slo"`` swaps the
    demand-driven capacity manager for the :class:`~repro.serving.
    SloController` (grow on measured p99 first-logit regression, shed via
    admission control at the top tier).  ``topology`` names a registered
    skeleton (``repro.core.agcn.graph``, e.g. ``ntu50`` / ``hand21``) —
    the service compiles its plans for that graph and generates matching
    clips; default is the NTU 25-joint skeleton.

    The adaptive-streaming knobs: ``use_ck`` (``--ck``) serves with the
    windowed data-dependent C_k graph (``repro.core.agcn.adaptive``; the
    published whole-clip form, ``ck_form="clip"``, has no stream to run
    on and is refused) and
    ``saliency_thresh`` (``--saliency-thresh``) > 0 skips uninformative
    frames per session through a :class:`~repro.serving.saliency.
    SaliencyGate` — both tag the merged rows (``ck``/``saliency`` axes)
    only when on, so feature-off rows are byte-identical to before the
    knobs existed.  Returns the metrics dicts from
    :func:`repro.serving.run_sessions` / :func:`repro.serving.replay`
    (and the routed runs) and merges them into ``BENCH_sessions.json``."""
    from repro.serving import Trace, replay, run_sessions, write_bench

    import dataclasses

    cfg = get_config(arch, reduced=reduced)
    assert cfg.family == "gcn", f"{arch} is not a gcn-family arch"
    if use_ck and not cfg.use_ck:
        # both paths build plans from cfg, so the flag rides replay too
        cfg = dataclasses.replace(cfg, use_ck=True)
    if trace:
        if topology:
            raise ValueError("--topology is not available with --trace: a "
                             "recorded trace pins its clip bytes to the "
                             "skeleton it was captured with")
        rec = Trace.load(trace)
        results = [
            replay(cfg, rec, backend=backend, qos=qos, policy=policy,
                   capacity_tiers=tuple(capacity_tiers or (slots,)),
                   slo_config=slo_config, deadline_slack=deadline_slack,
                   seed=seed, saliency_thresh=saliency_thresh)
            for backend in backends
        ]
        write_bench(results)
        return results
    n = n_sessions or 3 * slots
    # default mean inter-arrival ~ clip_len / slots keeps the slab busy
    # without unbounded queueing (offered load ≈ capacity)
    mean_gap = rate if rate > 0 else max(2.0, cfg.gcn_frames / slots)
    results = []
    for backend in backends:
        r = run_sessions(cfg, slots=slots, n_sessions=n,
                         mean_interarrival=mean_gap, backend=backend,
                         seed=seed, qos=qos, preempt_ratio=preempt_ratio,
                         deadline_slack=deadline_slack,
                         capacity_tiers=capacity_tiers, load=load,
                         mesh=mesh, policy=policy, slo_config=slo_config,
                         topology=topology or None, use_ck=use_ck,
                         saliency_thresh=saliency_thresh)
        results.append(r)
        if replicas > 1:
            if topology:
                raise ValueError("--topology is not threaded through the "
                                 "replica router yet — drop --replicas")
            from repro.distributed.router import run_routed_sessions
            results.append(run_routed_sessions(
                cfg, replicas=replicas, slots=slots, n_sessions=n,
                mean_interarrival=mean_gap, backend=backend, seed=seed,
                qos=qos, preempt_ratio=preempt_ratio,
                deadline_slack=deadline_slack,
                capacity_tiers=capacity_tiers, load=load))
    write_bench(results)
    return results


def generate(arch: str, *, reduced: bool = True, batch: int = 4,
             prompt_len: int = 16, gen: int = 32, seed: int = 0,
             greedy: bool = True, temperature: float = 1.0):
    cfg = get_config(arch, reduced=reduced)
    if cfg.family == "gcn":
        raise ValueError(f"{arch} is a gcn-family arch — use "
                         "`serve clip|stream|sessions`, not `serve lm`")
    key = jax.random.PRNGKey(seed)
    params = registry.init_params(cfg, key)
    max_len = prompt_len + gen
    cache = registry.init_cache(cfg, batch, max_len, jnp.float32)
    serve = jax.jit(make_serve_step(cfg), donate_argnums=(1,))

    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab_size, size=(batch, prompt_len))
    extra = {}
    if cfg.family == "audio":
        extra["memory"] = jnp.asarray(
            rng.standard_normal((batch, cfg.encoder_frames, cfg.d_model)),
            jnp.float32)

    # prefill token-by-token through the same step (functional parity with
    # the chunked prefill exercised by the prefill_32k dry-run cells)
    tok = jnp.asarray(prompt[:, :1], jnp.int32)
    out_tokens = [np.asarray(tok)]
    t0 = time.monotonic()
    for pos in range(max_len - 1):
        b = {"tokens": tok, "pos": jnp.asarray(pos, jnp.int32), **extra}
        next_tok, cache = serve(params, cache, b)
        if pos + 1 < prompt_len:
            tok = jnp.asarray(prompt[:, pos + 1 : pos + 2], jnp.int32)
        else:
            tok = next_tok[:, None]
        out_tokens.append(np.asarray(tok))
    dt = time.monotonic() - t0
    seqs = np.concatenate(out_tokens, axis=1)
    tps = batch * (max_len - 1) / dt
    return seqs, tps


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _parse_tiers(spec: str):
    """``"2,4,8"`` -> (2, 4, 8); empty/None -> None (fixed capacity)."""
    if not spec:
        return None
    return tuple(int(t) for t in spec.split(","))


def _ensure_fake_devices(n: int) -> None:
    """On a CPU run (``JAX_PLATFORMS=cpu``), make at least ``n`` host
    devices visible for ``--mesh n``; a run on chips uses the real ones.

    Must run before jax's backend initializes (the flag is read once);
    a user-provided ``--xla_force_host_platform_device_count`` wins.  If
    the platform still comes up short, ``make_batch_mesh`` raises with
    the same flag in the message."""
    if n <= 1 or os.environ.get("JAX_PLATFORMS", "").split(",") != ["cpu"]:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n}"
        + (f" {flags}" if flags else ""))


def _add_common(ap: argparse.ArgumentParser) -> None:
    from repro.core.agcn.engine import BACKENDS

    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=0,
                    help="0 -> family/mode default "
                         "(ModelConfig.serve_batch, the single source)")
    ap.add_argument("--backend", default="both", choices=(*BACKENDS, "both"),
                    help="gcn: engine backend(s) to serve with")


def build_parser() -> argparse.ArgumentParser:
    """The subcommand CLI: ``serve clip|stream|sessions|lm [flags]``."""
    from repro.serving import CONTROL_POLICIES, QOS_POLICIES, SHED_MODES

    ap = argparse.ArgumentParser(prog="repro.launch.serve")
    sub = ap.add_subparsers(dest="mode", required=True)

    p = sub.add_parser("clip", help="gcn: batched two-stream clip inference")
    _add_common(p)
    p.add_argument("--clips", type=int, default=64,
                   help="total clips to drain per backend")

    p = sub.add_parser("stream", help="gcn: per-frame continual inference")
    _add_common(p)

    p = sub.add_parser("sessions",
                       help="gcn: multi-session traffic through GcnService")
    _add_common(p)
    p.add_argument("--slots", type=int, default=4,
                   help="slot capacity of a fixed run (with "
                        "--capacity-tiers the capacity comes from the "
                        "tiers instead, but --slots still sets the load "
                        "defaults: --n-sessions 3×slots, --rate "
                        "clip_len/slots)")
    p.add_argument("--n-sessions", type=int, default=0,
                   help="total sessions to serve (default 3×slots)")
    p.add_argument("--rate", type=float, default=0.0,
                   help="mean inter-arrival ticks (0 -> clip_len/slots)")
    p.add_argument("--qos", default="fifo", choices=QOS_POLICIES,
                   help="scheduler policy: fifo run-to-completion, preempt "
                        "(priority snapshot-eviction), deadline (expiry "
                        "drops)")
    p.add_argument("--preempt-ratio", type=float, default=0.25,
                   help="fraction of high-priority sessions in the "
                        "generated load (every policy — a fifo run with "
                        "the same seed baselines a preempt run)")
    p.add_argument("--deadline-slack", type=int, default=25,
                   help="extra ticks past each session's minimal service "
                        "time before its deadline")
    p.add_argument("--capacity-tiers", default="",
                   help="comma-separated slot tiers, e.g. 2,4,8 — enables "
                        "elastic capacity (pre-built slab per tier, "
                        "hysteresis grow/shrink, snapshot/restore "
                        "migration)")
    p.add_argument("--load", default="poisson", choices=("poisson", "burst"),
                   help="arrival process: steady poisson or bursty "
                        "peaks-and-lulls (the elastic stress shape)")
    p.add_argument("--trace", default="",
                   help="replay a recorded Trace JSON file instead of "
                        "generating load — arrivals, lengths, priorities "
                        "and clip bytes are pinned by the trace, so runs "
                        "differing only in --policy A/B the controllers "
                        "on identical traffic")
    p.add_argument("--policy", default="demand", choices=CONTROL_POLICIES,
                   help="capacity control: demand (grow on raw "
                        "busy+queued) or slo (grow on measured p99 "
                        "first-logit regression, shed low-priority opens "
                        "via admission control at the top tier)")
    p.add_argument("--slo-target", type=int, default=0,
                   help="SLO bound: p99 arrival→first-logit latency in "
                        "scheduler ticks (0 -> SloConfig default; only "
                        "with --policy slo)")
    p.add_argument("--slo-window", type=int, default=0,
                   help="sliding latency-sample window of the SLO "
                        "controller (0 -> SloConfig default)")
    p.add_argument("--slo-shed-mode", default="", choices=("", *SHED_MODES),
                   help="what shedding does to low-priority opens: reject "
                        "turns them away, degrade serves every stride-th "
                        "frame (default: SloConfig default)")
    p.add_argument("--mesh", type=int, default=0,
                   help="shard the slab tick over an N-device 1-D batch "
                        "mesh (0/1 -> single device; with "
                        "JAX_PLATFORMS=cpu the fake-device XLA flag is "
                        "set automatically)")
    p.add_argument("--replicas", type=int, default=1,
                   help="also serve the load through a ReplicaRouter over "
                        "R service replicas (adds the routed BENCH row)")
    p.add_argument("--topology", default="",
                   help="registered skeleton topology to serve (e.g. "
                        "ntu25, ntu50, hand21, body_hand46) — plans "
                        "compile for that graph and the generated clips "
                        "match its joint count (default: ntu25)")
    p.add_argument("--ck", action="store_true",
                   help="serve with the windowed data-dependent C_k graph "
                        "(repro.core.agcn.adaptive) folded into every "
                        "block's spatial conv")
    p.add_argument("--saliency-thresh", type=float, default=0.0,
                   help="> 0 skips uninformative frames per session below "
                        "this attention-ratio threshold "
                        "(repro.serving.saliency; default 0 = off)")

    p = sub.add_parser("lm", help="LM families: prefill + decode")
    _add_common(p)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--gen", type=int, default=32)
    return ap


def _print_sessions(results) -> None:
    for r in results:
        cap = (f" capacity={r['capacity']}" if r["capacity"] != "fixed"
               else "")
        if r.get("replicas", 1) > 1:
            # merged router row: totals + percentiles only (per-replica
            # detail rides under "per_replica" in the BENCH row)
            print(f"backend={r['backend']} [sessions routed "
                  f"replicas={r['replicas']} qos={r['qos']}{cap}]: "
                  f"{r['sessions']} sessions over "
                  f"{r['replicas']}x{r['slots']} slots, "
                  f"{r['frames_per_s']:.1f} frames/s aggregate, "
                  f"occupancy {r['occupancy']*100:.0f}%, "
                  f"latency p50={r['latency_ms_p50']:.0f}ms "
                  f"p99={r['latency_ms_p99']:.0f}ms, "
                  f"{r['rebalances']} rebalance moves")
            continue
        mesh = f" mesh={r['mesh']}" if r.get("mesh", 1) > 1 else ""
        pol = (f" policy=slo trace={r.get('trace', '')}"
               if r.get("policy", "demand") != "demand"
               else (f" trace={r['trace']}" if r.get("trace") else ""))
        if r.get("ck"):
            mesh += " ck"
        if r.get("saliency"):
            mesh += (f" saliency={r['saliency']} "
                     f"(skip {r['skip_rate']*100:.0f}%)")
        print(f"backend={r['backend']} [sessions{mesh}{pol} qos={r['qos']}"
              f"{cap} load={r['load']}]: "
              f"{r['sessions']} sessions over {r['slots']} slots, "
              f"{r['frames_per_s']:.1f} frames/s aggregate, "
              f"occupancy {r['occupancy']*100:.0f}% time-weighted "
              f"({r['occupancy_busy']*100:.0f}% busy), "
              f"session latency p50={r['latency_ms_p50']:.0f}ms "
              f"p99={r['latency_ms_p99']:.0f}ms, "
              f"first-logit p50={r['first_logit_ms_p50']:.0f}ms "
              f"({r['first_logit_frames']} frames, "
              f"{r['sessions_no_first_logit']} without), "
              f"queue wait {r['queue_wait_ticks_mean']:.1f} ticks")
        for p, pl in sorted(r["latency_ms_by_priority"].items()):
            print(f"  priority {p}: n={pl['n']} "
                  f"p50={pl['p50_ms']:.0f}ms p99={pl['p99_ms']:.0f}ms "
                  f"(arrival→finish p50={pl['e2e_p50_ticks']:.0f} "
                  f"p99={pl['e2e_p99_ticks']:.0f} ticks, "
                  f"first-logit p99={pl['first_logit_p99_ticks']:.0f} "
                  f"ticks)")
        if r.get("policy", "demand") == "slo":
            print(f"  slo: target p99 {r['slo_target_p99_ticks']} ticks, "
                  f"shed_mode={r['shed_mode']} "
                  f"rejected={r['sessions_rejected']} "
                  f"degraded={r['sessions_degraded']} "
                  f"({r['shed_windows']} shed windows)")
        if r["qos"] == "preempt":
            print(f"  preemptions={r['preemptions']} "
                  f"restores={r['restores']}")
        if r["qos"] == "deadline":
            print(f"  deadline missed={r['deadline_missed']} "
                  f"(miss rate {r['deadline_miss_rate']*100:.0f}%)")
        if r["capacity"] != "fixed":
            print(f"  elastic: {r['migrations_grow']} grows / "
                  f"{r['migrations_shrink']} shrinks, "
                  f"migration {r['migration_ms_mean']:.1f}ms mean, "
                  f"final capacity {r['capacity_final']}, "
                  f"tier ticks {r['tier_ticks']}")
        if r.get("mesh", 1) > 1:
            print(f"  sharded: {r['mesh']} devices, collective cost "
                  f"{r['collective_ms_per_tick']:.2f}ms/tick")
    print("# merged BENCH_sessions.json")


def main(argv=None):
    """CLI entry: ``serve clip|stream|sessions|lm [flags]``."""
    from repro.core.agcn.engine import BACKENDS
    from repro.launch.cache import use_compile_cache

    use_compile_cache()

    argv = list(sys.argv[1:] if argv is None else argv)
    args = build_parser().parse_args(argv or ["-h"])
    cfg = get_config(args.arch, reduced=args.reduced)
    backends = BACKENDS if args.backend == "both" else (args.backend,)

    if args.mode == "sessions":
        assert cfg.family == "gcn", f"{args.arch} is not a gcn-family arch"
        _ensure_fake_devices(getattr(args, "mesh", 0))
        slo_config = None
        if getattr(args, "policy", "demand") == "slo":
            from repro.serving import SloConfig
            overrides = {}
            if getattr(args, "slo_target", 0):
                overrides["target_p99_ticks"] = args.slo_target
            if getattr(args, "slo_window", 0):
                overrides["window"] = args.slo_window
            if getattr(args, "slo_shed_mode", ""):
                overrides["shed_mode"] = args.slo_shed_mode
            slo_config = SloConfig(**overrides)
        results = serve_gcn_sessions(
            args.arch, reduced=args.reduced, slots=args.slots,
            n_sessions=args.n_sessions, rate=args.rate, backends=backends,
            qos=args.qos, preempt_ratio=args.preempt_ratio,
            deadline_slack=args.deadline_slack,
            capacity_tiers=_parse_tiers(args.capacity_tiers),
            load=args.load, mesh=getattr(args, "mesh", 0),
            replicas=getattr(args, "replicas", 1),
            policy=getattr(args, "policy", "demand"), slo_config=slo_config,
            trace=getattr(args, "trace", ""),
            topology=getattr(args, "topology", ""),
            use_ck=getattr(args, "ck", False),
            saliency_thresh=getattr(args, "saliency_thresh", 0.0))
        _print_sessions(results)
        return
    if args.mode == "stream":
        assert cfg.family == "gcn", f"{args.arch} is not a gcn-family arch"
        batch = cfg.serve_batch("stream", args.batch)
        res = serve_gcn_stream(args.arch, reduced=args.reduced,
                               batch=batch, backends=backends)
        for name, r in res.items():
            print(f"backend={name} [stream]: "
                  f"{r['frames_per_s']:.1f} frames/s "
                  f"({batch} streams), per-frame latency "
                  f"p50={r['latency_ms_p50']:.2f}ms "
                  f"mean={r['latency_ms_mean']:.2f}ms, "
                  f"clip-engine top-1 agreement "
                  f"{r['clip_agreement']*100:.1f}%")
        if len(res) == 2:
            a, b = (res[k]["top1"] for k in ("reference", "pallas"))
            print("backend top-1 agreement: "
                  f"{float((a == b).mean())*100:.1f}%")
        return
    if args.mode == "clip":
        assert cfg.family == "gcn", f"{args.arch} is not a gcn-family arch"
        res = serve_gcn(args.arch, reduced=args.reduced,
                        batch=cfg.serve_batch("clip", args.batch),
                        clips=args.clips, backends=backends)
        for name, r in res.items():
            print(f"backend={name}: {r['clips_per_s']:.1f} clips/s "
                  f"({len(r['top1'])} clips, 2-stream ensemble)")
        if len(res) == 2:
            a, b = (res[k]["top1"] for k in ("reference", "pallas"))
            agree = float((a == b).mean())
            print(f"backend top-1 agreement: {agree*100:.1f}%")
        return
    seqs, tps = generate(args.arch, reduced=args.reduced,
                         batch=cfg.serve_batch("lm", args.batch),
                         prompt_len=args.prompt_len, gen=args.gen)
    print(f"generated {seqs.shape} tokens at {tps:.1f} tok/s")
    print("sample:", seqs[0, : args.prompt_len + 8].tolist())


if __name__ == "__main__":
    main()
