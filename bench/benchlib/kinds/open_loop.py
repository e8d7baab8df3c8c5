"""Open-loop session traffic (``"kind": "open_loop"``) against one
``GcnService`` tier of N slots.

Traffic parameters: ``sessions`` (N), ``frame_hz``, ``qos``, ``lead_in_s``
(every session streams this long before the window opens, past the
first-logit delay), ``wait_for_window`` (drain after the window until every
frame due in it has its logits, at most ``drain_s``; otherwise stop at the
window's end and leave queued frames as capacity), ``reads_per_session``
(each session's logits are read this many times, at seeded times in the
window, and compared) and ``trace_s`` (the traced part of the window).
"""
from __future__ import annotations

import math
import time

import numpy as np

from benchlib import layout, program, reference, trace, traffic, work
from benchlib.cells import (CALIB_ROWS, CompileCounter, GcWatch, Tracer,
                            families, log_setup, memory_peak, settle, span,
                            trace_context)
from benchlib.openloop import OpenLoop

FAMILIES = ("graph_sconv", "cavity_tconv_step", "rfc_encode", "rfc_decode")
ROW_BLOCK = 16          # sessions per reference forward


def read_times(seed: int, sessions: int, reads: int, ws: float,
               seconds: float):
    """Each session's read times, ascending, uniform in the first 90% of
    the window, from the seed."""
    rng = np.random.default_rng([seed % 2 ** 63, 0x5A3])
    t = np.sort(rng.uniform(0.0, 0.9 * seconds, size=(sessions, reads)), 1)
    return {i: (ws + t[i]).tolist() for i in range(sessions)}


def run(conf, tr, seed, seconds, traced, trace_dir, log, t_start):
    import jax
    import jax.numpy as jnp

    model = conf["model"]
    quant = bool(conf["quant"])
    clock = time.monotonic
    setup = program.Clock()
    N = int(tr["sessions"])
    hz = float(tr["frame_hz"])
    period = 1.0 / hz
    V, C, classes = layout.stream_shapes(model)
    T = int(model["gcn_frames"])
    cfg = program.model_config(conf)

    with setup.phase("weights+plans"):
        params2 = reference.make_stream_params(model, seed)
        plans = program.build_plans(cfg, params2, conf)
    src = traffic.SessionFrames(seed, N + CALIB_ROWS, V, C, hz)
    x_calib = src.clips(np.arange(N, N + CALIB_ROWS), T)
    with setup.phase("calibration"):
        stats = program.calibrate(plans, jnp.asarray(x_calib))
    with setup.phase("compile"):
        compiled, per_slot = program.slab_program(cfg, plans, stats, N)
        counts = program.kernel_counts(compiled)
        del compiled
    families("slab", counts, FAMILIES, log)
    with setup.phase("service+warm"):
        svc = program.service(cfg, plans, stats, N, tr.get("qos", "fifo"))
        handles = [svc.open_session() for _ in range(N)]
        svc.tick()                    # admits every session; compiles
        svc.poll(handles[0], wait=True)
    log(f"slab: N={N} slots, per-slot state {per_slot} bytes "
        f"({per_slot * N} in all), first_logit_delay "
        f"{svc.first_logit_delay} raw frames "
        f"(reference count {layout.first_logit_frames(model)})")
    if svc.first_logit_delay != layout.first_logit_frames(model):
        raise RuntimeError("service and configuration disagree on the "
                           "first-logit delay")

    settle()
    loop = OpenLoop(svc, handles, src.frames, traffic.phases(seed, N, period),
                    period, clock, time.sleep, span)
    t0 = clock() + 0.01
    ws = t0 + float(tr["lead_in_s"])
    we = ws + seconds
    drain_end = we + float(tr.get("drain_s", 30.0))
    reads = read_times(seed, N, int(tr["reads_per_session"]), ws, seconds)
    tracer = Tracer(trace_dir, clock) if traced else None
    marks = {}
    if tracer:
        t_len = min(seconds, float(tr.get("trace_s", seconds)))
        marks = {"trace_on": (ws, tracer.on),
                 "trace_off": (ws + t_len, tracer.off)}
    CompileCounter.install()
    compiles = CompileCounter.n
    with GcWatch() as gcw:
        loop.run(t0, ws, we, drain_end, bool(tr["wait_for_window"]), reads,
                 marks)
    compiles = CompileCounter.n - compiles
    summ = loop.summary(ws, we)
    setup.phases["lead_in"] = ws - t0
    setup_s = ws - t_start
    mem = memory_peak(jax.local_devices())

    # every session's consumed-frame count, as the service reports it
    miscounted = sum(int(svc.poll(h).frames_consumed != loop.consumed[i])
                     for i, h in enumerate(handles))
    host_s = float(svc.wall_host_s)
    lat = summ["latency_s"]
    lateness = summ["lateness_s"]
    log_setup(setup, setup_s, log)
    log(f"window: {seconds} s, {summ['ticks_in_window']} ticks, "
        f"{summ['due']} frames due, {summ['answered']} answered, "
        f"{summ['served_in_window']} answered inside the window")
    if lat.size:
        log(f"frame latency ms: p50 {1e3 * np.percentile(lat, 50):.3f} "
            f"p95 {1e3 * np.percentile(lat, 95):.3f} "
            f"max {1e3 * lat.max():.3f}")
    if lateness.size:
        log(f"generator lateness ms: p95 "
            f"{1e3 * np.percentile(lateness, 95):.3f} max "
            f"{1e3 * lateness.max():.3f}")
    log(f"peak HBM {mem} bytes; host time in tick() {host_s:.3f} s; "
        f"traces+compiles while serving {compiles}")
    log(gcw.line())

    ctx = None
    if tracer:
        ticks = summ["ticks"]
        inw = (ticks[:, 0] >= tracer.t_on) & (ticks[:, 1] <= tracer.t_off)
        sel = ticks[inw]
        frames_w = float(sel[:, 2].sum())
        first = np.flatnonzero(inw)[0] if inw.any() else 0
        prev = ticks[first - 1, 3] if first > 0 else 0.0
        counters = {
            "ticks": int(inw.sum()),
            "tick_wall_s": float((sel[:, 1] - sel[:, 0]).sum()),
            "host_s": float(sel[-1, 3] - prev) if len(sel) else 0.0,
            "model_ops": 2 * frames_w / T * work.model_ops_per_row(model),
        }
        red = trace.load(trace_dir)
        ctx = trace_context(red, counters, model, 2, frames_w / T,
                            counters["ticks"], jax.devices()[0].device_kind)

    samples = loop.samples
    wanted = sum(len(v) for v in reads.values())
    unanswered = (summ["due"] - summ["answered"]
                  if tr["wait_for_window"] else 0)
    # free the program's state before the reference runs
    del svc, handles, plans, stats, loop

    rows = np.array([s[0] for s in samples], np.int64)
    seen = np.array([s[1] for s in samples], np.int64)
    got = np.stack([np.asarray(s[2], np.float32) if s[2] is not None
                    else np.full(classes, np.nan, np.float32)
                    for s in samples]) if samples else np.zeros((0, classes))
    done = np.array([layout.emitted(model, n) for n in seen], np.int64)

    def answers(num):
        """The reference's logits for every read, in ``num``."""
        if not len(rows):
            return np.zeros((0, classes), np.float32)
        ids = np.unique(rows)
        L = int(math.ceil(seen.max() / 64) * 64)
        pad = -len(ids) % ROW_BLOCK
        blk_ids = np.concatenate([ids, np.full(pad, ids[0])])
        cal = jax.jit(lambda p, x: reference.calibrate(p, x, model, quant,
                                                       num))
        feat = jax.jit(lambda p, st, fr: reference.stream_features(
            p, st, fr, model, quant, num))
        st = cal(params2, jnp.asarray(x_calib))
        parts = [feat(params2, st, jnp.asarray(src.clips(b, L)))
                 for b in blk_ids.reshape(-1, ROW_BLOCK)]
        feats2 = [jnp.concatenate([p[s] for p in parts]) for s in (0, 1)]
        pos = np.searchsorted(ids, rows)
        read = jax.jit(lambda p, f, r, d: reference.read_logits(p, f, r, d,
                                                                num))
        return np.asarray(read(params2, feats2, jnp.asarray(pos),
                               jnp.asarray(done)))

    e2e = {"setup_s": setup_s}
    if lat.size:
        e2e["frame_p95_ms"] = 1e3 * float(np.percentile(lat, 95))
    e2e["frames_per_s"] = summ["served_in_window"] / seconds
    return {"e2e": e2e, "ctx": ctx, "attempted": summ["due"],
            "failed": unanswered, "mem": mem, "got": got,
            "reference": answers,
            "checks": {"reads_missing": (wanted - len(samples), 0),
                       "sessions_miscounted": (miscounted, 0),
                       "frames_unanswered": (unanswered, 0)}}
