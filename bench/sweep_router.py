#!/usr/bin/env python3
"""Find the knee of routed open-loop session traffic on the chips, once.

    python bench/sweep_router.py --config agcn2s-pruned --traffic router4 \
        --sessions 32,64,96,128,192,256,320 --seconds 6 --seed 1

As ``bench/sweep.py``, with the traffic file's ``replicas`` one-chip
replicas behind a ``ReplicaRouter`` in place of the one service
(``benchlib.kinds.routed_open_loop``): for each N in turn (ascending),
N sessions over replicas of N / ``replicas`` slots each, sharing one set
of plans and one BN calibration.  The knee is the largest N whose
frame_p95_ms stays within two frame periods, with every frame due in the
window answered, and whose per-session backlog does not grow by more than
a frame over the window; the sweep stops at the first N past it.  One
line per N, then the knee, on standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main(argv=None) -> None:
    import numpy as np

    import run as bench_run
    from benchlib import layout, program, reference, traffic
    from benchlib.cells import CALIB_ROWS, settle
    from benchlib.kinds import routed_open_loop
    from benchlib.openloop import OpenLoop

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--sessions", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    conf = bench_run.load_json(BENCH, "configs", args.config + ".json")
    tr = bench_run.load_json(BENCH, "traffic", args.traffic + ".json")
    replicas = int(tr["replicas"])
    bench_run.use_compile_cache()
    bench_run.require_chips(replicas)
    import jax.numpy as jnp

    model = conf["model"]
    cfg = program.model_config(conf)
    V, C, _ = layout.stream_shapes(model)
    T = int(model["gcn_frames"])
    period = 1.0 / float(tr["frame_hz"])
    params2 = reference.make_stream_params(model, args.seed)
    plans = program.build_plans(cfg, params2, conf)
    calib = traffic.SessionFrames(args.seed + 1, CALIB_ROWS, V, C,
                                  float(tr["frame_hz"]))
    stats = program.calibrate(plans, jnp.asarray(
        calib.clips(np.arange(CALIB_ROWS), T)))
    knee = None
    for n in [int(s) for s in args.sessions.split(",")]:
        t = time.monotonic()
        svc = routed_open_loop.build(cfg, plans, stats, n,
                                     tr.get("qos", "fifo"), replicas)
        handles = [svc.open_session() for _ in range(n)]
        svc.tick()
        svc.poll(handles[0], wait=True)
        built = time.monotonic() - t
        src = traffic.SessionFrames(args.seed, n, V, C, float(tr["frame_hz"]))
        loop = OpenLoop(svc, handles, src.frames,
                        traffic.phases(args.seed, n, period), period,
                        time.monotonic, time.sleep)
        settle()
        t0 = time.monotonic() + 0.01
        ws = t0 + float(tr["lead_in_s"])
        we = ws + args.seconds
        loop.run(t0, ws, we, we + 5.0, True, {}, {})
        s = loop.summary(ws, we)
        lat = s["latency_s"]
        p95 = 1e3 * float(np.percentile(lat, 95)) if lat.size else float("inf")
        ticks = s["ticks"]
        inw = ticks[(ticks[:, 0] >= ws) & (ticks[:, 1] < we)]

        def lag(at):
            answered = ticks[ticks[:, 1] <= at, 2].sum()
            return float(loop.due_by(at).sum() - answered) / n

        lag0, lag_end = lag(ws), lag(we)
        tick_ms = 1e3 * float(np.mean(inw[:, 1] - inw[:, 0])) if len(inw) \
            else float("nan")
        ok = (p95 <= 2e3 * period and s["answered"] == s["due"]
              and lag_end - lag0 <= 1.0)
        print(json.dumps({"sessions": n, "replicas": replicas,
                          "frame_p95_ms": p95, "tick_ms": tick_ms,
                          "ticks": len(inw), "due": s["due"],
                          "answered": s["answered"],
                          "backlog_start": lag0, "backlog_end": lag_end,
                          "router_built_s": built, "within": ok}),
              flush=True)
        del svc, handles, loop
        if not ok:
            break
        knee = n
    print(json.dumps({"knee": knee}), flush=True)


if __name__ == "__main__":
    main()
