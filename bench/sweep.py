#!/usr/bin/env python3
"""Find the knee of the open-loop session traffic on the chip, once.

    python bench/sweep.py --config agcn2s-pruned --traffic live \
        --sessions 16,32,64,128,256,512,1024 --seconds 6 --seed 1

For each N in turn (ascending), one service of N slots runs the traffic
file's open loop with N sessions for ``--seconds``; every service shares
one set of plans and one BN calibration (``bn_stats=``), so the sweep
compiles one slab program per N and nothing else.  The knee is the largest
N whose frame_p95_ms stays within two frame periods and whose per-session
backlog does not grow over the window; the sweep stops at the first N past
it, then tries the multiples of 16 between the two (ascending,
stopping at the first past the limits) so that 4/5 and 5/4 of the knee
fall either side of the service's capacity.  One line per N, then the
knee, on standard output.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
REFINE = 16         # step of the refinement between grid points
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main(argv=None) -> None:
    import json

    import numpy as np

    import run as bench_run
    from benchlib import layout, program, reference, traffic
    from benchlib.cells import CALIB_ROWS, settle
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
    bench_run.use_compile_cache()
    bench_run.require_chips(1)
    import jax.numpy as jnp

    model = conf["model"]
    cfg = program.model_config(conf)
    V, C, _ = layout.stream_shapes(model)
    T = int(model["gcn_frames"])
    hz = float(tr["frame_hz"])
    period = 1.0 / hz
    params2 = reference.make_stream_params(model, args.seed)
    plans = program.build_plans(cfg, params2, conf)
    calib = traffic.SessionFrames(args.seed + 1, CALIB_ROWS, V, C, hz)
    stats = program.calibrate(plans, jnp.asarray(
        calib.clips(np.arange(CALIB_ROWS), T)))
    knee = None
    grid = [int(s) for s in args.sessions.split(",")]
    refine = REFINE
    tried = []
    while grid:
        n = grid.pop(0)
        tried.append(n)
        t = time.monotonic()
        svc = program.service(cfg, plans, stats, n, tr.get("qos", "fifo"))
        handles = [svc.open_session() for _ in range(n)]
        svc.tick()
        svc.poll(handles[0], wait=True)
        built = time.monotonic() - t
        src = traffic.SessionFrames(args.seed, n, V, C, hz)
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
        # per-session backlog (frames due, not yet answered) at the
        # window's start and end
        def lag(t):
            answered = ticks[ticks[:, 1] <= t, 2].sum()
            return float(loop.due_by(t).sum() - answered) / n

        lag0, lag_end = lag(ws), lag(we)
        tick_ms = 1e3 * float(np.mean(inw[:, 1] - inw[:, 0])) if len(inw) \
            else float("nan")
        ok = (p95 <= 2e3 * period and s["answered"] == s["due"]
              and lag_end - lag0 <= 1.0)
        print(json.dumps({"sessions": n, "frame_p95_ms": p95,
                          "tick_ms": tick_ms, "ticks": len(inw),
                          "due": s["due"], "answered": s["answered"],
                          "backlog_start": lag0, "backlog_end": lag_end,
                          "service_built_s": built, "within": ok}),
              flush=True)
        del svc, handles, loop
        if ok:
            knee = n
            continue
        # past the knee: try the multiples of ``refine`` between the last
        # N within the limits and this one, ascending, then stop
        grid = [m for m in range((knee or 0) + refine, n, refine)
                if m not in tried] if refine and knee else []
        refine = 0
    print(json.dumps({"knee": knee, "tried": tried}), flush=True)


if __name__ == "__main__":
    main()
