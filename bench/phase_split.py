#!/usr/bin/env python3
"""One traced run of a session cell, with the serving tick split into its
phases and the device's idle time named by the program's spans too.

    python bench/phase_split.py --workload pruned-live --seed <n> \
        --seconds <s>

The cell runs as ``run.py --trace 1`` runs it, and the last line of
standard output is one JSON object: ``line``, the result line ``run.py``
prints, and from the same trace ``phase_ms`` (milliseconds per tick of
each ``svc.*`` phase span inside the window's ticks), ``ticks``, and
``idle_gaps``: idle seconds per naming span when ``svc.*`` spans name the
gaps beside the ``bench.*`` spans (the line's ``breakdown`` names them by
the ``bench.*`` spans alone).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

T_START = time.monotonic()

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main(argv=None) -> None:
    import run as bench_run
    from benchlib import phases
    from benchlib.cells import run_cell

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    spec = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    cell, conf, tr, limits, e2e, layer = bench_run.cell_spec(
        spec, args.workload)
    bench_run.use_compile_cache()
    devices = bench_run.require_chips(int(cell["chips"]))
    devices = devices[: int(cell["chips"])]
    trace_dir = os.path.join(bench_run.ROOT, ".bench_trace", cell["name"])

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    res = run_cell(conf, tr, limits, args.seed, args.seconds, True,
                   trace_dir, log, T_START)
    line = bench_run.result_line(res, e2e, layer, True, devices)
    path = phases.newest_trace(trace_dir)
    ev = phases.load(path)
    got = phases.tick_phases(ev["host"])
    ticks, phase_s = got if got else (0, {})
    out = {"line": line, "ticks": ticks,
           "phase_ms": {p: 1e3 * s / ticks for p, s in phase_s.items()},
           "idle_gaps": phases.by_span(phases.idle_gaps(ev["ops"],
                                                        ev["host"]))}
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
