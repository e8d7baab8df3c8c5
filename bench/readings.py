#!/usr/bin/env python3
"""Readings that set a cell's limit: the compared numbers of the program
and of the control, over many seeds, in one process.

    python bench/readings.py --workload pruned-clip --seeds 11,12,13 \
        --seconds 3 [--fault answer|state|half]

For every seed the cell runs as ``run.py`` runs it (at the cell's own
sizes, with a short window) and is judged as ``run.py`` judges it.  Then
the control, the plain reference one storage step below the numerics the
configuration states (bfloat16 for float32), is put in the program's place
and judged the same way.  Each answer set is also read against the
float32 reference with unrounded operands (``exact``), and the program
against the stated numerics with the graph and temporal matmuls unrounded
(``kernels_exact``), for comparison.  ``--fault`` plants a fault of
``benchlib/faults.py`` under the timed path first, and then only the
program's judgement is read.  One JSON line per seed on standard output.  The benchmark's own runs never compute the control.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main(argv=None) -> None:
    import run as bench_run
    from benchlib import faults, reference
    from benchlib.cells import gap, run_cell

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)

    spec = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    cell, conf, tr, limits, _, _ = bench_run.cell_spec(spec, args.workload)
    bench_run.use_compile_cache()
    bench_run.require_chips(int(cell["chips"]))
    if args.fault:
        faults.plant(args.fault, setattr)
    stated = reference.stated(conf)
    others = {"exact": reference.EXACT,
              "kernels_exact": dataclasses.replace(stated, kernel_exact=True)}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        res = run_cell(conf, tr, limits, seed, args.seconds, False, "",
                       lambda m: print(m, file=sys.stderr, flush=True), t0,
                       controls=not args.fault)
        line = {"workload": args.workload, "seed": seed, "fault": args.fault,
                "correct": res["correct"], "compared": res["compared"],
                "e2e": res["e2e"]}
        if args.fault:
            print(json.dumps(line), flush=True)
            continue
        ref, got, low = res["reference"], res["got"], res["low"]
        also = {}
        for name, num in others.items():
            want = ref(num)
            also[name] = {"program": gap(got, want), "control": gap(low, want)}
        line.update(control_correct=res["control"]["correct"],
                    control_compared=res["control"]["compared"],
                    answers=int(got.size // got.shape[-1]), also=also,
                    seconds=time.monotonic() - t0)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
