"""Benchmark harness entry point — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--only MODULE]

With ``--only MODULE`` the module's rows are also written to
``BENCH_<MODULE>.json`` (e.g. ``--only kernels_bench`` →
``BENCH_kernels_bench.json`` with the backend-comparison rows); ``--json``
forces the dump for a full run (one file per module).  ``--smoke`` times a
single iteration per row — ``test.sh`` runs ``--only kernels --smoke`` so
the json emission path cannot silently rot.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

from benchmarks import common

MODULES = [
    "pruning_bench",      # Fig. 8/9/10 — hybrid pruning
    "agcn_ablation",      # Table I    — C_k cost
    "rfc_storage",        # Table III + Fig. 11 — RFC storage
    "dyn_sched",          # Table II   — Dyn-Mult-PE sizing
    "throughput",         # Tables IV/V — throughput & peak perf
    "kernels_bench",      # kernel micro-benchmarks
]


def main() -> None:
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run one module (accepts 'kernels' for kernels_bench)")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<module>.json for every module run")
    ap.add_argument("--smoke", action="store_true",
                    help="single timed iteration per row (fast end-to-end "
                         "check that BENCH json emission still works)")
    ap.add_argument("--out-dir", default=".",
                    help="directory for the BENCH_<module>.json artifacts — "
                         "smoke runs should point this at a temp dir so "
                         "1-iteration timings never overwrite the checked-in "
                         "artifacts (see test.sh)")
    # unknown flags (e.g. --backend) pass through to the modules' own parsers
    args, _ = ap.parse_known_args()
    if args.smoke:
        common.SMOKE = True
    only = args.only
    if only and only not in MODULES and f"{only}_bench" in MODULES:
        only = f"{only}_bench"           # `--only kernels` shorthand
    mods = [only] if only else MODULES
    print("name,us_per_call,derived")
    failed = []
    for m in mods:
        try:
            mod = __import__(f"benchmarks.{m}", fromlist=["main"])
            mod.main()
        except Exception as e:  # noqa: BLE001
            failed.append(m)
            traceback.print_exc()
            # emit (not print) so the failure lands in the JSON artifact too
            # — a partial BENCH_<module>.json must not look like a full run
            common.emit(f"{m}/ERROR", 0.0, repr(e))
        rows = common.drain_rows()
        if rows and (only or args.json):
            path = os.path.join(args.out_dir, f"BENCH_{m}.json")
            with open(path, "w") as f:
                json.dump(rows, f, indent=1)
            print(f"# wrote {len(rows)} rows -> {path}", file=sys.stderr)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
