#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell comes from ``BENCHMARK.json`` at the repository root; its
configuration file (``bench/configs/<config>.json``), traffic file
(``bench/traffic/<traffic>.json``) and limits file
(``bench/limits/<cell>.json``) are found by name.  ``--trace 0`` reports
the cell's end-to-end metrics; ``--trace 1`` traces part of the window
with the JAX profiler and reports the cell's per-layer metrics, each read
by ``bench/metrics/<metric>.py``.

JAX must see a TPU with at least the cell's chips: otherwise the run exits
non-zero before printing any result.  The last line of standard output is
one JSON object; the numbers compared with the reference are also the last
lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def log(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_spec(spec: dict, workload: str):
    """(cell, configuration, traffic, limits, e2e metrics, per-layer
    metrics) of one workload of ``BENCHMARK.json``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r} "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    conf = load_json(BENCH, "configs", cell["config"] + ".json")
    tr = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    limits = load_json(BENCH, "limits", workload + ".json")

    def mine(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in spec["end_to_end"] if mine(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if mine(m) and m["moves"] in names]
    return cell, conf, tr, limits, e2e, layer


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or ``$JAX_COMPILATION_CACHE_DIR``), keeping every compile —
    the many small ones included — so only a cell's first run compiles."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def log_cache_lookups() -> None:
    """One stderr line per persistent-cache lookup of a jitted program
    (hit or miss, with its name), so a run shows what it compiled."""
    import logging

    class Lookups(logging.Filter):
        def filter(self, record):
            msg = record.getMessage()
            return ("cache hit" in msg or "CACHE MISS" in msg)

    handler = logging.StreamHandler(sys.stderr)
    handler.addFilter(Lookups())
    handler.setFormatter(logging.Formatter("compile cache: %(message).160s"))
    logger = logging.getLogger("jax._src.compiler")
    logger.setLevel(logging.DEBUG)
    logger.addHandler(handler)


def require_chips(n: int):
    """The devices to run on; exits without a result unless JAX sees at
    least ``n`` TPU chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); no result")
    if len(devices) < n:
        sys.exit(f"bench: the cell needs {n} chips, JAX sees "
                 f"{len(devices)}; no result")
    return devices


def read_metric(name: str, ctx) -> float | None:
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def result_line(res: dict, e2e, layer, traced: bool, devices) -> dict:
    from benchlib import trace as tracing

    d0 = devices[0]
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": {},
           "device": {"platform": d0.platform, "kind": d0.device_kind,
                      "count": len(devices),
                      "memory_peak_bytes": res["memory_peak_bytes"]}}
    if traced:
        ctx = res["ctx"]
        for m in layer:
            v = read_metric(m["name"], ctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        out["device"]["busy_s"] = ctx["red"]["busy_s"]
        out["device"]["window_s"] = ctx["red"]["window_s"]
        out["breakdown"] = tracing.breakdown(ctx["red"])
    else:
        for m in e2e:
            if m["name"] in res["e2e"]:
                out["metrics"][m["name"]] = {"value": res["e2e"][m["name"]],
                                             "unit": m["unit"]}
    out["compared"] = res["compared"]
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_json(ROOT, "BENCHMARK.json")
    cell, conf, tr, limits, e2e, layer = cell_spec(spec, args.workload)
    use_compile_cache()
    log_cache_lookups()
    devices = require_chips(int(cell["chips"]))[: int(cell["chips"])]
    log(f"device: {devices[0].device_kind} x{len(devices)}; cell "
        f"{cell['name']} ({cell['config']} x {cell['traffic']}), seed "
        f"{args.seed}, {args.seconds} s, trace {args.trace}")

    from benchlib.cells import run_cell

    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    res = run_cell(conf, tr, limits, args.seed, args.seconds,
                   bool(args.trace), trace_dir, log, T_START)
    line = result_line(res, e2e, layer, bool(args.trace), devices)
    if args.trace:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
    for k, v in res["compared"].items():
        print(f"compared {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
