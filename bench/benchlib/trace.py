"""Reduce a JAX profiler trace to what the per-layer readers need.

Device planes are ``/device:TPU:<n>``.  On each, the ``XLA Ops`` line holds
one event per executed HLO instruction, named by its HLO text
(``%cavity_tconv_step.29 = f32[...] custom-call(...)``): the op's name is
the instruction name with its ``.<n>`` suffix dropped, which for a Pallas
kernel is the kernel's ``name=``.  The ``XLA Modules`` line holds one event
per program execution.  Host spans recorded by the benchmark with
``jax.profiler.TraceAnnotation`` (``bench.*``) are events on the host plane.
All of these share one clock.

The traced window runs from the ``bench.trace_start`` span to the
``bench.trace_end`` span.
"""
from __future__ import annotations

import glob
import re
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

_SUFFIX = re.compile(r"\.\d+$")

# kernel families read by the roofline metrics: family -> op names
FAMILIES = {
    "sconv": ("graph_sconv", "graph_sconv_csr"),
    "tconv": ("cavity_tconv", "cavity_tconv_step"),
    "rfc": ("rfc_encode", "rfc_decode"),
}


def op_name(event_name: str) -> str:
    """``%rfc_encode.19 = (f32[...]) custom-call(...)`` -> ``rfc_encode``."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals: Sequence[Tuple[float, float]]) -> float:
    return float(sum(e - s for s, e in intervals))


def reduce_events(device_ops: Dict[int, List[Tuple[str, float, float]]],
                  modules: Dict[int, List[Tuple[str, float, float]]],
                  host: List[Tuple[str, float, float]]) -> Dict:
    """The reduction, on plain event lists (name, start_s, end_s): per
    device ``device_ops`` (the XLA Ops line) and ``modules`` (the XLA
    Modules line), and the host's ``bench.*`` spans.

    Returns window_s, busy_s (mean over devices of the busy union inside
    the window), op_s (device seconds per op name inside the window,
    summed over devices), family_s, step_busy_s (per program execution
    inside the window, the busy union of its ops) and gaps (idle
    intervals of device 0 inside the window, each with the host span that
    covers its middle)."""
    starts = [s for n, s, e in host if n == "bench.trace_start"]
    ends = [s for n, s, e in host if n == "bench.trace_end"]
    if not starts or not ends or not device_ops:
        return {}
    lo, hi = min(starts), max(ends)
    window = hi - lo
    busy, op_s, steps = [], {}, []
    gaps: List[Tuple[float, str]] = []
    for dev, ops in sorted(device_ops.items()):
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if e > lo and s < hi]
        u = union((s, e) for _, s, e in inside)
        busy.append(covered(u))
        for n, s, e in inside:
            op_s[n] = op_s.get(n, 0.0) + (e - s)
        for _, ms, me in modules.get(dev, []):
            if ms >= lo and me <= hi:
                steps.append(covered(clip(u, ms, me)))
        if dev == min(device_ops):
            edges = [lo] + [x for iv in u for x in iv] + [hi]
            spans = sorted((s, e, n) for n, s, e in host
                           if n.startswith("bench.") and
                           n not in ("bench.trace_start", "bench.trace_end"))
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    mid = 0.5 * (a + b)
                    name = "none"
                    for s, e, n in spans:       # innermost covering span
                        if s <= mid <= e:
                            name = n
                    gaps.append((b - a, name))
    family_s = {f: sum(op_s.get(n, 0.0) for n in names)
                for f, names in FAMILIES.items()}
    return {"window_s": window, "busy_s": float(np.mean(busy)),
            "op_s": op_s, "family_s": family_s, "step_busy_s": steps,
            "gaps": gaps, "devices": len(device_ops)}


def load(trace_dir: str) -> Dict:
    """Read the one ``.xplane.pb`` under ``trace_dir`` and reduce it."""
    from jax.profiler import ProfileData

    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {files}")
    pd = ProfileData.from_file(files[0])
    device_ops: Dict[int, list] = {}
    modules: Dict[int, list] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_ops[dev] = [
                        (op_name(ev.name), ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
                elif line.name == "XLA Modules":
                    modules[dev] = [
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9))
    return reduce_events(device_ops, modules, host)


def breakdown(red: Dict, top: int = 10) -> Dict:
    """The ten device ops that took most time and the ten largest idle
    totals by host span, in seconds."""
    ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:top]
    by_span: Dict[str, float] = {}
    for dur, name in red["gaps"]:
        by_span[name] = by_span.get(name, 0.0) + dur
    gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
