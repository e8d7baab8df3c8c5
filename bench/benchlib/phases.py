"""The phases of the serving tick in a traced run.

``GcnService.tick()`` runs as contiguous named phases, each inside a
``svc.<phase>`` profiler span: ``svc.feed`` (controllers, the scheduler's
``tick_inputs``, bookkeeping), ``svc.stage`` (the step's inputs to the
device), ``svc.dispatch`` (every jitted call), ``svc.readback`` (a forced
logit readback, also from ``poll(wait=True)``) and ``svc.drain``
(``tick_outputs``, record retirement).  The spans share the profiler's
clock with the device planes and the benchmark's ``bench.*`` spans.

``benchlib.trace.load`` keeps only the ``bench.*`` host spans, so these
readers read the run's trace file themselves: ``bench/run.py`` keeps a
``--trace 1`` run's trace under ``.bench_trace/<cell>/`` until its
metrics are read.  A program without the spans reads as nothing.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from benchlib import trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRACES = os.path.join(ROOT, ".bench_trace")
MARKS = ("bench.trace_start", "bench.trace_end")

Span = Tuple[str, float, float]         # (name, start_s, end_s)

_loaded: Dict[Tuple[str, float], Dict] = {}


def newest_trace(traces: str = TRACES) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``traces`` (the running cell's:
    each run removes its own trace once its metrics are read)."""
    files = glob.glob(f"{traces}/**/*.xplane.pb", recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load(path: str) -> Dict:
    """The trace's host spans (``bench.*`` and ``svc.*``) and the device
    ops of its first TPU, as (name, start_s, end_s); read once per file."""
    key = (path, os.path.getmtime(path))
    if key not in _loaded:
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        host: List[Span] = []
        devices: Dict[int, List[Span]] = {}
        for plane in pd.planes:
            m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
            for line in plane.lines:
                if m and line.name == "XLA Ops":
                    devices[int(m.group(1))] = [
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
                elif plane.name.startswith("/host:CPU"):
                    host += [(ev.name, ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9)
                             for ev in line.events
                             if ev.name.startswith(("bench.", "svc."))]
        _loaded.clear()
        _loaded[key] = {"host": host,
                        "ops": devices[min(devices)] if devices else []}
    return _loaded[key]


def window(host: List[Span]) -> Optional[Tuple[float, float]]:
    """The traced window, as ``trace.reduce_events`` takes it."""
    starts = [s for n, s, _ in host if n == MARKS[0]]
    ends = [s for n, s, _ in host if n == MARKS[1]]
    if not starts or not ends:
        return None
    return min(starts), max(ends)


def tick_phases(host: List[Span]) -> Optional[Tuple[int, Dict[str, float]]]:
    """The ticks that lie wholly in the traced window (``bench.tick``
    spans) and the seconds each ``svc.<phase>`` span that starts inside
    one of them took, summed per phase.  None without the window's marks,
    its ticks or any phase span."""
    win = window(host)
    if win is None:
        return None
    lo, hi = win
    ticks = sorted((s, e) for n, s, e in host
                   if n == "bench.tick" and s >= lo and e <= hi)
    starts = [s for s, _ in ticks]
    phase_s: Dict[str, float] = {}
    for n, s, e in host:
        if not n.startswith("svc."):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < ticks[i][1]:
            p = n[len("svc."):]
            phase_s[p] = phase_s.get(p, 0.0) + (e - s)
    if not ticks or not phase_s:
        return None
    return len(ticks), phase_s


def phase_ms(ctx, phase: str, path: Optional[str] = None) -> Optional[float]:
    """Milliseconds per tick of one phase in the traced window (the
    newest trace's, or ``path``'s); None when the trace is not the run's
    whose context this is (another window) or holds no such span."""
    path = path or newest_trace()
    if path is None:
        return None
    host = load(path)["host"]
    win = window(host)
    want = ctx["red"].get("window_s")
    if win is None or want is None or abs((win[1] - win[0]) - want) > 1e-9:
        return None
    got = tick_phases(host)
    if got is None or phase not in got[1]:
        return None
    n, phase_s = got
    return 1e3 * phase_s[phase] / n


def idle_gaps(ops: List[Span], host: List[Span]) -> List[Tuple[float, str]]:
    """Idle intervals of the device inside the traced window, each with
    the innermost ``bench.*`` or ``svc.*`` span covering its middle, as
    ``trace.reduce_events`` names them by the ``bench.*`` spans alone."""
    win = window(host)
    if win is None:
        return []
    lo, hi = win
    busy = trace.union(trace.clip([(s, e) for _, s, e in ops], lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    spans = sorted((s, e, n) for n, s, e in host if n not in MARKS)
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            mid = 0.5 * (a + b)
            name = "none"
            for s, e, n in spans:               # innermost covering span
                if s <= mid <= e:
                    name = n
            gaps.append((b - a, name))
    return gaps


def by_span(gaps: List[Tuple[float, str]]) -> List[List]:
    """Idle seconds per naming span, largest first."""
    out: Dict[str, float] = {}
    for dur, name in gaps:
        out[name] = out.get(name, 0.0) + dur
    return [[n, s] for n, s in sorted(out.items(), key=lambda kv: -kv[1])]
