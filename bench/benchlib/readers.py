"""Arithmetic shared by the per-layer metric readers in ``bench/metrics``.

Every reader takes the traced run's context:

  ctx["red"]       trace reduction (``benchlib.trace.reduce_events``)
  ctx["counters"]  host counts over the traced window: ``ticks``,
                   ``tick_wall_s`` (sum over ticks of start to logits on the
                   host), ``host_s`` (growth of ``GcnService.wall_host_s``),
                   ``model_ops`` (counted model operations of the work
                   answered)
  ctx["work"]      ``benchlib.work.window_work`` of that work
  ctx["peak"]      ``benchlib.peaks.peak`` of the device

and returns a number, or None when it finds nothing to read.
"""
from __future__ import annotations

from typing import Optional


def tick_host_ms(ctx) -> Optional[float]:
    """Host time inside ``tick()`` per tick."""
    c = ctx["counters"]
    if not c.get("ticks"):
        return None
    return 1e3 * c["host_s"] / c["ticks"]


def step_device_ms(ctx) -> Optional[float]:
    """Mean device-busy time of one program execution."""
    steps = ctx["red"].get("step_busy_s") or []
    if not steps:
        return None
    return 1e3 * sum(steps) / len(steps)


def mfu_rate(ctx) -> Optional[float]:
    """Counted model operations per second of the traced window over the
    peak, in %."""
    c, red = ctx["counters"], ctx["red"]
    if not c.get("model_ops") or not red.get("window_s"):
        return None
    return 100.0 * c["model_ops"] / red["window_s"] / ctx["peak"]["flops"]


def mfu_tick(ctx) -> Optional[float]:
    """Counted model operations per tick over (mean tick wall time x
    peak), in %."""
    c = ctx["counters"]
    if not c.get("model_ops") or not c.get("tick_wall_s"):
        return None
    return 100.0 * c["model_ops"] / c["tick_wall_s"] / ctx["peak"]["flops"]


def roofline(ctx, family: str) -> Optional[float]:
    """A kernel family's share of its roofline: the least time the chip
    needs for the family's counted operations or bytes, over the family's
    summed kernel time, in %."""
    t = ctx["red"].get("family_s", {}).get(family, 0.0)
    w = ctx["work"].get(family)
    if not t or not w or not w["ops"]:
        return None
    need = max(w["ops"] / ctx["peak"]["flops"],
               w["bytes"] / ctx["peak"]["hbm_bytes_s"])
    return 100.0 * need / t


def idle_share(ctx) -> Optional[float]:
    """Share of the traced window in which no op ran on the device, %."""
    red = ctx["red"]
    if not red.get("window_s"):
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
