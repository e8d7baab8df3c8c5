"""Open-loop session traffic against ``GcnService``.

N long-lived sessions each have a frame due every ``period`` seconds from
their own phase.  The client loop submits every frame as it comes due,
ticks whenever a frame is pending, and after each tick forces that tick's
logits to the host once (``poll(wait=True)``).  A tick consumes one
pending frame of every session that has one (the service's FIFO slab feed),
so the loop knows which frame each tick answered; it checks that count
against the service's own ``frames_consumed`` for the sampled sessions as
they are read and for every session at the end.

A frame's latency runs from when it was due to when the host holds the
logits of the tick that consumed it, so a slow tick delays every frame due
behind it.  Clock, sleep and span annotation are injected, so the
accounting runs under a fake clock in the tests.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np


def _null_span(name):
    return contextlib.nullcontext()


class OpenLoop:
    """One open-loop run.  ``frames(ids, ks)`` returns the frames to
    submit; ``phases`` is each session's offset into its period."""

    def __init__(self, svc, handles, frames: Callable, phases: np.ndarray,
                 period: float, clock: Callable[[], float],
                 sleep: Callable[[float], None], span=_null_span):
        self.svc, self.handles = svc, list(handles)
        self.frames, self.phases = frames, np.asarray(phases, np.float64)
        self.period, self.clock, self.sleep, self.span = (
            period, clock, sleep, span)
        n = len(self.handles)
        self.submitted = np.zeros(n, np.int64)
        self.consumed = np.zeros(n, np.int64)
        self.t0 = 0.0
        # per tick: start, logits on host, frames consumed, host time inside
        # tick() so far (the service's own counter)
        self.ticks: List[Tuple[float, float, int, float]] = []
        self.lat: List[np.ndarray] = []       # latency of window frames
        self.late: List[np.ndarray] = []      # generator lateness, window
        self.served_in_window = 0
        # reads: (session, frames consumed, logits or None), in the order
        # taken
        self.samples: List[Tuple[int, int, np.ndarray]] = []
        self.marks: Dict[str, float] = {}

    def due_by(self, t: float) -> np.ndarray:
        """Frames of each session due at or before time ``t``."""
        # the small slack keeps a frame due exactly at ``t`` from rounding
        # below its own due time
        return np.maximum(0, np.floor(
            (t - self.t0 - self.phases) / self.period + 1e-9
        ).astype(np.int64) + 1)

    def due_time(self, ids, ks) -> np.ndarray:
        return self.t0 + self.phases[ids] + np.asarray(ks) * self.period

    def _submit(self, now: float, ws: float, we: float) -> None:
        due = self.due_by(now)
        new = due - self.submitted
        ids = np.repeat(np.arange(len(new)), new)
        if not len(ids):
            return
        ks = np.concatenate([np.arange(a, b) for a, b in
                             zip(self.submitted[new > 0], due[new > 0])])
        with self.span("bench.submit"):
            fr = self.frames(ids, ks)
            for i, f in zip(ids, fr):
                self.svc.submit(self.handles[i], f)
        self.submitted = due
        d = self.due_time(ids, ks)
        w = (d >= ws) & (d < we)
        if w.any():
            self.late.append(now - d[w])

    def run(self, t0: float, ws: float, we: float, drain_end: float,
            wait_for_window: bool, read_at: Dict[int, List[float]],
            marks: Optional[Dict[str, Tuple[float, Callable]]] = None):
        """Drive the service from ``t0`` (schedule origin).  Frames due in
        [ws, we) are the window's.  With ``wait_for_window`` the loop runs
        on after ``we`` until every window frame has its logits or
        ``drain_end`` passes; otherwise it stops at ``we``.  ``read_at``
        maps a session to ascending times: for each, the session's first
        tick that starts at or after it, and after its earlier reads, has
        its logits read and kept.  ``marks`` are callbacks fired once when the
        clock passes their time (tracing on and off)."""
        self.t0 = t0
        marks = dict(marks or {})
        todo = {i: list(ts) for i, ts in read_at.items()}
        last_window = self.due_by(we - 1e-12) - 1   # last window frame index
        while True:
            now = self.clock()
            for name, (t, fn) in list(marks.items()):
                if now >= t:
                    fn()
                    self.marks[name] = self.clock()
                    del marks[name]
            if now >= we:
                if not wait_for_window or now >= drain_end or np.all(
                        self.consumed > last_window):
                    break
            self._submit(now, ws, we)
            pending = self.submitted > self.consumed
            if not pending.any():
                nxt = self.due_time(np.arange(len(self.phases)),
                                    self.submitted).min()
                with self.span("bench.wait"):
                    self.sleep(max(1e-6, min(nxt, we) - self.clock()))
                continue
            t_start = self.clock()
            with self.span("bench.tick"):
                self.svc.tick()
            first = int(np.argmax(pending))
            with self.span("bench.readback"):
                self.svc.poll(self.handles[first], wait=True)
            t_done = self.clock()
            ids = np.flatnonzero(pending)
            ks = self.consumed[ids]
            self.consumed[ids] += 1
            self.ticks.append((t_start, t_done, len(ids),
                               float(self.svc.wall_host_s)))
            d = self.due_time(ids, ks)
            w = (d >= ws) & (d < we)
            if w.any():
                self.lat.append(t_done - d[w])
            if ws <= t_done < we:
                self.served_in_window += len(ids)
            for i in ids:
                ts = todo.get(i)
                if ts and t_start >= ts[0]:
                    ts.pop(0)
                    st = self.svc.poll(self.handles[i])
                    self.samples.append((int(i), st.frames_consumed,
                                         st.logits))
                    if st.frames_consumed != self.consumed[i]:
                        raise RuntimeError(
                            f"session {i}: service consumed "
                            f"{st.frames_consumed} frames, the loop counted "
                            f"{self.consumed[i]}")
        return self

    def summary(self, ws: float, we: float) -> Dict:
        """Window counts: frames due, frames answered, latencies."""
        due_w = self.due_by(we - 1e-12) - self.due_by(ws - 1e-12)
        lat = np.concatenate(self.lat) if self.lat else np.zeros(0)
        late = np.concatenate(self.late) if self.late else np.zeros(0)
        ticks = np.array(self.ticks) if self.ticks else np.zeros((0, 4))
        inw = (ticks[:, 0] >= ws) & (ticks[:, 1] < we)
        return {"due": int(due_w.sum()), "answered": int(lat.size),
                "latency_s": lat, "lateness_s": late,
                "served_in_window": self.served_in_window,
                "ticks": ticks, "ticks_in_window": int(inw.sum())}
