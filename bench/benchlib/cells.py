"""One run of one cell: set-up, the measured window, the traced window (if
asked), then the correctness comparison against the plain reference.

``run_cell`` is driven by data only: the configuration file (model, plan
options, stated numerics), the traffic file and the cell's limits file.
The traffic file's ``kind`` names the module ``benchlib/kinds/<kind>.py``
that runs it, so a new kind of traffic is a new file.  A kind's ``run``
returns what its window measured, the answers it served, and a function
that computes the plain reference's answers to the same requests in given
numerics; ``run_cell`` judges the answers against the reference in the
configuration's stated numerics.
"""
from __future__ import annotations

import gc
import importlib
import os
import shutil
import time
from typing import Callable, Dict, Optional

import numpy as np

from benchlib import reference, work

CALIB_ROWS = 8          # one-body rows of the BN calibration batch


def span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """Profiler on/off around a sub-window, with host marks on both ends
    (``bench.trace_start`` / ``bench.trace_end``) and their host times."""

    def __init__(self, trace_dir: Optional[str], clock: Callable[[], float]):
        self.dir, self.clock = trace_dir, clock
        self.t_on = self.t_off = None

    def on(self):
        import jax

        if os.path.isdir(self.dir):
            shutil.rmtree(self.dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_on = self.clock()
        with span("bench.trace_start"):
            pass

    def off(self):
        import jax

        self.t_off = self.clock()
        with span("bench.trace_end"):
            pass
        jax.profiler.stop_trace()


class CompileCounter:
    """Counts JAX traces and backend compiles (JAX's monitoring events),
    so a run can show that nothing compiled inside its window."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")
    n = 0
    _on = False

    @classmethod
    def install(cls) -> None:
        import jax

        if not cls._on:
            def listen(name, secs, **kw):
                if name in cls.EVENTS:
                    cls.n += 1
            jax.monitoring.register_event_duration_secs_listener(listen)
            cls._on = True


class GcWatch:
    """Python's cyclic collections while it is on: count per generation
    and the longest pause, so a run shows whether the collector stalled
    its window."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.longest_s = 0.0
        self._t = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.count[info["generation"]] += 1
            self.longest_s = max(self.longest_s,
                                 time.perf_counter() - self._t)
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)

    def line(self) -> str:
        return (f"gc in the window: collections by generation {self.count}, "
                f"longest {1e3 * self.longest_s:.3f} ms")


def settle() -> None:
    """End of set-up: collect, then freeze every object alive so far out
    of Python's cyclic collector.  Set-up leaves hundreds of thousands of
    long-lived tracked objects (plans, compiled programs, JAX's caches);
    without this, each full collection in the window scans them all and
    stalls the serving loop.  Objects made in the window are still
    collected as usual."""
    gc.collect()
    gc.freeze()


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def families(path: str, counts: Dict[str, int], want, log) -> None:
    """Logs the timed program's compiled kernels per family and refuses
    one in which a family of ``want`` is missing (on the chip)."""
    import jax

    log(f"kernel_counts {path}: {counts}")
    missing = [k for k in want if not counts.get(k)]
    # off the chip (the tests) Pallas interprets and nothing compiles
    if missing and jax.devices()[0].platform == "tpu":
        raise RuntimeError(f"no compiled kernel of {missing} in the timed "
                           "program: a kernel runs interpreted or not at all")


def trace_context(red, counters, model, streams, rows_clips, dispatches,
                  device_kind):
    from benchlib import peaks

    return {"red": red, "counters": counters, "peak": peaks.peak(device_kind),
            "work": work.window_work(model, streams, rows_clips, dispatches)}


def log_setup(setup, setup_s, log) -> None:
    log("setup split: " + ", ".join(f"{k} {v:.3f} s"
                                     for k, v in setup.phases.items())
        + f"; setup_s {setup_s:.3f}")


NO_ANSWER = float(np.finfo(np.float64).max)   # a missing or non-finite answer


def gap(got: np.ndarray, want: np.ndarray) -> float:
    """The compared number: the widest logit gap, max |got - want|; a
    non-finite answer reads ``NO_ANSWER`` (JSON has no infinity)."""
    got = np.asarray(got, np.float64)
    if got.size == 0 or not np.all(np.isfinite(got)):
        return NO_ANSWER
    return float(np.max(np.abs(got - np.asarray(want, np.float64))))


def judge(got, want, limits: dict, checks: Dict, log=None) -> Dict:
    """``correct`` and the compared numbers, each beside its limit: the
    widest logit gap of ``got`` from the reference's ``want`` (limit from
    the cell's limits file), and the kind's own exact ``checks``
    (name -> (value, limit))."""
    compared = {"logit_gap": (gap(got, want), limits["logit_gap"]),
                **checks}
    if log is not None and np.size(got):
        agree = (np.argmax(got, -1) == np.argmax(want, -1)).ravel()
        log(f"{len(agree)} answers compared; top-1 agreement with the "
            f"reference {int(agree.sum())}/{len(agree)} (not compared; "
            "random weights)")
    return {"correct": all(v <= lim for v, lim in compared.values()),
            "compared": {k: {"value": float(v), "limit": float(lim)}
                         for k, (v, lim) in compared.items()}}


def kind_module(kind: str):
    """The module that runs a traffic kind: ``benchlib/kinds/<kind>.py``."""
    return importlib.import_module(f"benchlib.kinds.{kind}")


def run_cell(conf: dict, tr: dict, limits: dict, seed: int, seconds: float,
             traced: bool, trace_dir: str, log: Callable[[str], None],
             t_start: float, controls: bool = False) -> Dict:
    """Run one cell once; see the module docstring.  With ``controls``
    the result also holds ``control``: the control's answers (the plain
    reference one storage step below the stated numerics) put in the
    program's place and judged the same way."""
    out = kind_module(tr["kind"]).run(conf, tr, seed, seconds, traced,
                                      trace_dir, log, t_start)
    gc.collect()
    ref = out["reference"]
    want = ref(reference.stated(conf))
    res = judge(out["got"], want, limits, out["checks"], log)
    res.update(attempted=out["attempted"], failed=out["failed"],
               memory_peak_bytes=out["mem"], e2e=out["e2e"], ctx=out["ctx"])
    if controls:
        low = ref(reference.control(conf))
        res.update(control=judge(low, want, limits, out["checks"]),
                   low=low, got=out["got"], reference=ref)
    return res
