"""The serving tick's named phases (``GcnService.phase_s``, ``svc.*`` spans).

``tick()`` runs as contiguous phases — feed, stage, dispatch, a readback
when one is due, drain — each a profiler span and a ``phase_s`` entry;
``poll(wait=True)`` and ``metrics()`` add to ``readback`` too.  Locked
here, on the reference backend with two slots, under each QoS policy over
a one-topology and a mixed-topology slab, through a scripted run with
finishing sessions: under ``fifo`` every tick is event-free, under
``preempt`` a preemption and a restore run through the event tick, and
under ``deadline`` a queued session misses its deadline:

* ``wall_host_s`` and ``wall_device_s`` are sums of the phases;
* every tick opens its spans in order, none nested in another;
* once the service is built (and warmed), ticking compiles nothing.
"""
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.serving import QOS_POLICIES, GcnService
from repro.serving.service import HOST_PHASES, TICK_PHASES

CFG = get_config("agcn-2s", reduced=True)
C = CFG.gcn_in_channels

_COMPILES = {"on": False, "n": 0}


def _count_compiles(name, secs, **kw):
    if _COMPILES["on"] and name in (
            "/jax/core/compile/jaxpr_trace_duration",
            "/jax/core/compile/backend_compile_duration"):
        _COMPILES["n"] += 1


jax.monitoring.register_event_duration_secs_listener(_count_compiles)


class SpanRecorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each span's
    enter and exit in order."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        rec = self

        class Span:
            def __enter__(self):
                rec.log.append(("enter", name))

            def __exit__(self, *exc):
                rec.log.append(("exit", name))

        return Span()


TOPOLOGIES = {"single": ("ntu25",), "mixed": ("ntu25", "ntu50")}


@pytest.fixture(scope="module", params=[
    (qos, topo) for qos in QOS_POLICIES for topo in TOPOLOGIES],
    ids=lambda p: f"{p[0]}-{p[1]}")
def scripted(request):
    """X (priority 0) and Y (priority 1, the second topology) fill both
    slots; Z (priority 2) arrives at tick 5.  Under ``preempt`` Z evicts
    X, which is restored when a slot frees; under ``fifo`` Z queues for a
    free slot; under ``deadline`` Z's deadline (tick 6) passes while it
    queues.  The run goes on until no session is left.  Returns the
    service, each tick's span log and whether it carried snapshot or
    restore events, the compiles counted while ticking and the host wall
    time around the calls."""
    qos, topo = request.param
    topologies = TOPOLOGIES[topo]
    first, second = topologies[0], topologies[-1]
    svc = GcnService(CFG, backend="reference", qos=qos,
                     capacity_tiers=(2,), topologies=topologies, seed=0)
    rec = SpanRecorder()
    svc._span = rec
    rng = np.random.default_rng(3)

    def arrive(priority, topology, frames, deadline=None):
        h = svc.open_session(priority=priority, topology=topology,
                             deadline=deadline)
        V = svc._topos[topology].num_joints
        svc.submit_clip(h, rng.standard_normal((frames, V, C))
                        .astype(np.float32))
        return h

    handles = [arrive(0, first, 10), arrive(1, second, 12)]
    per_tick, wall = [], 0.0
    _COMPILES.update(on=True, n=0)
    try:
        while True:
            if svc.now == 5:
                handles.append(arrive(2, first, 8,
                                      6 if qos == "deadline" else None))
            if svc.idle():
                break
            start = len(rec.log)
            events = svc.sched.preemptions + svc.sched.restores
            t0 = time.monotonic()
            svc.tick()
            wall += time.monotonic() - t0
            events = svc.sched.preemptions + svc.sched.restores > events
            per_tick.append((rec.log[start:], events))
        start = len(rec.log)
        t0 = time.monotonic()
        svc.poll(handles[0], wait=True)
        svc.metrics()
        wall += time.monotonic() - t0
        outside = rec.log[start:]
    finally:
        _COMPILES["on"] = False
    return {"svc": svc, "qos": qos, "per_tick": per_tick,
            "outside": outside, "compiles": _COMPILES["n"], "wall": wall,
            "handles": handles}


def test_wall_counters_are_sums_of_the_phases(scripted):
    svc, qos = scripted["svc"], scripted["qos"]
    if qos == "preempt":
        assert svc.sched.preemptions >= 1 and svc.sched.restores >= 1
    else:
        assert svc.sched.preemptions == 0 and svc.sched.restores == 0
    # Z, the last handle, misses its deadline under "deadline"
    want = ["done"] * 3 if qos != "deadline" else ["done", "done", "missed"]
    assert [svc.poll(h).state for h in scripted["handles"]] == want
    ph = svc.phase_s
    assert set(ph) == set(TICK_PHASES)
    assert svc.wall_host_s == pytest.approx(
        ph["feed"] + ph["stage"] + ph["dispatch"] + ph["drain"])
    assert svc.wall_device_s == pytest.approx(ph["readback"])
    assert svc.wall_s == pytest.approx(sum(ph.values()))
    # every phase ran, and a finishing session forced a readback
    assert all(ph[p] > 0.0 for p in TICK_PHASES)
    # the phases are contiguous inside the calls that ran them: their sum
    # is the host wall time around those calls, less call overhead
    assert 0.5 * scripted["wall"] <= sum(ph.values()) <= scripted["wall"]
    m = svc.metrics()
    assert m["wall_host_s"] == svc.wall_host_s
    assert m["wall_device_s"] == svc.wall_device_s
    assert "phase_s" not in m


def test_each_tick_opens_its_spans_in_order(scripted):
    host = ["svc." + p for p in HOST_PHASES]
    with_readback = host[:3] + ["svc.readback"] + host[3:]
    forced = event_ticks = 0
    for log, events in scripted["per_tick"]:
        # spans never nest: each enter is closed before the next opens
        assert log[0::2] == [("enter", n) for _, n in log[0::2]]
        assert log[1::2] == [("exit", n) for _, n in log[0::2]]
        names = [n for _, n in log[0::2]]
        assert names in (host, with_readback)
        forced += names == with_readback
        event_ticks += events
    # only ticks on which a session finishes force the readback, event
    # ticks and plain ticks alike
    assert 0 < forced < len(scripted["per_tick"])
    if scripted["qos"] == "preempt":
        assert event_ticks > 0
    else:
        assert event_ticks == 0
    # poll(wait=True) and metrics() after the last tick find the logits
    # already forced, so they open no readback span
    assert scripted["outside"] == []


def test_poll_wait_times_its_readback():
    svc = GcnService(CFG, backend="reference", capacity_tiers=(2,), seed=0)
    rec = SpanRecorder()
    svc._span = rec
    h = svc.open_session()
    svc.submit_clip(h, np.zeros((12, CFG.gcn_joints, C), np.float32))
    svc.tick()
    before = dict(svc.phase_s)
    start = len(rec.log)
    svc.poll(h, wait=True)
    assert rec.log[start:] == [("enter", "svc.readback"),
                               ("exit", "svc.readback")]
    assert svc.phase_s["readback"] > before["readback"]
    assert {p: svc.phase_s[p] for p in HOST_PHASES} == {
        p: before[p] for p in HOST_PHASES}


def test_ticking_after_warm_up_compiles_nothing(scripted):
    assert scripted["compiles"] == 0
