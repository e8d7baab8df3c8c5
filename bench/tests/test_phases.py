"""The tick-phase readers (``benchlib.phases``): the program's ``svc.*``
spans summed per tick, and the device's idle gaps named by them."""
import types

import numpy as np
import pytest

from benchlib import phases, readers, trace
from benchlib.openloop import OpenLoop

HOST_PHASES = ("feed", "stage", "dispatch", "drain")


def _events():
    # test_trace's synthetic trace, with a svc.dispatch span inside the
    # first bench.tick over the idle gap 1-2 s
    ops = {0: [("graph_sconv", 2.0, 3.0), ("cavity_tconv_step", 2.5, 4.0),
               ("rfc_encode", 5.0, 6.0), ("cavity_tconv", 8.0, 10.0),
               ("fusion", 0.0, 0.5)]}
    modules = {0: [("jit_slab_step", 2.0, 4.0), ("jit_slab_step", 4.5, 6.0),
                   ("jit_slab_step", 7.5, 10.5)]}
    host = [("bench.trace_start", 1.0, 1.0), ("bench.trace_end", 11.0, 11.0),
            ("bench.tick", 1.0, 6.5), ("bench.readback", 4.0, 5.0),
            ("bench.wait", 6.5, 8.0)]
    return ops, modules, host


def test_a_svc_span_names_its_gap_and_nothing_else_moves():
    ops, modules, host = _events()
    with_svc = host + [("svc.dispatch", 1.2, 1.9)]
    red = trace.reduce_events(ops, modules, host)
    # the benchmark's own reduction reads only bench.* spans
    assert trace.reduce_events(ops, modules, with_svc) == red
    gaps = sorted((round(d, 6), n) for d, n in
                  phases.idle_gaps(ops[0], with_svc))
    assert gaps == [(1.0, "bench.readback"), (1.0, "none"),
                    (1.0, "svc.dispatch"), (2.0, "bench.wait")]
    # without svc spans the gaps are named as the reduction names them
    assert sorted(phases.idle_gaps(ops[0], host)) == sorted(red["gaps"])
    assert phases.by_span(phases.idle_gaps(ops[0], with_svc))[0] == \
        ["bench.wait", pytest.approx(2.0)]


def test_no_svc_spans_reads_nothing():
    _, _, host = _events()
    assert phases.tick_phases(host) is None
    assert phases.tick_phases(host[2:] + [("svc.feed", 1.1, 1.2)]) is None


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += max(0.0, dt)


class SpanLog:
    """Records (name, start, end) on the fake clock."""

    def __init__(self, clock):
        self.clock, self.spans = clock, []

    def __call__(self, name):
        log = self

        class Span:
            def __enter__(self):
                self.t0 = log.clock()

            def __exit__(self, *exc):
                log.spans.append((name, self.t0, log.clock()))

        return Span()


class PhasedService:
    """Consumes one pending frame per session per tick; a tick's host
    phases cost ``shares`` of its duration on the fake clock, inside
    ``svc.*`` spans, and add up in ``wall_host_s``; ``poll(wait=True)``
    waits ``readback`` seconds inside ``svc.readback``."""

    shares = {"feed": 0.1, "stage": 0.05, "dispatch": 0.3, "drain": 0.05}

    def __init__(self, clock, span, n, durations, readback=0.002):
        self.clock, self.span, self.durations = clock, span, list(durations)
        self.readback = readback
        self.buf, self.done = [0] * n, [0] * n
        self.phase_s = dict.fromkeys(list(self.shares) + ["readback"], 0.0)
        self.n = 0

    @property
    def wall_host_s(self):
        return sum(self.phase_s[p] for p in HOST_PHASES)

    def submit(self, h, frame):
        self.buf[h] += 1

    def tick(self):
        d = self.durations[min(self.n, len(self.durations) - 1)]
        self.n += 1
        for p, share in self.shares.items():
            with self.span("svc." + p):
                self.clock.t += share * d
                self.phase_s[p] += share * d
        for i in range(len(self.buf)):
            if self.buf[i] > self.done[i]:
                self.done[i] += 1

    def poll(self, h, wait=False):
        if wait:
            with self.span("svc.readback"):
                self.clock.t += self.readback
                self.phase_s["readback"] += self.readback
        return types.SimpleNamespace(frames_consumed=self.done[h],
                                     logits=np.zeros(3))


def test_phase_ms_adds_up_to_tick_host_ms():
    """Over the ticks that lie wholly in the traced window, the four host
    phases per tick add up to ``tick_host_ms`` as the open-loop cell
    computes it from the service's counter; readback spans fall in
    ``bench.readback``, outside the ticks."""
    clock = FakeClock()
    log = SpanLog(clock)
    durations = [0.01, 0.02, 0.015, 0.03, 0.012]
    svc = PhasedService(clock, log, 3, durations * 40)
    loop = OpenLoop(svc, range(3),
                    lambda ids, ks: np.zeros((len(ids), 2, 3), np.float32),
                    np.array([0.0, 0.011, 0.023]), 0.033, clock,
                    clock.sleep, log)
    on_off = {}

    def mark(name):
        def fn():
            on_off[name] = clock()
            with log(name):
                pass
        return fn

    ws, we = 0.5, 2.5
    marks = {"on": (0.8, mark("bench.trace_start")),
             "off": (1.9, mark("bench.trace_end"))}
    loop.run(0.0, ws, we, we + 5.0, True, {}, marks)
    # the open-loop cell's counters over the traced window
    ticks = np.array(loop.ticks)
    inw = ((ticks[:, 0] >= on_off["bench.trace_start"])
           & (ticks[:, 1] <= on_off["bench.trace_end"]))
    first = np.flatnonzero(inw)[0]
    counters = {"ticks": int(inw.sum()),
                "host_s": float(ticks[inw][-1, 3] - ticks[first - 1, 3])}
    tick_ms = readers.tick_host_ms({"counters": counters})

    n, phase_s = phases.tick_phases(log.spans)
    assert n == counters["ticks"] > 20
    assert "readback" not in phase_s
    assert sum(1e3 * phase_s[p] / n for p in HOST_PHASES) == \
        pytest.approx(tick_ms, rel=1e-9)
    assert 1e3 * phase_s["dispatch"] / n == pytest.approx(0.6 * tick_ms)


def test_real_service_trace_on_the_cpu(tmp_path):
    """A two-slot reference-backend ``GcnService`` ticked under the JAX
    profiler: the trace's phase spans add up to the growth of
    ``wall_host_s`` over the traced ticks, and ``phase_ms`` reads them
    only for the run whose window it is handed."""
    import jax

    from repro.configs import get_config
    from repro.serving import GcnService

    cfg = get_config("agcn-2s", reduced=True)
    svc = GcnService(cfg, backend="reference", capacity_tiers=(2,), seed=0)
    rng = np.random.default_rng(0)
    hs = [svc.open_session() for _ in range(2)]
    for h in hs:
        svc.submit_clip(h, rng.standard_normal(
            (12, cfg.gcn_joints, cfg.gcn_in_channels)).astype(np.float32))
    svc.tick()
    svc.poll(hs[0], wait=True)
    span = jax.profiler.TraceAnnotation
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with span("bench.trace_start"):
        pass
    host0, n = svc.wall_host_s, 0
    for _ in range(6):
        with span("bench.tick"):
            svc.tick()
        with span("bench.readback"):
            svc.poll(hs[0], wait=True)
        n += 1
    host = svc.wall_host_s - host0
    with span("bench.trace_end"):
        pass
    jax.profiler.stop_trace()

    path = phases.newest_trace(str(tmp_path))
    ev = phases.load(path)
    got_n, phase_s = phases.tick_phases(ev["host"])
    assert got_n == n
    assert set(HOST_PHASES) <= set(phase_s)
    spans = sum(phase_s[p] for p in HOST_PHASES)
    # each span encloses its phase's two clock reads
    assert host <= spans <= host * 1.05 + 1e-4
    lo, hi = phases.window(ev["host"])
    ctx = {"red": {"window_s": hi - lo}}
    got = {p: phases.phase_ms(ctx, p, path) for p in HOST_PHASES}
    assert sum(got.values()) == pytest.approx(1e3 * spans / n)
    assert phases.phase_ms({"red": {"window_s": hi - lo + 1.0}}, "feed",
                           path) is None
    assert phases.phase_ms(ctx, "nosuch", path) is None
