"""The one-dispatch serving tick (engine.fused_tick + GcnService fused path).

The tentpole locks:

* **Fused == legacy, bitwise** — on both backends, a scripted QoS trace
  (admissions, preemptions with restores, a mid-clip elastic grow/shrink
  migration) produces byte-identical final logits whether the service
  runs the fused single-dispatch tick or the legacy multi-dispatch
  sequence; bystander sessions riding alongside the churn are identical
  too (every session in the trace is compared).
* **Single dispatch per tick** — the fused service issues exactly one
  jitted call per tick regardless of event counts, while the legacy path
  pays 2 extra dispatches per snapshot/restore event per stream.
* **One compilation per tier** — snapshot/restore event counts (0, 1,
  max) are traced values of the fixed-shape sentinel-padded order
  buffers, so they never retrace; overflowing the static buffer raises
  instead of silently retracing.

Plus the host-side satellites: the scheduler's per-tick event budget
defers surplus preemptions (never overflows the static buffers), the
snapshot-ring allocator raises on exhaustion, and the jax-free sentinel
mirror in the scheduler equals the engine's.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.agcn import engine
from repro.core.agcn import model as M
from repro.core.pruning.plan import build_prune_plan
from repro.serving import CapacityConfig, GcnService, SessionRequest
from repro.serving import scheduler as sched_mod
from repro.serving.scheduler import SlabScheduler, pad_event_orders

CFG = get_config("agcn-2s", reduced=True)
V, C = CFG.gcn_joints, CFG.gcn_in_channels


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def prune_plan(params):
    sw = [np.asarray(b["Wk"]) for b in params["blocks"]]
    return build_prune_plan(sw, CFG.gcn_channels, [1.0, 0.5, 0.5, 0.5],
                            "cav-70-1", input_skip=2)


def _plan_and_bn(params, prune_plan, backend):
    plan = engine.build_execution_plan(params, CFG, prune_plan, quant=True,
                                       backend=backend)
    bn = engine.collect_bn_stats(
        plan, jax.random.normal(jax.random.PRNGKey(1),
                                (2, CFG.gcn_frames, V, C)))
    return plan, bn


def _qos_trace(rng):
    """(arrival, priority, T) script: fills a 2-slot tier with low-prio
    clips, lands high-prio arrivals that force snapshot evictions and
    later restores, and keeps enough backlog to trip an elastic grow."""
    spec = [(0, 0, 12), (0, 0, 12), (1, 0, 10), (1, 0, 10),
            (2, 1, 6), (3, 1, 6), (5, 0, 8), (18, 0, 7)]
    return [SessionRequest(
        sid=i, arrival=a, priority=p,
        clip=rng.standard_normal((T, V, C)).astype(np.float32))
        for i, (a, p, T) in enumerate(spec)]


def _drive_requests(svc, reqs, max_ticks=600):
    """Feed a SessionRequest script through the handle API, run to idle;
    returns ({sid: final logits}, metrics)."""
    pending = sorted(reqs, key=lambda r: r.arrival)
    i = 0
    while svc.now < max_ticks:
        while i < len(pending) and pending[i].arrival <= svc.now:
            r = pending[i]
            h = svc.open_session(priority=r.priority, arrival=r.arrival)
            svc.submit_clip(h, r.clip)
            i += 1
        if svc.idle():
            if i == len(pending):
                break
            svc.advance_clock(pending[i].arrival)
            continue
        svc.tick()
    assert svc.idle(), "service did not drain within the tick budget"
    m = svc.metrics()
    return {rec.sid: rec.logits for rec in m["records"]}, m


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_fused_matches_legacy_qos_trace(params, prune_plan, backend):
    """Fused single-dispatch ticks == legacy multi-dispatch ticks, bitwise,
    across preemptions + restores + an elastic grow/shrink migration —
    including every bystander session riding through the churn — and the
    fused path really is one device dispatch per tick."""
    plan, bn = _plan_and_bn(params, prune_plan, backend)
    ccfg = CapacityConfig(tiers=(2, 4), grow_patience=1, shrink_patience=2,
                          cooldown=3)
    runs = {}
    for fused in (True, False):
        svc = GcnService(CFG, backend=backend, plans=(plan,),
                         bn_stats=(bn,), qos="preempt",
                         capacity_tiers=(2, 4), capacity_config=ccfg,
                         fused=fused)
        runs[fused] = _drive_requests(svc, _qos_trace(np.random.default_rng(7)))
    of, mf = runs[True]
    ol, ml = runs[False]
    # the trace actually exercised the churn it scripts
    assert mf["preemptions"] > 0 and mf["restores"] > 0
    assert mf["migrations"] > 0
    assert mf["preemptions"] == ml["preemptions"]
    assert mf["migrations"] == ml["migrations"]
    # single dispatch per tick, fused; legacy pays per-event dispatches
    assert mf["device_dispatches"] == mf["ticks"]
    assert ml["device_dispatches"] > ml["ticks"]
    assert mf["tick_path"] == "fused" and ml["tick_path"] == "legacy"
    # wall split satellite: both components present and sum to wall_s
    assert mf["wall_s"] == pytest.approx(
        mf["wall_host_s"] + mf["wall_device_s"])
    assert set(of) == set(ol)
    for sid in sorted(of):
        np.testing.assert_array_equal(of[sid], ol[sid],
                                      err_msg=f"session {sid}")


def test_fused_no_retrace_across_event_counts(params, prune_plan):
    """0, 1 and max snapshot/restore events per tick reuse ONE compilation
    per entry point per tier: event-free ticks hit the plain step, event
    ticks hit the fused megakernel whose order buffers are traced values
    of the static sentinel-padded shape — never shape changes."""
    plan, bn = _plan_and_bn(params, prune_plan, "reference")
    svc = GcnService(CFG, plans=(plan,), bn_stats=(bn,), qos="preempt",
                     capacity_tiers=(2,), warm=False, fused=True)
    from repro.train.steps import (make_gcn_fused_tick, make_gcn_slab_step,
                                   on_packed_constants)
    inner = on_packed_constants(make_gcn_fused_tick(CFG))
    inner_step = on_packed_constants(make_gcn_slab_step(CFG))
    traces = []
    step_traces = []

    def counted(consts, slabs, frames, valid, reset, hold,
                snap_order, rest_order, rings):
        traces.append(1)
        return inner(consts, slabs, frames, valid, reset, hold,
                     snap_order, rest_order, rings)

    def counted_step(consts, slabs, frames, valid, reset, hold):
        step_traces.append(1)
        return inner_step(consts, slabs, frames, valid, reset, hold)

    svc._fused_tick = jax.jit(counted, donate_argnums=(1, 8))
    svc._step = jax.jit(counted_step)
    rng = np.random.default_rng(11)

    def arrive(priority, T):
        h = svc.open_session(priority=priority)
        svc.submit_clip(h, rng.standard_normal((T, V, C)).astype(np.float32))
        return h

    arrive(0, 8)
    svc.tick()                       # 0 events -> plain step dispatch
    arrive(0, 8)
    svc.tick()                       # 0 events, second slot fills
    assert len(traces) == 0          # no events yet: megakernel untouched
    arrive(1, 4)
    svc.tick()                       # 1 snapshot event (preempt)
    arrive(1, 4)
    svc.tick()                       # max events for S=2: both slots evict
    assert svc.sched.preemptions >= 2
    svc.run_until_idle()             # restores drain the preempted pair
    assert svc.sched.restores == svc.sched.preemptions
    assert len(traces) == 1, "fused tick retraced within one tier"
    assert len(step_traces) == 1, "no-event step retraced within one tier"


def test_sentinel_and_overflow():
    """The scheduler's jax-free sentinel mirrors the engine's, and
    overflowing the static order buffer raises instead of retracing."""
    assert sched_mod.SNAP_SENTINEL == int(engine.SNAP_SENTINEL)
    buf = pad_event_orders([(0, 3), (1, 0)], 4)
    assert buf.shape == (4, 2) and buf.dtype == np.int32
    assert (buf[2:] == sched_mod.SNAP_SENTINEL).all()
    np.testing.assert_array_equal(buf[:2], [[0, 3], [1, 0]])
    with pytest.raises(ValueError, match="overflow"):
        pad_event_orders([(0, 0), (1, 1), (2, 2)], 2)


def _host_sched(slots, snap_ring=None):
    return SlabScheduler(slots, V, C, flush_frames=lambda n: 0,
                         first_logit_delay=1, policy="preempt",
                         snap_ring=snap_ring)


def test_event_budget_defers_surplus_preemptions():
    """A preempt storm beyond the per-tick budget defers to later ticks —
    the fixed-shape order buffers can never overflow — and every deferred
    eviction still happens."""
    S = 16
    sched = _host_sched(S, snap_ring=64)
    assert sched.max_events == sched_mod.MAX_EVENTS_PER_TICK == 8
    for sid in range(S):             # fill every slot with low priority
        sched.submit(SessionRequest(sid=sid, arrival=0, priority=0,
                                    clip=np.zeros((20, V, C), np.float32)))
    sched.tick_inputs(0, 0.0)
    assert sched.busy() == S
    for sid in range(S, 2 * S):      # a full-slab high-priority storm
        sched.submit(SessionRequest(sid=sid, arrival=1, priority=1,
                                    clip=np.zeros((4, V, C), np.float32)))
    tp = sched.tick_inputs(1, 1.0)
    assert len(tp.snapshot) == 8     # capped at the budget...
    assert len(tp.snap_order) == 8
    tp = sched.tick_inputs(2, 2.0)
    assert len(tp.snapshot) == 8     # ...and the rest evict next tick
    assert sched.preemptions == 16


def test_snapshot_ring_exhaustion_raises():
    """More live device snapshots than ring rows is a loud RuntimeError
    naming the knob, not a silent overwrite."""
    sched = _host_sched(2, snap_ring=1)
    for sid in range(2):
        sched.submit(SessionRequest(sid=sid, arrival=0, priority=0,
                                    clip=np.zeros((20, V, C), np.float32)))
    sched.tick_inputs(0, 0.0)
    for sid in range(2, 4):
        sched.submit(SessionRequest(sid=sid, arrival=1, priority=1,
                                    clip=np.zeros((4, V, C), np.float32)))
    with pytest.raises(RuntimeError, match="snap_capacity"):
        sched.tick_inputs(1, 1.0)


def test_queue_sid_index_tracks_membership():
    """The O(1) poll indexes stay consistent through push/pop/drop_if."""
    sched = _host_sched(2)
    q = sched.queue
    reqs = [SessionRequest(sid=i, arrival=i, priority=i % 2,
                           clip=np.zeros((2, V, C), np.float32))
            for i in range(5)]
    for r in reqs:
        q.push(r)
    assert all(q.get(r.sid) is r for r in reqs)
    popped = q.pop()                 # highest priority, earliest arrival
    assert q.get(popped.sid) is None
    dropped = q.drop_if(lambda it: it.sid == 4)
    assert [d.sid for d in dropped] == [4] and q.get(4) is None
    assert len(q) == 3 and all(q.get(i) is not None for i in (0, 2))


def test_poll_async_default_never_forces_readback(params, prune_plan):
    """Regression: poll() used to force the pending tick's logits to host
    on every call, so a polling client serialized the fused pipeline.
    The default poll is now async — mid-clip polls return logits=None and
    leave ``_last_logits`` as a device future — and only ``wait=True``
    (or a finishing session) pays the readback, which lands in the
    wall_device_s / device_dispatches accounting."""
    plan, bn = _plan_and_bn(params, prune_plan, "reference")
    svc = GcnService(CFG, plans=(plan,), bn_stats=(bn,), capacity_tiers=(2,),
                     fused=True)
    rng = np.random.default_rng(13)
    h = svc.open_session()
    svc.submit_clip(h, rng.standard_normal((20, V, C)).astype(np.float32))
    for _ in range(4):                    # a polling client, every tick
        svc.tick()
        st = svc.poll(h)
        assert st.state == "active" and st.logits is None
        # the tick's logits are still an un-forced device future
        assert not isinstance(svc._last_logits, np.ndarray)
    wd0 = svc.wall_device_s
    st = svc.poll(h, wait=True)           # opt-in sync point
    assert isinstance(st.logits, np.ndarray)
    assert isinstance(svc._last_logits, np.ndarray)
    assert svc.wall_device_s >= wd0
    # once forced, further async polls read the host buffer for free
    assert svc.poll(h).logits is not None
    svc.run_until_idle()
    m = svc.metrics()
    assert m["device_dispatches"] == m["ticks"]   # polling added none
    assert svc.poll(h).state == "done"
    assert np.isfinite(svc.poll(h).logits).all()


# ---------------------------------------------------------------------------
# packed constant operands (engine.pack_constants, steps.on_packed_constants)
# ---------------------------------------------------------------------------

def _assert_bits(got, want, what=""):
    """Two pytrees with the same structure (statics included) and every
    leaf equal in dtype, shape and bytes."""
    gl, gt = jax.tree_util.tree_flatten(got)
    wl, wt = jax.tree_util.tree_flatten(want)
    assert gt == wt, f"{what}: tree structure differs"
    for i, (g, w) in enumerate(zip(gl, wl)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, (what, i)
        assert g.tobytes() == w.tobytes(), f"{what}: leaf {i} differs"


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_unpack_of_pack_is_bit_identical(params, prune_plan, backend):
    """unpack(pack(plans, stats)) gives back the plans and the frozen BN
    statistics leaf for leaf, bit for bit — eagerly and inside a trace —
    with one buffer per dtype, no leaf changing dtype, and every leaf on
    an aligned offset."""
    plan, bn = _plan_and_bn(params, prune_plan, backend)
    tree = ((plan, plan), (bn, bn))
    packed = engine.pack_constants(tree)
    leaves = jax.tree_util.tree_leaves(tree)
    kinds = {np.asarray(x).dtype.name for x in leaves}
    assert {"float32", "int32"} <= kinds
    assert len(packed.buffers) == len(kinds)
    assert [b.dtype.name for b in packed.buffers] == sorted(kinds)
    assert len(packed.layout.leaves) == len(leaves)
    assert all(off % engine.PACK_ALIGN == 0
               for _, off, _ in packed.layout.leaves)
    _assert_bits(packed.unpack(), tree, "eager unpack")
    _assert_bits(jax.jit(lambda c: c.unpack())(packed), tree, "traced")
    # equal layouts are one jit-cache key
    again = engine.pack_constants(tree)
    assert again.layout == packed.layout
    assert hash(again.layout) == hash(packed.layout)


class _Shadow:
    """Wraps a service's jitted tick entry points: before each packed
    call, the same call runs in the unpacked form — ``make_gcn_slab_step``
    / ``make_gcn_fused_tick`` on the unpacked plans and a slab that
    carries its BN statistics, as the service called them before its
    constants were packed — and every output is compared bit for bit."""

    def __init__(self, svc):
        from repro.train.steps import make_gcn_fused_tick, make_gcn_slab_step

        self.plain_step = jax.jit(make_gcn_slab_step(CFG))
        self.plain_fused = jax.jit(make_gcn_fused_tick(CFG))
        self.step, self.fused = svc._step, svc._fused_tick
        self.calls = {"step": 0, "fused": 0}
        svc._step, svc._fused_tick = self.check_step, self.check_fused

    @staticmethod
    def _unpacked(consts, slabs):
        plans, stats = consts.unpack()
        assert all(s.bn_stats == {} for s in slabs)
        return plans, tuple(dataclasses.replace(s, bn_stats=st)
                            for s, st in zip(slabs, stats))

    @staticmethod
    def _bare(slabs):
        return tuple(dataclasses.replace(s, bn_stats={}) for s in slabs)

    def check_step(self, consts, slabs, *args):
        want_slabs, want = jax.block_until_ready(
            self.plain_step(*self._unpacked(consts, slabs), *args))
        got_slabs, got = self.step(consts, slabs, *args)
        _assert_bits(got, want, "step logits")
        _assert_bits(got_slabs, self._bare(want_slabs), "step slabs")
        self.calls["step"] += 1
        return got_slabs, got

    def check_fused(self, consts, slabs, *args):
        # the packed call donates its slabs and rings: compute the
        # unpacked form first
        want = jax.block_until_ready(
            self.plain_fused(*self._unpacked(consts, slabs), *args))
        got = self.fused(consts, slabs, *args)
        _assert_bits(got[1], want[1], "fused logits")
        _assert_bits(got[0], self._bare(want[0]), "fused slabs")
        _assert_bits(got[2], want[2], "fused rings")
        self.calls["fused"] += 1
        return got


def _mixed_trace(rng):
    """X (priority 0, ntu25) and Y (priority 1, ntu50) fill a 2-slot slab;
    Z (priority 2, ntu25) arrives at tick 5 and preempts X, which is
    restored when a slot frees."""
    spec = [(0, 0, "ntu25", 10), (0, 1, "ntu50", 12), (5, 2, "ntu25", 8)]
    out = []
    for i, (a, p, topo, T) in enumerate(spec):
        v = 50 if topo == "ntu50" else 25
        out.append(SessionRequest(
            sid=i, arrival=a, priority=p, topology=topo,
            clip=rng.standard_normal((T, v, C)).astype(np.float32)))
    return out


def _drive_topology_requests(svc, reqs, max_ticks=600):
    """As ``_drive_requests``, opening each session on its topology."""
    pending = sorted(reqs, key=lambda r: r.arrival)
    i = 0
    while svc.now < max_ticks:
        while i < len(pending) and pending[i].arrival <= svc.now:
            r = pending[i]
            h = svc.open_session(priority=r.priority, arrival=r.arrival,
                                 topology=r.topology)
            svc.submit_clip(h, r.clip)
            i += 1
        if svc.idle():
            if i == len(pending):
                break
            svc.advance_clock(pending[i].arrival)
            continue
        svc.tick()
    assert svc.idle(), "service did not drain within the tick budget"
    return svc.metrics()


PACKED_CASES = {
    # preemptions, restores (fused event ticks) and an elastic grow/shrink
    "single-elastic": dict(topologies=("ntu25",), capacity_tiers=(2, 4)),
    # two skeletons in one slab: one packed-constants dispatch per group
    "mixed": dict(topologies=("ntu25", "ntu50"), capacity_tiers=(2,)),
}


@pytest.mark.parametrize("case", list(PACKED_CASES))
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_packed_tick_matches_unpacked_step(backend, case):
    """Every jitted call of the service's tick — plain slab steps, fused
    event ticks with preemption and restore, each skeleton group's
    dispatch, ticks before and after an elastic migration — returns the
    same slabs, logits and snapshot rings, bit for bit, as the unpacked
    per-tick step on the unpacked plans and statistics."""
    kw = PACKED_CASES[case]
    ccfg = CapacityConfig(tiers=kw["capacity_tiers"], grow_patience=1,
                          shrink_patience=2, cooldown=3)
    svc = GcnService(CFG, backend=backend, qos="preempt", seed=0,
                     capacity_config=ccfg, warm=False, **kw)
    shadow = _Shadow(svc)
    rng = np.random.default_rng(7)
    if case == "mixed":
        m = _drive_topology_requests(svc, _mixed_trace(rng))
    else:
        _, m = _drive_requests(svc, _qos_trace(rng))
        assert m["migrations"] > 0
    assert m["preemptions"] > 0 and m["restores"] > 0
    assert shadow.calls["fused"] > 0 and shadow.calls["step"] > 0
    assert m["device_dispatches"] == sum(shadow.calls.values())


_COMPILES = {"on": False, "n": 0}


def _count_compiles(name, secs, **kw):
    if _COMPILES["on"] and name in (
            "/jax/core/compile/jaxpr_trace_duration",
            "/jax/core/compile/backend_compile_duration"):
        _COMPILES["n"] += 1


jax.monitoring.register_event_duration_secs_listener(_count_compiles)


@pytest.mark.parametrize("case", list(PACKED_CASES))
def test_packed_tick_compiles_nothing_after_warm_up(case):
    """Once the service is built and warmed, its packed-constant ticks
    compile nothing: not across event counts, skeleton groups or an
    elastic migration."""
    kw = PACKED_CASES[case]
    ccfg = CapacityConfig(tiers=kw["capacity_tiers"], grow_patience=1,
                          shrink_patience=2, cooldown=3)
    svc = GcnService(CFG, backend="reference", qos="preempt", seed=0,
                     capacity_config=ccfg, **kw)
    rng = np.random.default_rng(7)
    _COMPILES.update(on=True, n=0)
    try:
        if case == "mixed":
            m = _drive_topology_requests(svc, _mixed_trace(rng))
        else:
            _, m = _drive_requests(svc, _qos_trace(rng))
            assert m["migrations"] > 0
    finally:
        _COMPILES["on"] = False
    assert m["preemptions"] > 0 and m["restores"] > 0
    assert _COMPILES["n"] == 0
