"""The `repro.serving` service API — handle protocol + elastic capacity.

The tentpole locks:

* **Handle protocol** — open/submit/poll/close through ``GcnService``
  (including starved open sessions, which are *held* in place, never
  zero-padded) produces the same logits as an uninterrupted single-stream
  run.
* **Elastic migration parity** (the acceptance criterion): a session
  migrated across capacity tiers (grow *and* shrink, active mid-clip)
  produces logits equal to the uninterrupted fixed-capacity session — on
  both backends — and a bystander session riding along through a
  migration is *bit-identical* to its unmigrated run.
* **No retrace within a tier**: admissions, holds, drains and occupancy
  changes share one compiled step per tier.
* **Hysteresis never thrashes**: the capacity manager under an
  oscillating step load never emits grow→shrink→grow inside 3 ticks.

Plus the satellite units: the (backend, slots, qos, capacity, load)
BENCH merge key, the scheduler's open-session hold bookkeeping, and the
single-source serve batch default.
"""
import json
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serving
from repro.configs import get_config
from repro.core.agcn import engine
from repro.core.agcn import model as M
from repro.core.pruning.plan import build_prune_plan
from repro.serving import (CapacityConfig, CapacityManager, GcnService,
                           SessionRequest, bench_key, write_bench)

CFG = get_config("agcn-2s", reduced=True)
V, C = CFG.gcn_joints, CFG.gcn_in_channels
REPO = pathlib.Path(__file__).resolve().parent.parent


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


def _run_independent(plan, bn, clip):
    """One session alone: batch-1 step_frame over clip + flush drain —
    the uninterrupted fixed-capacity baseline."""
    state = engine.init_stream_state(plan, 1, bn_stats=bn)
    step = jax.jit(engine.step_frame)
    xc = jnp.asarray(clip)[None]
    T = xc.shape[1]
    zeros = jnp.zeros_like(xc[:, 0])
    logits = None
    for r in range(T + engine.stream_flush_frames(plan, T)):
        frame = xc[:, r] if r < T else zeros
        state, logits = step(plan, state, frame, jnp.asarray(r < T))
    return np.asarray(logits)[0]


def _drive(svc, arrivals, max_ticks=600):
    """Open+submit each (clip, kwargs) at its arrival tick, run to idle;
    returns {index: final logits}."""
    handles = {}
    out = {}
    pending = sorted(range(len(arrivals)), key=lambda i: arrivals[i][0])
    i = 0
    while svc.now < max_ticks:
        while i < len(pending) and arrivals[pending[i]][0] <= svc.now:
            at, clip, kw = arrivals[pending[i]]
            h = svc.open_session(arrival=at, **kw)
            svc.submit_clip(h, clip)
            handles[pending[i]] = h
            i += 1
        if svc.idle():
            if i == len(pending):
                break
            svc.advance_clock(arrivals[pending[i]][0])
            continue
        svc.tick()
    assert svc.idle(), "service did not drain within the tick budget"
    for k, h in handles.items():
        st = svc.poll(h)
        assert st.state == "done"
        out[k] = st.logits
    return out


# ------------------------------------------------------- handle protocol

@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_handle_api_matches_independent(params, prune_plan, backend):
    """open/submit/poll/close with starvation gaps (ticks where the open
    session has no buffered frame are held, not padded) equals the
    uninterrupted single-stream run, on the paper's pruned+quant target."""
    plan, bn = _plan_and_bn(params, prune_plan, backend)
    svc = GcnService(CFG, backend=backend, plans=(plan,), bn_stats=(bn,),
                     capacity_tiers=(2,))
    rng = np.random.default_rng(5)
    T = 10
    clip = rng.standard_normal((T, V, C)).astype(np.float32)
    h = svc.open_session()
    fed = 0
    # feed irregularly: some ticks get 0 frames (hold), some 2 (buffered)
    for burst in (1, 0, 2, 0, 0, 3, 1, 0, 3):
        for _ in range(burst):
            svc.submit(h, clip[fed])
            fed += 1
        st = svc.poll(h)
        assert st.state in ("queued", "active")
        svc.tick()
    assert fed == T
    svc.close(h)
    assert svc.poll(h).state in ("active", "draining")
    svc.run_until_idle()
    st = svc.poll(h)
    assert st.state == "done"
    assert st.record is not None and st.record.frames == T
    want = _run_independent(plan, bn, clip)
    np.testing.assert_allclose(st.logits, want, atol=1e-3, rtol=1e-3,
                               err_msg=f"held session ({backend})")


def test_poll_states_and_errors(params):
    """poll reports queued→active→draining→done; submit validates frame
    shape; submitting to a closed session and unknown handles raise."""
    plan = engine.build_execution_plan(params, CFG, backend="reference")
    bn = engine.collect_bn_stats(
        plan, jax.random.normal(jax.random.PRNGKey(1),
                                (2, CFG.gcn_frames, V, C)))
    svc = GcnService(CFG, plans=(plan,), bn_stats=(bn,), capacity_tiers=(1,))
    h0 = svc.open_session()
    h1 = svc.open_session()
    assert svc.poll(h0).state == "queued" and svc.poll(h1).state == "queued"
    clip = np.zeros((2, V, C), np.float32)
    svc.submit_clip(h0, clip)
    svc.tick()
    assert svc.poll(h0).state == "active"
    assert svc.poll(h1).state == "queued"      # one slot only
    svc.tick()
    svc.tick()
    assert svc.poll(h0).state == "draining"
    # default poll is async (no forced readback); wait=True syncs
    assert svc.poll(h0, wait=True).logits is not None
    with pytest.raises(ValueError):
        svc.submit(h0, clip[0])                # closed stream
    with pytest.raises(ValueError):
        svc.submit(h1, np.zeros((V + 1, C)))   # wrong shape
    with pytest.raises(KeyError):
        svc.poll(serving.SessionHandle(sid=999))
    svc.submit_clip(h1, clip)
    svc.run_until_idle()
    assert svc.poll(h0).state == "done" and svc.poll(h1).state == "done"


def test_run_until_idle_raises_on_unclosed_session(params):
    """An open session that is never closed holds its slot forever — the
    drain helper must fail loudly instead of spinning."""
    plan = engine.build_execution_plan(params, CFG, backend="reference")
    bn = engine.collect_bn_stats(
        plan, jax.random.normal(jax.random.PRNGKey(1),
                                (2, CFG.gcn_frames, V, C)))
    svc = GcnService(CFG, plans=(plan,), bn_stats=(bn,), capacity_tiers=(1,))
    h = svc.open_session()
    svc.submit(h, np.zeros((V, C), np.float32))
    with pytest.raises(RuntimeError, match="close"):
        svc.run_until_idle(max_ticks=5)


# --------------------------------------------------- elastic capacity

ELASTIC_CCFG = CapacityConfig(tiers=(2, 4), grow_patience=1,
                              shrink_patience=2, cooldown=3)


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_elastic_migration_parity(params, prune_plan, backend):
    """The acceptance lock: sessions migrated across capacity tiers (a
    grow with two active mid-clip sessions, then a shrink with one) equal
    the uninterrupted fixed-capacity runs on both backends."""
    plan, bn = _plan_and_bn(params, prune_plan, backend)
    svc = GcnService(CFG, backend=backend, plans=(plan,), bn_stats=(bn,),
                     capacity_tiers=(2, 4), capacity_config=ELASTIC_CCFG)
    rng = np.random.default_rng(9)
    lengths = (26, 20, 8, 8)
    clips = [rng.standard_normal((T, V, C)).astype(np.float32)
             for T in lengths]
    # sid 0/1 admitted at the 2-tier; sid 2/3 arrive while both slots are
    # busy -> demand 4 -> grow to 4 migrates two active sessions; after
    # the short sessions drain, demand 1 -> shrink migrates the long one
    arrivals = [(0, clips[0], {}), (1, clips[1], {}),
                (4, clips[2], {}), (4, clips[3], {})]
    got = _drive(svc, arrivals)
    events = svc.capman.events
    assert any(e.new > e.old and e.busy > 0 for e in events), events
    assert any(e.new < e.old and e.busy > 0 for e in events), events
    for i, clip in enumerate(clips):
        want = _run_independent(plan, bn, clip)
        np.testing.assert_allclose(got[i], want, atol=1e-3, rtol=1e-3,
                                   err_msg=f"session {i} ({backend})")


def test_elastic_bystander_bit_identity(params, prune_plan):
    """A session that merely rides along through grow+shrink migrations
    (snapshot-gather → scatter into the other tier's slab) is *bit-
    identical* to the same session served at fixed capacity — migration
    is an exact state copy and per-slot math does not depend on S."""
    plan, bn = _plan_and_bn(params, prune_plan, "reference")
    rng = np.random.default_rng(10)
    clips = [rng.standard_normal((T, V, C)).astype(np.float32)
             for T in (26, 8, 8)]
    arrivals = [(0, clips[0], {}), (2, clips[1], {}), (2, clips[2], {})]

    fixed = GcnService(CFG, plans=(plan,), bn_stats=(bn,),
                       capacity_tiers=(4,))
    elastic = GcnService(CFG, plans=(plan,), bn_stats=(bn,),
                         capacity_tiers=(2, 4),
                         capacity_config=ELASTIC_CCFG)
    want = _drive(fixed, arrivals)
    got = _drive(elastic, arrivals)
    assert elastic.capman.events, "no migration happened"
    for i in range(len(clips)):
        np.testing.assert_array_equal(got[i], want[i],
                                      err_msg=f"session {i}")


def test_no_retrace_within_tier(params):
    """Admissions, holds, flush drains and occupancy changes are traced
    masking: one compilation of the slab step serves a whole tier."""
    plan = engine.build_execution_plan(params, CFG, backend="reference")
    bn = engine.collect_bn_stats(
        plan, jax.random.normal(jax.random.PRNGKey(1),
                                (2, CFG.gcn_frames, V, C)))
    # these ticks carry no snapshot/restore events, so every one runs the
    # plain step wrapped below; the event tick's no-retrace guard lives
    # in tests/test_fused_tick.py
    svc = GcnService(CFG, plans=(plan,), bn_stats=(bn,), capacity_tiers=(3,),
                     warm=False)
    # count traces of the service's own step by re-jitting a counting
    # wrapper around the same step factory the service uses
    from repro.train.steps import make_gcn_slab_step, on_packed_constants
    inner = on_packed_constants(make_gcn_slab_step(CFG))
    traces = []

    def counted(consts, slabs, frames, valid, reset, hold):
        traces.append(1)
        return inner(consts, slabs, frames, valid, reset, hold)

    svc._step = jax.jit(counted)
    rng = np.random.default_rng(3)
    h0 = svc.open_session()
    svc.submit_clip(h0, rng.standard_normal((4, V, C)).astype(np.float32))
    svc.tick()
    h1 = svc.open_session()               # open session: starved -> hold
    svc.submit(h1, rng.standard_normal((V, C)).astype(np.float32))
    svc.tick()
    svc.tick()                            # h1 starves (hold), h0 drains
    svc.close(h1)
    svc.run_until_idle()
    assert svc.poll(h0).state == "done" and svc.poll(h1).state == "done"
    assert len(traces) == 1


@pytest.mark.parametrize("topologies", [("ntu25",), ("ntu25", "ntu50")],
                         ids=["single", "mixed"])
def test_call_arrays_counts_bare_slabs_and_packed_constants(topologies):
    """``call_arrays`` grows, per jitted tick call, by 2 x the slabs'
    per-slot leaves (in and out) + the packed constant buffers + frames
    and 3 masks + the logits — the arrays that really cross the call —
    and no plan array or frozen BN statistic crosses it."""
    svc = GcnService(CFG, backend="reference", capacity_tiers=(2,),
                     topologies=topologies, seed=0, warm=False)
    per_slot = sum(len(jax.tree_util.tree_leaves(
        engine.snapshot_slots(s, 0))) for s in svc.slabs)
    plan_ids = {id(x) for x in jax.tree_util.tree_leaves(
        (svc.plans, svc.bn_stats))}
    inner = svc._step
    seen = []

    def recorded(consts, slabs, *args):
        assert isinstance(consts, engine.PackedConstants)
        assert all(s.bn_stats == {} for s in slabs)
        leaves = jax.tree_util.tree_leaves((consts, slabs, args))
        assert not plan_ids & {id(x) for x in leaves}
        assert len(jax.tree_util.tree_leaves(slabs)) == per_slot
        out = inner(consts, slabs, *args)
        seen.append(len(leaves) + len(jax.tree_util.tree_leaves(out)))
        assert seen[-1] == 2 * per_slot + len(consts.buffers) + 4 + 1
        return out

    svc._step = recorded
    rng = np.random.default_rng(5)
    for t in topologies:
        h = svc.open_session(topology=t)
        vt = svc._topos[t].num_joints
        svc.submit_clip(h, rng.standard_normal((6, vt, C))
                        .astype(np.float32))
    per_tick = []
    while not svc.idle():
        before, n = svc.call_arrays, len(seen)
        svc.tick()
        per_tick.append(svc.call_arrays - before)
        assert per_tick[-1] == sum(seen[n:])
    # one dispatch per occupied skeleton group, on every tick
    assert len(seen) == svc.metrics()["device_dispatches"]
    assert max(per_tick) == len(topologies) * seen[0]
    assert svc.metrics()["call_arrays"] == sum(per_tick)


def test_capacity_manager_hysteresis_never_thrashes():
    """Under a worst-case oscillating step load (demand flips between
    over- and under-capacity every tick), resize events are spaced by at
    least the cooldown — never grow→shrink→grow inside 3 ticks — and a
    steady load settles at one tier."""
    cm = CapacityManager(CapacityConfig(tiers=(2, 4, 8), grow_patience=1,
                                        shrink_patience=1, cooldown=3))
    for tick in range(60):                # square-wave step load
        demand = 5 if (tick // 1) % 2 == 0 else 1
        busy = min(demand, cm.capacity)
        cm.observe(busy, demand - busy, tick)
    for a, b in zip(cm.events, cm.events[1:]):
        assert b.tick - a.tick >= 3, (a, b)
    # grow→shrink→grow inside any 3-tick window is impossible
    for a, b, c in zip(cm.events, cm.events[1:], cm.events[2:]):
        if a.new > a.old and b.new < b.old and c.new > c.old:
            assert c.tick - a.tick > 3

    # steady high load: grow once to the fitting tier, then no events
    cm = CapacityManager(CapacityConfig(tiers=(2, 4, 8), grow_patience=2,
                                        shrink_patience=4, cooldown=4))
    for tick in range(30):
        cm.observe(min(6, cm.capacity), 6 - min(6, cm.capacity), tick)
    assert [(-e.old, e.new) for e in cm.events] == [(-2, 8)]
    # steady lull afterwards: walk down one tier per patience+cooldown
    for tick in range(30, 60):
        cm.observe(1, 0, tick)
    assert cm.capacity == 2
    assert [e.new for e in cm.events] == [8, 4, 2]


def test_capacity_manager_validation():
    """Tier/cooldown validation and start_tier selection."""
    with pytest.raises(ValueError):
        CapacityConfig(tiers=())
    with pytest.raises(ValueError):
        CapacityConfig(tiers=(2, 2))
    with pytest.raises(ValueError):
        CapacityConfig(tiers=(2, 4), cooldown=1)
    with pytest.raises(ValueError):
        CapacityManager(CapacityConfig(tiers=(2, 4)), start_tier=3)
    cm = CapacityManager(CapacityConfig(tiers=(8, 2, 4)), start_tier=4)
    assert cm.capacity == 4 and cm.tiers == (2, 4, 8)


def test_scheduler_resize_compacts_and_validates():
    """SlabScheduler.resize packs active sessions into the low slots,
    returns the old→new mapping, and refuses a shrink below busy()."""
    sched = serving.SlabScheduler(4, V, C, flush_frames=lambda T: 1,
                                  first_logit_delay=1)
    clip = np.zeros((3, V, C), np.float32)
    for sid in range(3):
        sched.submit(SessionRequest(sid=sid, arrival=0, clip=clip))
    sched.tick_inputs(0, 0.0)
    sched.tick_outputs(0, np.zeros((4, 8)), 0.0)
    sched.slots[1] = None                 # fake an eviction: occupancy 0,2
    mapping = sched.resize(2)
    assert mapping == {0: 0, 2: 1}
    assert sched.busy() == 2 and len(sched.slots) == 2
    with pytest.raises(ValueError):
        sched.resize(1)


def test_scheduler_holds_starved_open_session():
    """Host-side hold bookkeeping: an admitted open session with an empty
    buffer is held (no rel advance, no valid frame), resumes when frames
    arrive, and drains only after close()."""
    sched = serving.SlabScheduler(1, V, C, flush_frames=lambda T: 2,
                                  first_logit_delay=1)
    req = SessionRequest(sid=0, arrival=0)          # open: clip=None
    sched.submit(req)
    tp = sched.tick_inputs(0, 0.0)
    assert tp.hold[0] and not tp.valid[0]           # admitted, starved
    sched.tick_outputs(0, np.zeros((1, 8)), 0.0)
    assert sched.slots[0].rel == 0                  # held: no advance
    req.push_frame(np.ones((V, C), np.float32))
    tp = sched.tick_inputs(1, 0.0)
    assert tp.valid[0] and not tp.hold[0]
    np.testing.assert_array_equal(tp.frames[0], np.ones((V, C)))
    sched.tick_outputs(1, np.zeros((1, 8)), 0.0)
    assert sched.slots[0].rel == 1 and sched.slots[0].total is None
    req.close()
    done = []
    for tick in range(2, 6):
        tp = sched.tick_inputs(tick, 0.0)
        assert not tp.hold[0] and not tp.valid[0]   # flush drain
        done += sched.tick_outputs(tick, np.zeros((1, 8)), 0.0)
    assert [r.sid for r in done] == [0]
    assert done[0].frames == 1
    assert sched.valid_frames == 1


# ------------------------------------------------------- satellite units

def test_write_bench_elastic_rows_do_not_collide(tmp_path):
    """The merge key includes capacity and load: an elastic run, its fixed
    baselines under burst load, and the legacy steady-state rows under the
    same (backend, slots, qos) all coexist; re-writing one key replaces
    only that row."""
    path = str(tmp_path / "BENCH_sessions.json")
    legacy = {"backend": "reference", "slots": 2, "qos": "fifo",
              "frames_per_s": 100.0}                 # pre-elastic row
    write_bench([legacy], path)
    elastic = {"backend": "reference", "slots": 2, "qos": "fifo",
               "capacity": "elastic:2,4,8", "load": "burst",
               "frames_per_s": 300.0, "records": ["dropme"]}
    fixed_burst = {"backend": "reference", "slots": 2, "qos": "fifo",
                   "capacity": "fixed", "load": "burst",
                   "frames_per_s": 150.0}
    write_bench([elastic, fixed_burst], path)
    rows = json.loads(open(path).read())
    assert len(rows) == 3                            # nothing clobbered
    assert rows[0] == legacy
    assert rows[1]["capacity"] == "elastic:2,4,8"
    assert "records" not in rows[1]
    assert bench_key(legacy) == ("reference", 2, "fifo", "fixed", "poisson",
                                 1, 1, "demand", "", "ntu25", False, 0.0)
    assert bench_key(elastic) != bench_key(fixed_burst) != bench_key(legacy)
    # replace just the elastic row
    write_bench([{**elastic, "frames_per_s": 311.0}], path)
    rows = json.loads(open(path).read())
    assert len(rows) == 3
    assert rows[1]["frames_per_s"] == 311.0
    assert rows[0] == legacy and rows[2] == fixed_burst


def test_run_sessions_elastic_end_to_end():
    """run_sessions(capacity_tiers=..., load="burst"): every session
    completes, the elastic accounting is populated, and the row carries
    the capacity/load merge axes."""
    res = serving.run_sessions(CFG, slots=2, n_sessions=6,
                               mean_interarrival=8.0, lengths=(8,),
                               backend="reference", seed=0,
                               capacity_tiers=(2, 4, 8), load="burst")
    assert res["sessions"] == 6
    assert res["capacity"] == "elastic:2,4,8"
    assert res["load"] == "burst"
    assert res["migrations"] == (res["migrations_grow"]
                                 + res["migrations_shrink"])
    assert res["migrations"] >= 1
    assert res["migration_ms_mean"] >= 0.0
    assert sum(res["tier_ticks"].values()) > 0
    assert res["capacity_final"] in (2, 4, 8)
    for rec in res["records"]:
        assert np.isfinite(rec.logits).all()


def test_serve_batch_default_resolves_in_config():
    """--batch 0 family/mode defaults live in ModelConfig.serve_batch:
    explicit requests win, gcn clip/stream differ, LM families fall back
    to the global default — no per-subcommand branches."""
    gcn = get_config("agcn-2s", reduced=True)
    lm = get_config("smollm-360m", reduced=True)
    assert gcn.serve_batch("clip") == 8
    assert gcn.serve_batch("stream") == 4
    assert gcn.serve_batch("clip", 3) == 3
    assert lm.serve_batch("lm") == 4
    assert lm.serve_batch("lm", 16) == 16


def test_api_surface_gate_matches_checked_in_snapshot():
    """tools/check_api.py: the checked-in docs/api_surface.txt matches the
    source (the --docs tier gate), and drift is detected."""
    r = subprocess.run([sys.executable, str(REPO / "tools/check_api.py")],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import check_api
        surface = check_api.build_surface()
        assert "repro.serving.service.GcnService.open_session" in surface
        assert "repro.core.agcn.engine.step_frames" in surface
        # determinism: two builds render identically
        assert surface == check_api.build_surface()
    finally:
        sys.path.pop(0)


# ------------------------------------------- long-lived-service bugfixes

def test_deadline_expired_queue_never_grows_capacity(params):
    """Regression: under qos="deadline" the capacity manager used to see
    queued-but-already-expired sessions as demand and grow a tier for
    work it would immediately shed.  Expired sessions are swept *before*
    the demand observation, so an expired-heavy queue leaves capacity at
    the bottom tier with zero resize events."""
    plan = engine.build_execution_plan(params, CFG, backend="reference")
    bn = engine.collect_bn_stats(
        plan, jax.random.normal(jax.random.PRNGKey(1),
                                (2, CFG.gcn_frames, V, C)))
    svc = GcnService(CFG, plans=(plan,), bn_stats=(bn,), qos="deadline",
                     capacity_tiers=(2, 4), capacity_config=ELASTIC_CCFG)
    clip = np.zeros((6, V, C), np.float32)
    live = [svc.open_session(deadline=10_000) for _ in range(2)]
    dead = [svc.open_session(deadline=-1) for _ in range(4)]  # expired at 0
    for h in live + dead:
        svc.submit_clip(h, clip)
    svc.run_until_idle()
    assert svc.capman.events == []                # no spurious grow
    assert svc.capacity == 2
    m = svc.metrics()
    assert m["sessions"] == 2 and m["deadline_missed"] == 4
    for h in live:
        assert svc.poll(h).state == "done"
    for h in dead:
        assert svc.poll(h).state == "missed"


def test_advance_clock_idle_lull_shrinks_capacity(params):
    """Regression: an idle elastic service never saw shrink ticks (the
    capacity manager only observed inside tick()), so a traffic lull left
    it parked at the top tier forever.  advance_clock feeds the skipped
    ticks to the capacity manager, walks the ladder down and migrates
    once — capacity returns to the bottom tier before the next arrival."""
    plan = engine.build_execution_plan(params, CFG, backend="reference")
    bn = engine.collect_bn_stats(
        plan, jax.random.normal(jax.random.PRNGKey(1),
                                (2, CFG.gcn_frames, V, C)))
    # shrink_patience=6 > the drain tail, so the busy phase ends still
    # parked at the top tier — only the lull can walk it back down
    ccfg = CapacityConfig(tiers=(2, 4), grow_patience=1,
                          shrink_patience=6, cooldown=3)
    svc = GcnService(CFG, plans=(plan,), bn_stats=(bn,),
                     capacity_tiers=(2, 4), capacity_config=ccfg)
    rng = np.random.default_rng(6)
    arrivals = [(0, rng.standard_normal((8, V, C)).astype(np.float32), {})
                for _ in range(4)]
    _drive(svc, arrivals)                    # burst grows 2 -> 4
    assert any(e.new > e.old for e in svc.capman.events)
    assert svc.capacity == 4                 # still at the top tier
    svc.advance_clock(svc.now + 200)         # the lull
    assert svc.capacity == 2                 # walked back down
    assert svc.now >= 200
    # still serves correctly afterwards at the bottom tier
    h = svc.open_session()
    svc.submit_clip(h, arrivals[0][1])
    svc.run_until_idle()
    np.testing.assert_array_equal(svc.poll(h).logits,
                                  _drive(GcnService(CFG, plans=(plan,),
                                                    bn_stats=(bn,),
                                                    capacity_tiers=(2,)),
                                         arrivals[:1])[0])


def test_service_bookkeeping_bounded_and_keep_records(params):
    """Regression: a long-lived service accumulated per-session dicts and
    full record lists without bound.  With retain_records=3, serving 9
    sessions leaves every host map trimmed to the retention bound, while
    the lifetime aggregates in metrics() still count all 9;
    metrics(keep_records=1) caps the returned record list."""
    plan = engine.build_execution_plan(params, CFG, backend="reference")
    bn = engine.collect_bn_stats(
        plan, jax.random.normal(jax.random.PRNGKey(1),
                                (2, CFG.gcn_frames, V, C)))
    svc = GcnService(CFG, plans=(plan,), bn_stats=(bn,), capacity_tiers=(2,),
                     retain_records=3)
    clip = np.zeros((4, V, C), np.float32)
    for _ in range(9):
        h = svc.open_session()
        svc.submit_clip(h, clip)
        svc.run_until_idle()
        assert svc.poll(h).state == "done"   # newest is always pollable
    assert len(svc._records) <= 3
    assert len(svc._sessions) <= 3
    assert len(svc.sched.completed) <= 3
    m = svc.metrics()
    assert m["sessions"] == 9                # lifetime counter, not len()
    assert len(m["records"]) <= 3
    assert len(svc.metrics(keep_records=1)["records"]) == 1
    with pytest.raises(ValueError):
        GcnService(CFG, plans=(plan,), bn_stats=(bn,), retain_records=0)


# --------------------------------------------- SLO overload coverage gap

def test_overload_demand_queues_slo_sheds(params, prune_plan):
    """Sustained overload at a saturated top tier — the cell the demand
    policy has no answer for.  A drip of low-priority sessions keeps both
    slots of the (only) tier busy end-to-end; a high-priority session
    arrives mid-overload.  Under ``policy="demand"`` there is no higher
    tier to grow into and no admission control, so the high-priority
    session waits out a full slot turnover behind *active* low-priority
    work and breaches the 50-tick first-logit bound.  Under
    ``policy="slo"`` the controller sheds the late low-priority opens at
    the top tier, a slot is free when the high-priority session arrives,
    and its first-logit latency holds the bound — on the identical
    arrival sequence."""
    plan, bn = _plan_and_bn(params, prune_plan, "reference")
    rng = np.random.default_rng(6)
    T = 12
    target = 50
    # lows at 0, 2, then every 12 ticks; one high mid-overload at 70
    lows = [0, 2] + list(range(12, 97, 12))
    arrivals = [(t, 0) for t in lows] + [(70, 1)]

    def run(policy):
        svc = GcnService(
            CFG, plans=(plan,), bn_stats=(bn,), capacity_tiers=(2,),
            policy=policy,
            slo_config=(serving.SloConfig(
                target_p99_ticks=target, window=16, breach_patience=2,
                recover_patience=16, shed_mode="reject")
                if policy == "slo" else None))
        pending = sorted(arrivals)
        handles, i = [], 0
        while svc.now < 400:
            while i < len(pending) and pending[i][0] <= svc.now:
                at, prio = pending[i]
                h = svc.open_session(priority=prio, arrival=at)
                if svc.poll(h).state != "rejected":
                    svc.submit_clip(
                        h, rng.standard_normal((T, V, C)).astype(np.float32))
                handles.append((h, prio))
                i += 1
            if svc.idle():
                if i == len(pending):
                    break
                svc.advance_clock(pending[i][0])
                continue
            svc.tick()
        assert svc.idle()
        return svc, handles

    svc_d, hd = run("demand")
    svc_s, hs = run("slo")
    md, ms = svc_d.metrics(), svc_s.metrics()
    hp_d = md["latency_ms_by_priority"]["1"]["first_logit_p99_ticks"]
    hp_s = ms["latency_ms_by_priority"]["1"]["first_logit_p99_ticks"]
    # demand admits everything and the high-priority session eats the
    # turnover wait; slo sheds lows so it latches within the bound
    assert hp_d > target
    assert hp_s <= target
    assert ms["sessions_rejected"] > 0
    assert md.get("sessions_rejected", 0) == 0
    # every high-priority session completes under both policies, and the
    # rejected lows really are the shed ones (poll says so)
    assert all(svc_s.poll(h).state == "done" for h, p in hs if p == 1)
    assert sum(svc_s.poll(h).state == "rejected"
               for h, p in hs) == ms["sessions_rejected"]
    assert all(svc_d.poll(h).state == "done" for h, _ in hd)


def test_streaming_refuses_published_ck():
    """The published C_k pools over the whole clip, which a live session
    does not have: the slab, GcnService and ``serve sessions --ck``
    (``run_sessions``) refuse ck_form='clip' and say why."""
    import dataclasses

    cfg = dataclasses.replace(get_config("agcn-2s", reduced=True),
                              use_ck=True, ck_form="clip")
    why = "no whole clip"
    with pytest.raises(ValueError, match=why):
        GcnService(cfg, capacity_tiers=(2,))
    with pytest.raises(ValueError, match=why):
        serving.run_sessions(cfg, slots=2, n_sessions=2, use_ck=True)
    plan = engine.build_execution_plan(
        M.init_params(cfg, jax.random.PRNGKey(0)), cfg, None)
    x = jnp.zeros((2, cfg.gcn_frames, cfg.gcn_joints, cfg.gcn_in_channels))
    with pytest.raises(ValueError, match=why):
        engine.init_session_slab(plan, 2, x_calib=x)
