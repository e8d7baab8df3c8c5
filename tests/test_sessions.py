"""Multi-session slab serving — the session-scheduler correctness contract.

The lock: S sessions streamed *concurrently* through one session slab
(staggered admissions, different clip lengths, slot recycling through the
traced reset mask) must produce, at each session's eviction, the same
logits as S *independent* single-stream ``step_frame`` runs — on both
backends.  Plus host-side SlabScheduler bookkeeping (admission queueing,
occupancy, first-logit ticks), Poisson load-generation determinism,
``reset_slots`` isolation, and the no-retrace invariant of the slab step.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.agcn import engine
from repro.core.agcn import model as M
from repro.core.pruning.plan import build_prune_plan
from repro import serving as sess

CFG = get_config("agcn-2s", reduced=True)
V, C = CFG.gcn_joints, CFG.gcn_in_channels


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def x():
    return jax.random.normal(jax.random.PRNGKey(1), (2, CFG.gcn_frames, V, C))


@pytest.fixture(scope="module")
def prune_plan(params):
    sw = [np.asarray(b["Wk"]) for b in params["blocks"]]
    return build_prune_plan(sw, CFG.gcn_channels, [1.0, 0.5, 0.5, 0.5],
                            "cav-70-1", input_skip=2)


def _run_scheduled(plan, reqs, slots, policy="fifo"):
    """Drive a slab through the SlabScheduler under ``policy``, executing
    the TickPlan's snapshot/restore orders; return ({sid: final logits},
    bn_stats, scheduler)."""
    bn = engine.collect_bn_stats(
        plan, jax.random.normal(jax.random.PRNGKey(1),
                                (2, CFG.gcn_frames, V, C)))
    slab = engine.init_session_slab(plan, slots, bn_stats=bn)
    sched = sess.SlabScheduler(
        slots, V, C,
        flush_frames=lambda T: engine.stream_flush_frames(plan, T),
        first_logit_delay=engine.stream_first_logit_delay(plan),
        policy=policy)
    step = jax.jit(engine.step_frames)
    snap_fn = jax.jit(engine.snapshot_slots)
    rest_fn = jax.jit(engine.restore_slots)
    snaps = {}
    pending = sorted(reqs, key=lambda r: r.arrival)
    i = 0
    for tick in range(500):
        while i < len(pending) and pending[i].arrival <= tick:
            sched.submit(pending[i])
            i += 1
        if i == len(pending) and sched.idle():
            break
        tp = sched.tick_inputs(tick, 0.0)
        for s, sid in tp.snapshot:
            snaps[sid] = snap_fn(slab, jnp.asarray(s))
        for s, sid in tp.restore:
            slab = rest_fn(slab, jnp.asarray(s), snaps.pop(sid))
        slab, logits = step(plan, slab, jnp.asarray(tp.frames),
                            jnp.asarray(tp.valid), jnp.asarray(tp.reset))
        sched.tick_outputs(tick, np.asarray(logits), 0.0)
    assert sched.idle(), "scheduler did not drain within the tick budget"
    return {r.sid: r.logits for r in sched.completed}, bn, sched


def _run_independent(plan, bn, clip):
    """One session alone: batch-1 step_frame over clip + flush drain."""
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


# ------------------------------------------------------------------- parity

@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_slab_matches_independent_streams(params, prune_plan, backend):
    """The tentpole lock: staggered concurrent sessions through the slab
    (including a queued session admitted into a *recycled* slot) equal
    independent single-stream runs, on the paper's pruned+quant target."""
    plan = engine.build_execution_plan(params, CFG, prune_plan, quant=True,
                                       backend=backend)
    rng = np.random.default_rng(3)
    lengths = (24, 14, 10)                 # different clip lengths
    clips = [rng.standard_normal((T, V, C)).astype(np.float32)
             for T in lengths]
    # 2 slots, 3 sessions: sid 2 queues until sid 1's drain frees its slot
    reqs = [sess.SessionRequest(sid=i, arrival=a, clip=c)
            for i, (a, c) in enumerate(zip((0, 4, 9), clips))]
    got, bn, _ = _run_scheduled(plan, reqs, slots=2)
    assert sorted(got) == [0, 1, 2]
    for i, clip in enumerate(clips):
        want = _run_independent(plan, bn, clip)
        np.testing.assert_allclose(got[i], want, atol=1e-3, rtol=1e-3,
                                   err_msg=f"session {i} (backend={backend})")


def test_slab_step_never_retraces(params, x):
    """Admissions, evictions and occupancy changes are traced masking: one
    compilation serves every (reset, valid) combination."""
    plan = engine.build_execution_plan(params, CFG, backend="reference")
    slab = engine.init_session_slab(plan, 3, x_calib=x)
    traces = []

    @jax.jit
    def counted(plan, slab, frames, valid, reset):
        traces.append(1)
        return engine.step_frames(plan, slab, frames, valid, reset)

    frames = jnp.zeros((3, V, C))
    for valid, reset in (((1, 0, 0), (1, 0, 0)),
                         ((1, 1, 0), (0, 1, 0)),
                         ((0, 0, 0), (0, 0, 0))):
        slab, _ = counted(plan, slab, frames,
                          jnp.asarray(valid, bool), jnp.asarray(reset, bool))
    assert len(traces) == 1


def test_reset_slots_isolates(params, x):
    """reset_slots zeroes exactly the masked slots' per-slot state and
    never touches other slots or the shared frozen BN calibration."""
    plan = engine.build_execution_plan(params, CFG, backend="reference")
    slab = engine.init_session_slab(plan, 2, x_calib=x)
    step = jax.jit(engine.step_frame)
    for r in range(4):
        slab, _ = step(plan, slab, jnp.asarray(x[:2, r]), jnp.asarray(True))
    reset = engine.reset_slots(slab, jnp.asarray([True, False]))
    assert int(reset.t_raw[0]) == 0 and int(reset.t_raw[1]) == 4
    b0 = reset.blocks[0]
    assert not np.asarray(b0["ring_s"][0]).any()
    np.testing.assert_array_equal(np.asarray(b0["ring_s"][1]),
                                  np.asarray(slab.blocks[0]["ring_s"][1]))
    for site in reset.bn_stats:
        np.testing.assert_array_equal(
            np.asarray(reset.bn_stats[site]["mean"]),
            np.asarray(slab.bn_stats[site]["mean"]))


# --------------------------------------------------------------- scheduler

def _mini_sched(slots=2, flush=3, first=2):
    return sess.SlabScheduler(slots, V, C,
                              flush_frames=lambda T: flush,
                              first_logit_delay=first)


def test_scheduler_admission_queueing_and_recycling():
    """More sessions than slots: FIFO queueing, admission only into free
    slots, reset raised exactly on the admission tick, eviction after
    clip + flush, recycled slot admits the queued session."""
    sched = _mini_sched(slots=1, flush=2)
    clip = np.zeros((3, V, C), np.float32)
    sched.submit(sess.SessionRequest(sid=0, arrival=0, clip=clip))
    sched.submit(sess.SessionRequest(sid=1, arrival=0, clip=clip))
    logits = np.zeros((1, 4))
    done_at = {}
    for tick in range(12):
        if sched.idle():
            break
        tp = sched.tick_inputs(tick, 0.0)
        if tick in (0, 5):                  # admissions: tick 0 and recycle
            assert tp.reset[0]
        else:
            assert not tp.reset[0]
        assert tp.valid[0] == (tick in (0, 1, 2, 5, 6, 7))  # clip frames only
        assert not tp.hold.any()            # closed clips never starve
        for rec in sched.tick_outputs(tick, logits, 0.0):
            done_at[rec.sid] = tick
    # total per session = 3 clip + 2 flush = 5 ticks; sid 1 waits 5 ticks
    assert done_at == {0: 4, 1: 9}
    assert [r.sid for r in sched.completed] == [0, 1]
    assert sched.completed[1].admitted == 5
    assert sched.completed[1].arrival == 0


def test_scheduler_counts_valid_frames_and_occupancy():
    sched = _mini_sched(slots=2, flush=1)
    clip = np.zeros((2, V, C), np.float32)
    sched.submit(sess.SessionRequest(sid=0, arrival=0, clip=clip))
    logits = np.zeros((2, 4))
    for tick in range(3):
        sched.tick_inputs(tick, 0.0)
        sched.tick_outputs(tick, logits, 0.0)
    assert sched.valid_frames == 2          # flush ticks don't count
    assert sched.occupancy_samples == [0.5, 0.5, 0.5]


def test_poisson_arrivals_deterministic():
    a = sess.poisson_arrivals(8, 4.0, (10, 20), V, C, seed=7)
    b = sess.poisson_arrivals(8, 4.0, (10, 20), V, C, seed=7)
    assert [r.arrival for r in a] == [r.arrival for r in b]
    assert a[0].arrival == 0                # first arrival anchors the clock
    assert all(x.arrival <= y.arrival for x, y in zip(a, a[1:]))
    assert all(r.clip.shape in ((10, V, C), (20, V, C)) for r in a)
    np.testing.assert_array_equal(a[3].clip, b[3].clip)


# ------------------------------------------------------- QoS / preemption

@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_snapshot_restore_roundtrip(params, prune_plan, backend):
    """The QoS tentpole lock: snapshot a mid-clip slot, evict it, run
    arbitrary foreign traffic in the slot, restore, resume — the final
    logits equal the uninterrupted session's, and a neighbour slot fed the
    identical frame sequence in both runs is bit-for-bit untouched."""
    plan = engine.build_execution_plan(params, CFG, prune_plan, quant=True,
                                       backend=backend)
    bn = engine.collect_bn_stats(
        plan, jax.random.normal(jax.random.PRNGKey(1),
                                (2, CFG.gcn_frames, V, C)))
    rng = np.random.default_rng(11)
    T = 8
    clip_a = jnp.asarray(rng.standard_normal((T, V, C)).astype(np.float32))
    clip_b = jnp.asarray(rng.standard_normal((T, V, C)).astype(np.float32))
    foreign = jnp.asarray(rng.standard_normal((5, V, C)).astype(np.float32))
    total = T + engine.stream_flush_frames(plan, T)
    zeros = jnp.zeros((V, C))
    step = jax.jit(engine.step_frames)
    snap_fn = jax.jit(engine.snapshot_slots)
    rest_fn = jax.jit(engine.restore_slots)

    def frame_of(clip, r):
        return clip[r] if r < T else zeros

    def run(interrupt):
        slab = engine.init_session_slab(plan, 2, bn_stats=bn)
        out = {}
        rel = [0, 0]                          # per-slot session clocks
        snap = None
        while rel[0] < total or rel[1] < total:
            if interrupt and rel[0] == 5 and snap is None:
                snap = snap_fn(slab, jnp.asarray(0))
                for i in range(len(foreign)):  # foreign session in slot 0
                    fr = jnp.stack([foreign[i], frame_of(clip_b, rel[1])])
                    slab, lg = step(plan, slab,
                                    fr, jnp.asarray([True, rel[1] < T]),
                                    jnp.asarray([i == 0, False]))
                    if rel[1] == total - 1:
                        out["b"] = np.asarray(lg)[1]
                    rel[1] = min(rel[1] + 1, total)
                slab = rest_fn(slab, jnp.asarray(0), snap)
            fr = jnp.stack([frame_of(clip_a, rel[0]),
                            frame_of(clip_b, rel[1])])
            slab, lg = step(plan, slab, fr,
                            jnp.asarray([rel[0] < T, rel[1] < T]),
                            jnp.asarray([False, False]))
            if rel[0] == total - 1:
                out["a"] = np.asarray(lg)[0]
            if rel[1] == total - 1:
                out["b"] = np.asarray(lg)[1]
            rel = [min(rel[0] + 1, total), min(rel[1] + 1, total)]
        return out

    want = run(interrupt=False)
    got = run(interrupt=True)
    np.testing.assert_allclose(got["a"], want["a"], atol=1e-3, rtol=1e-3,
                               err_msg=f"preempted slot ({backend})")
    np.testing.assert_array_equal(got["b"], want["b"],
                                  err_msg=f"bystander slot ({backend})")


def test_preempt_policy_matches_independent(params, prune_plan):
    """A high-priority arrival snapshot-evicts the lowest-priority active
    slot; the victim re-queues, restores into a freed slot and resumes —
    every session (victim, preemptor, bystander) still equals its
    independent single-stream run."""
    plan = engine.build_execution_plan(params, CFG, prune_plan, quant=True,
                                       backend="reference")
    rng = np.random.default_rng(7)
    clips = [rng.standard_normal((T, V, C)).astype(np.float32)
             for T in (24, 24, 10)]
    reqs = [
        sess.SessionRequest(sid=0, arrival=0, clip=clips[0], priority=0),
        sess.SessionRequest(sid=1, arrival=2, clip=clips[1], priority=0),
        # both slots busy with priority 0 -> sid 2 preempts the latest
        # admission (sid 1), which later restores and resumes
        sess.SessionRequest(sid=2, arrival=6, clip=clips[2], priority=1),
    ]
    got, bn, sched = _run_scheduled(plan, reqs, slots=2, policy="preempt")
    assert sorted(got) == [0, 1, 2]
    assert sched.preemptions == 1 and sched.restores == 1
    by_sid = {r.sid: r for r in sched.completed}
    assert by_sid[1].preemptions == 1          # the victim
    assert by_sid[0].preemptions == 0 and by_sid[2].preemptions == 0
    for i, clip in enumerate(clips):
        want = _run_independent(plan, bn, clip)
        np.testing.assert_allclose(got[i], want, atol=1e-3, rtol=1e-3,
                                   err_msg=f"session {i}")


def test_preempt_fifo_never_preempts(params, prune_plan):
    """Priorities without the preempt policy are admission order only: the
    fifo policy runs every session to completion."""
    plan = engine.build_execution_plan(params, CFG, prune_plan, quant=True,
                                       backend="reference")
    rng = np.random.default_rng(8)
    clips = [rng.standard_normal((10, V, C)).astype(np.float32)
             for _ in range(2)]
    reqs = [sess.SessionRequest(sid=0, arrival=0, clip=clips[0], priority=0),
            sess.SessionRequest(sid=1, arrival=3, clip=clips[1], priority=5)]
    got, _, sched = _run_scheduled(plan, reqs, slots=1, policy="fifo")
    assert sorted(got) == [0, 1]
    assert sched.preemptions == 0 and sched.restores == 0


def test_admission_queue_strict_priority_arrival_order():
    """The admission queue pops strictly by (priority desc, arrival asc,
    submission order) — with uniform priorities it degenerates to FIFO."""
    q = sess.AdmissionQueue()
    clip = np.zeros((1, V, C), np.float32)
    for sid, prio, arr in [(0, 0, 0), (1, 1, 5), (2, 1, 3), (3, 0, 1),
                           (4, 2, 9), (5, 0, 0)]:
        q.push(sess.SessionRequest(sid=sid, arrival=arr, clip=clip,
                                   priority=prio))
    order = [q.pop().sid for _ in range(len(q))]
    assert order == [4, 2, 1, 0, 5, 3]


def test_deadline_policy_drops_expected():
    """Deadline policy: an expired queued session is dropped without ever
    touching a slot, an active session whose deadline passes mid-service is
    evicted, and on-time sessions complete — exactly those and no others."""
    sched = sess.SlabScheduler(1, V, C, flush_frames=lambda T: 2,
                               first_logit_delay=2, policy="deadline")
    clip = np.zeros((3, V, C), np.float32)          # total = 3 clip + 2 flush
    sched.submit(sess.SessionRequest(sid=0, arrival=0, clip=clip,
                                     deadline=10))
    sched.submit(sess.SessionRequest(sid=1, arrival=0, clip=clip,
                                     deadline=3))   # expires while queued
    logits = np.zeros((1, 4))
    reqs2_submitted = False
    for tick in range(20):
        if tick == 6 and not reqs2_submitted:
            # admitted at 6, needs 5 ticks -> finishes 10 > deadline 8
            sched.submit(sess.SessionRequest(sid=2, arrival=6, clip=clip,
                                             deadline=8))
            reqs2_submitted = True
        if reqs2_submitted and sched.idle():
            break
        sched.tick_inputs(tick, 0.0)
        sched.tick_outputs(tick, logits, 0.0)
    assert [r.sid for r in sched.completed] == [0]
    assert sorted(r.sid for r in sched.missed) == [1, 2]
    assert sched.preemptions == 0


def test_run_sessions_deadline_policy():
    """serve --sessions --qos deadline end-to-end: a tight slack under
    contention misses some sessions, and completed + missed account for
    every generated session."""
    res = sess.run_sessions(CFG, slots=1, n_sessions=4,
                            mean_interarrival=2.0, lengths=(8,),
                            backend="reference", seed=0,
                            qos="deadline", deadline_slack=5)
    assert res["qos"] == "deadline"
    assert res["sessions"] + res["deadline_missed"] == 4
    assert res["deadline_missed"] >= 1          # 1-slot contention must miss
    assert res["deadline_miss_rate"] == pytest.approx(
        res["deadline_missed"] / 4)


# ------------------------------------------------- serving-metrics bugfixes

def test_first_logit_sentinel_survives_and_is_reported():
    """A session whose clip+flush total never reaches the first-logit delay
    keeps the -1.0 sentinel (never a bogus latch), so the driver can count
    it instead of silently shrinking the percentile population."""
    sched = sess.SlabScheduler(1, V, C, flush_frames=lambda T: 0,
                               first_logit_delay=5)
    clip = np.zeros((2, V, C), np.float32)          # total = 2 < delay 5
    sched.submit(sess.SessionRequest(sid=0, arrival=0, clip=clip))
    logits = np.zeros((1, 4))
    for tick in range(4):
        sched.tick_inputs(tick, now=1.0)
        sched.tick_outputs(tick, logits, now=1.0)
    assert sched.idle()
    assert sched.completed[0].wall_first_logit == -1.0


def test_first_logit_latch_on_short_clips():
    """Regression (T=1/T=2 with input_skip=2): the >=-latch records a first
    logit for every session — short clips included — and run_sessions
    reports the no-first-logit count explicitly."""
    sched = sess.SlabScheduler(1, V, C, flush_frames=lambda T: 4 - T,
                               first_logit_delay=3)
    clip = np.zeros((1, V, C), np.float32)          # total = 4 >= delay 3
    sched.submit(sess.SessionRequest(sid=0, arrival=0, clip=clip))
    logits = np.zeros((1, 4))
    for tick in range(6):
        sched.tick_inputs(tick, now=float(tick))
        sched.tick_outputs(tick, logits, now=float(tick))
    assert sched.idle()
    assert sched.completed[0].wall_first_logit == 2.0   # tick rel == delay-1
    res = sess.run_sessions(CFG, slots=2, n_sessions=4,
                            mean_interarrival=2.0, lengths=(1, 2),
                            backend="reference", seed=0)
    assert res["sessions"] == 4
    assert res["sessions_no_first_logit"] == 0
    assert res["first_logit_ms_p50"] > 0
    for rec in res["records"]:
        assert rec.frames in (1, 2)
        assert rec.wall_first_logit >= rec.wall_admitted


def test_occupancy_time_weighted_counts_idle_gaps():
    """Sparse Poisson traffic: the busy-conditional occupancy (processed
    ticks only) must overstate the true time-weighted occupancy, which
    counts the fast-forwarded idle gaps as zero."""
    res = sess.run_sessions(CFG, slots=1, n_sessions=2,
                            mean_interarrival=150.0, lengths=(4,),
                            backend="reference", seed=1)
    assert res["occupancy_busy"] == pytest.approx(1.0)
    assert 0.0 < res["occupancy"] < res["occupancy_busy"]


def test_write_bench_merges_by_backend_slots_qos(tmp_path):
    """serve --sessions --backend pallas must not clobber the reference
    rows: write_bench merges by (backend, slots, qos), replacing matching
    rows in place and appending new keys."""
    path = str(tmp_path / "BENCH_sessions.json")
    ref = {"backend": "reference", "slots": 4, "qos": "fifo",
           "frames_per_s": 500.0, "records": ["dropme"]}
    pal = {"backend": "pallas", "slots": 4, "qos": "fifo",
           "frames_per_s": 80.0}
    sess.write_bench([ref, pal], path)
    rows = json.loads(open(path).read())
    assert [r["backend"] for r in rows] == ["reference", "pallas"]
    assert "records" not in rows[0]
    # pallas-only rewrite: reference row survives, pallas row is replaced,
    # a new qos key is appended
    sess.write_bench([{"backend": "pallas", "slots": 4, "qos": "fifo",
                       "frames_per_s": 99.0},
                      {"backend": "pallas", "slots": 4, "qos": "preempt",
                       "frames_per_s": 70.0}], path)
    rows = json.loads(open(path).read())
    assert len(rows) == 3
    assert rows[0]["backend"] == "reference"
    assert rows[0]["frames_per_s"] == 500.0
    assert rows[1] == {"backend": "pallas", "slots": 4, "qos": "fifo",
                       "frames_per_s": 99.0}
    assert rows[2]["qos"] == "preempt"
    # rows written before the qos axis existed merge as qos=fifo
    legacy = [{"backend": "reference", "slots": 4, "frames_per_s": 1.0}]
    with open(path, "w") as f:
        json.dump(legacy, f)
    sess.write_bench([ref], path)
    rows = json.loads(open(path).read())
    assert len(rows) == 1 and rows[0]["frames_per_s"] == 500.0


def test_run_sessions_end_to_end():
    """The serve --sessions path (two-stream ensemble, Poisson traffic):
    every session completes, metrics are populated, logits finite."""
    res = sess.run_sessions(CFG, slots=2, n_sessions=3,
                            mean_interarrival=4.0, lengths=(8, 12),
                            backend="reference", seed=0)
    assert res["sessions"] == 3
    assert res["frames_per_s"] > 0 and 0 < res["occupancy"] <= 1
    assert res["first_logit_frames"] == 41  # reduced cfg, worked by hand
    for rec in res["records"]:
        assert np.isfinite(rec.logits).all()
        assert rec.frames in (8, 12)
        # occupancy ticks = clip + flush drain (37 for the reduced cfg's
        # K=9 / skip-2 / stride-2 pipeline, same hand-worked number as
        # test_streaming.test_flush_frames_formula)
        assert rec.finished - rec.admitted + 1 == rec.frames + 37


# --------------------------------------------- long-lived-service bugfixes

def test_peek_priority_empty_queue_returns_none():
    """Regression: peeking an empty admission queue (the preempt policy
    probes it every tick) returns None instead of raising IndexError."""
    q = sess.AdmissionQueue()
    assert q.peek_priority() is None
    clip = np.zeros((1, V, C), np.float32)
    q.push(sess.SessionRequest(sid=0, arrival=0, clip=clip, priority=3))
    assert q.peek_priority() == 3
    q.pop()
    assert q.peek_priority() is None


def test_sweep_expired_unit():
    """Regression: sweep_expired drops expired queued sessions *before*
    anyone reads queue depth — stale demand must not linger — is
    idempotent, and is a no-op under non-deadline policies."""
    sched = sess.SlabScheduler(1, V, C, flush_frames=lambda T: 1,
                               first_logit_delay=1, policy="deadline")
    clip = np.zeros((2, V, C), np.float32)
    for sid in range(4):                    # all already expired at tick 5
        sched.submit(sess.SessionRequest(sid=sid, arrival=0, clip=clip,
                                         deadline=2))
    sched.submit(sess.SessionRequest(sid=9, arrival=0, clip=clip,
                                     deadline=50))
    assert len(sched.queue) == 5
    sched.sweep_expired(5)
    assert len(sched.queue) == 1            # only the live one remains
    assert sorted(r.sid for r in sched.missed) == [0, 1, 2, 3]
    sched.sweep_expired(5)                  # idempotent
    assert sched.n_missed == 4
    fifo = sess.SlabScheduler(1, V, C, flush_frames=lambda T: 1,
                              first_logit_delay=1)
    fifo.submit(sess.SessionRequest(sid=0, arrival=0, clip=clip,
                                    deadline=-1))
    fifo.sweep_expired(10)                  # fifo never sheds by deadline
    assert len(fifo.queue) == 1


def test_scheduler_bounded_memory_10k_soak():
    """The long-lived-service lock: 10k sessions (a deadline mix, so both
    the completed and missed paths churn) through a retain=64 scheduler
    leave every host-side record structure bounded by the retention knob,
    while the lifetime aggregates still count all 10k."""
    retain = 64
    sched = sess.SlabScheduler(8, V, C, flush_frames=lambda T: 0,
                               first_logit_delay=1, policy="deadline",
                               retain=retain)
    clip = np.zeros((1, V, C), np.float32)
    logits = np.zeros((8, 4))
    tick, submitted = 0, 0
    while submitted < 10_000 or not sched.idle():
        while submitted < 10_000 and len(sched.queue) < 16:
            # even sids get a hopeless deadline -> the missed path
            dl = tick - 1 if submitted % 2 == 0 else tick + 100
            sched.submit(sess.SessionRequest(sid=submitted, arrival=tick,
                                             clip=clip, deadline=dl))
            submitted += 1
        sched.tick_inputs(tick, 0.0)
        sched.tick_outputs(tick, logits, 0.0)
        tick += 1
        assert tick < 50_000
    assert sched.n_completed + sched.n_missed == 10_000
    assert sched.n_completed >= 5_000       # the live half all complete
    assert len(sched.completed) <= retain
    assert len(sched.missed) <= retain
    assert len(sched.missed_sids) <= retain
    assert len(sched.occupancy_samples) <= retain
    # lifetime aggregates survive the trim: occupancy over *all* ticks
    assert 0 < sched.occ_sum / sched.occ_ticks <= 1
