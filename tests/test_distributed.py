"""Distributed serving tier: mesh-sharded slab ticks + the replica router.

Run with ``./test.sh --dist`` (exports
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so the 1-D batch
mesh is real on CPU).  The tentpole locks:

* **Sharded == single-device** — the same QoS trace (admissions,
  preemptions with restores, an elastic grow/shrink migration) produces
  logits within 1e-3 of the single-device run when the slab, snapshot
  ring and tick are sharded over a 4-device mesh, on both backends.
* **Cross-replica migration parity** — a session drained out of one
  replica (active slot or preempted ring snapshot) and resumed on
  another matches its uninterrupted run ≤1e-3, and bystander sessions on
  both replicas are *bit-identical*.
* **Router mechanics** — consistent sid→replica pinning through
  migrations, load feedback placement, drain-and-rebalance moves, and
  the routed BENCH row (``replicas``/``rebalances`` axes).

The mesh-gated cells skip on a single-device run (the plain full tier);
the router cells run everywhere — replicas don't need extra devices.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.agcn import engine
from repro.core.agcn import model as M
from repro.core.pruning.plan import build_prune_plan
from repro.distributed.router import ReplicaRouter, run_routed_sessions
from repro.distributed.serving import collective_cost_ms, make_batch_mesh
from repro.serving import CapacityConfig, GcnService, SessionRequest

CFG = get_config("agcn-2s", reduced=True)
V, C = CFG.gcn_joints, CFG.gcn_in_channels

needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4 "
           "(the ./test.sh --dist tier)")


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


def _qos_trace(rng):
    """Fill a 4-slot tier with low-priority clips, then land high-priority
    arrivals at tick 1 — they preempt *before* the elastic grow triggers,
    and the preempted pair becomes the backlog that grows the tier."""
    spec = [(0, 0, 12), (0, 0, 12), (0, 0, 12), (0, 0, 12),
            (1, 1, 6), (1, 1, 6)]
    return [SessionRequest(
        sid=i, arrival=a, priority=p,
        clip=rng.standard_normal((T, V, C)).astype(np.float32))
        for i, (a, p, T) in enumerate(spec)]


def _single(plan, bn, clip):
    """Uninterrupted single-session baseline on a fresh 1-slot service."""
    svc = GcnService(CFG, plans=(plan,), bn_stats=(bn,), capacity_tiers=(1,))
    h = svc.open_session()
    svc.submit_clip(h, clip)
    svc.run_until_idle()
    return svc.poll(h).logits


# ------------------------------------------------------------- mesh tier

def test_make_batch_mesh_overask_raises():
    """Asking for more devices than visible is a loud error naming the
    fake-device flag, not a short mesh."""
    with pytest.raises(RuntimeError, match="device_count"):
        make_batch_mesh(jax.device_count() + 1)


@needs4
def test_mesh_divisibility_validation(params, prune_plan):
    """Every capacity tier must divide the mesh size — uneven slot shards
    are rejected at construction, naming the tier."""
    plan, bn = _plan_and_bn(params, prune_plan, "reference")
    mesh = make_batch_mesh(4)
    with pytest.raises(ValueError, match="divide"):
        GcnService(CFG, plans=(plan,), bn_stats=(bn,),
                   capacity_tiers=(4, 6), mesh=mesh, warm=False)


@needs4
def test_sharded_parity_reference(params, prune_plan):
    """The tentpole lock (reference backend): a QoS trace with
    preemptions, restores and an elastic grow runs bit-for-bit through
    the mesh-sharded slab — same churn counts, session logits within
    1e-3 of the single-device run."""
    plan, bn = _plan_and_bn(params, prune_plan, "reference")
    # grow_patience=3 so the tick-1 high-priority arrivals preempt while
    # the tier is still full; the preempted backlog then drives the grow
    ccfg = CapacityConfig(tiers=(4, 8), grow_patience=3, shrink_patience=2,
                          cooldown=3)
    runs = {}
    for mesh in (make_batch_mesh(4), None):
        svc = GcnService(CFG, plans=(plan,), bn_stats=(bn,), qos="preempt",
                         capacity_tiers=(4, 8), capacity_config=ccfg,
                         mesh=mesh)
        runs[mesh is not None] = _drive_requests(
            svc, _qos_trace(np.random.default_rng(7)))
    osh, msh = runs[True]
    o1, m1 = runs[False]
    assert msh["mesh"] == 4 and m1["mesh"] == 1
    assert msh["preemptions"] > 0 and msh["migrations"] > 0
    assert msh["preemptions"] == m1["preemptions"]
    assert msh["migrations"] == m1["migrations"]
    assert set(osh) == set(o1)
    for sid in sorted(osh):
        np.testing.assert_allclose(osh[sid], o1[sid], atol=1e-3, rtol=1e-3,
                                   err_msg=f"session {sid}")


@needs4
@pytest.mark.slow
def test_sharded_parity_pallas(params, prune_plan):
    """The same lock on the pallas backend (interpret mode on CPU): a
    fixed 4-slot sharded tier with a preemption round-trip matches the
    single-device run ≤1e-3."""
    plan, bn = _plan_and_bn(params, prune_plan, "pallas")
    spec = [(0, 0, 8), (0, 0, 8), (0, 0, 8), (0, 0, 8), (1, 1, 4)]
    rng = np.random.default_rng(11)
    reqs = [SessionRequest(
        sid=i, arrival=a, priority=p,
        clip=rng.standard_normal((T, V, C)).astype(np.float32))
        for i, (a, p, T) in enumerate(spec)]
    runs = {}
    for mesh in (make_batch_mesh(4), None):
        svc = GcnService(CFG, backend="pallas", plans=(plan,),
                         bn_stats=(bn,), qos="preempt", capacity_tiers=(4,),
                         mesh=mesh)
        runs[mesh is not None] = _drive_requests(svc, reqs)
    osh, msh = runs[True]
    o1, m1 = runs[False]
    assert msh["preemptions"] == m1["preemptions"] > 0
    for sid in sorted(osh):
        np.testing.assert_allclose(osh[sid], o1[sid], atol=1e-3, rtol=1e-3,
                                   err_msg=f"session {sid}")


@needs4
def test_collective_cost_measurable(params, prune_plan):
    """The per-tick collective overhead of the sharded step is a finite
    non-negative number — the ``collective_ms_per_tick`` BENCH axis."""
    plan, bn = _plan_and_bn(params, prune_plan, "reference")
    svc = GcnService(CFG, plans=(plan,), bn_stats=(bn,), capacity_tiers=(4,),
                     mesh=make_batch_mesh(4))
    ms = collective_cost_ms(svc, iters=4)
    assert np.isfinite(ms) and ms >= 0.0


# ------------------------------------------------------------ router tier

def _two_replicas(plan, bn, **kw):
    mk = lambda: GcnService(CFG, plans=(plan,), bn_stats=(bn,), **kw)
    return ReplicaRouter([mk(), mk()])


def test_cross_replica_active_migration_parity(params, prune_plan):
    """The creative-leap lock: a session drained mid-clip out of replica
    0's *slot* and resumed on replica 1 matches its uninterrupted run
    ≤1e-3; the bystander sharing replica 0 is bit-identical to a run
    where no migration happened."""
    plan, bn = _plan_and_bn(params, prune_plan, "reference")
    rng = np.random.default_rng(3)
    clip_a = rng.standard_normal((14, V, C)).astype(np.float32)
    clip_b = rng.standard_normal((10, V, C)).astype(np.float32)
    base = _single(plan, bn, clip_a)

    def run(migrate):
        router = _two_replicas(plan, bn, capacity_tiers=(2,))
        ha = router.open_session(replica=0)
        router.submit_clip(ha, clip_a)
        hb = router.open_session(replica=0)
        router.submit_clip(hb, clip_b)
        for _ in range(5):
            router.tick()
        if migrate:
            assert router.replica_of(ha) == 0
            router.migrate_session(ha, 1)
            assert router.replica_of(ha) == 1      # the pin moved
            assert router.rebalances == 1
        router.run_until_idle()
        return router.poll(ha).logits, router.poll(hb).logits

    logits_a, bystander = run(migrate=True)
    _, bystander_base = run(migrate=False)
    np.testing.assert_allclose(logits_a, base, atol=1e-3, rtol=1e-3)
    np.testing.assert_array_equal(bystander, bystander_base)


def test_cross_replica_preempted_export_parity(params, prune_plan):
    """A *preempted* session (device state parked in the snapshot ring)
    exports through the ring row and resumes on the other replica with
    uninterrupted-run parity — the ring adopt/release allocator path."""
    plan, bn = _plan_and_bn(params, prune_plan, "reference")
    rng = np.random.default_rng(5)
    clip_lo = rng.standard_normal((16, V, C)).astype(np.float32)
    clip_hi = rng.standard_normal((12, V, C)).astype(np.float32)
    base = _single(plan, bn, clip_lo)

    router = _two_replicas(plan, bn, capacity_tiers=(1,), qos="preempt")
    h_lo = router.open_session(replica=0, priority=0)
    router.submit_clip(h_lo, clip_lo)
    for _ in range(4):
        router.tick()
    h_hi = router.open_session(replica=0, priority=1)
    router.submit_clip(h_hi, clip_hi)
    router.tick()                       # preempts h_lo into the ring
    assert router.poll(h_lo).state == "queued"
    src = router.services[0]
    assert src.sched.preemptions == 1
    router.migrate_session(h_lo, 1)     # ring row -> host -> replica 1
    router.run_until_idle()
    np.testing.assert_allclose(router.poll(h_lo).logits, base,
                               atol=1e-3, rtol=1e-3)
    assert router.poll(h_hi).state == "done"
    # the exported session's ring row was returned to replica 0's free list
    assert len(src.sched._ring_free) == src.snap_capacity


def test_router_pinning_and_feedback(params, prune_plan):
    """Placement follows the load feedback (least busy+queued replica,
    index tie-break); handles stay pinned; queue-depth shows up in the
    feedback rows."""
    plan, bn = _plan_and_bn(params, prune_plan, "reference")
    router = _two_replicas(plan, bn, capacity_tiers=(2,))
    rng = np.random.default_rng(2)
    clips = [rng.standard_normal((6, V, C)).astype(np.float32)
             for _ in range(4)]
    hs = [router.open_session() for _ in range(4)]
    for h, c in zip(hs, clips):
        router.submit_clip(h, c)
    # round-robin by load: 0, 1, 0, 1
    assert [router.replica_of(h) for h in hs] == [0, 1, 0, 1]
    fb = router.feedback()
    assert [f["replica"] for f in fb] == [0, 1]
    assert all(f["busy"] + f["queued"] == 2 for f in fb)
    router.run_until_idle()
    assert all(router.poll(h).state == "done" for h in hs)
    with pytest.raises(KeyError):
        router.poll(type(hs[0])(rsid=999))


def test_router_rebalance_drains_hot_replica(params, prune_plan):
    """Sessions force-pinned onto one replica rebalance onto the idle one
    (queued sessions move first), and the move count lands in the merged
    metrics row."""
    plan, bn = _plan_and_bn(params, prune_plan, "reference")
    router = _two_replicas(plan, bn, capacity_tiers=(2,))
    rng = np.random.default_rng(4)
    hs = []
    for _ in range(4):
        h = router.open_session(replica=0)      # manual hot-spotting
        router.submit_clip(h, rng.standard_normal((8, V, C))
                           .astype(np.float32))
        hs.append(h)
    router.tick()
    assert router.feedback()[0]["queued"] == 2
    moved = router.rebalance(threshold=2)
    assert moved == 2
    assert sorted(router.replica_of(h) for h in hs) == [0, 0, 1, 1]
    router.run_until_idle()
    m = router.metrics()
    assert m["rebalances"] == 2 and m["replicas"] == 2
    assert m["sessions"] == 4


def test_run_routed_sessions_row(params, prune_plan):
    """The routed batch driver serves every session and emits the merged
    BENCH row with the distributed axes and the table-rendering fields."""
    m = run_routed_sessions(CFG, replicas=2, slots=2, n_sessions=8,
                            mean_interarrival=2.0, lengths=(6,), seed=0,
                            qos="fifo", rebalance_every=4, max_ticks=4000)
    assert m["sessions"] == 8 and m["replicas"] == 2
    assert m["rebalances"] >= 0 and len(m["per_replica"]) == 2
    for k in ("slots", "frames_per_s", "occupancy",
              "latency_ms_p50", "latency_ms_p99", "load"):
        assert k in m, k
    assert m["frames_per_s"] > 0


@needs4
def test_router_replicas_pinned_one_per_device():
    """``ReplicaRouter.build`` places replica i on device i: its plans, BN
    stats, packed tick constants, slab and snapshot-ring leaves live
    there, and stay there through ticks — four replicas never share one
    device."""
    router = ReplicaRouter.build(CFG, replicas=4, capacity_tiers=(2,))
    devs = jax.devices()[:4]
    rng = np.random.default_rng(9)
    for i in range(4):
        h = router.open_session(replica=i)
        router.submit_clip(h, rng.standard_normal((6, V, C))
                           .astype(np.float32))
    for _ in range(3):
        router.tick()
    for i, svc in enumerate(router.services):
        trees = (svc.plans, svc.bn_stats, svc._consts, svc.slabs,
                 svc._rings)
        leaves = jax.tree_util.tree_leaves(trees)
        assert leaves
        assert all(leaf.devices() == {devs[i]} for leaf in leaves), i
    router.run_until_idle()
