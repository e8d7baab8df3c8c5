"""Variable-topology engine tests.

Three layers of the tentpole are locked here:

* **registry** — `GraphTopology` invariants for every registered
  skeleton, plus bit-exact agreement between `ntu25` and the legacy
  hard-coded NTU graph / bone stream;
* **CSR spatial conv** — the gather-accumulate path matches the dense
  einsum path ≤1e-3 on both backends for every registry topology, in
  the dense and pruned+quant plan variants, and the `sconv="auto"`
  selector picks dense on legacy (noise-floor) graphs and CSR on truly
  sparse ones;
* **mixed-skeleton slab** — one `GcnService` holding `ntu25` + `ntu50`
  sessions concurrently reproduces each session's dedicated
  single-topology run, and a preemption leaves bystander sessions
  bit-identical.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.agcn import engine
from repro.core.agcn import model as M
from repro.core.agcn.graph import (get_topology, static_graph,
                                   topology_names)
from repro.core.pruning.plan import build_prune_plan
from repro.kernels import ops
from repro.serving import GcnService
from repro.serving.slo import SloConfig, SloController

CFG = get_config("agcn-2s", reduced=True)
TOPOLOGIES = ("ntu25", "ntu50", "hand21", "body_hand46")


def _cfg_for(topo):
    return dataclasses.replace(CFG, gcn_joints=topo.num_joints)


# ---------------------------------------------------------------- registry

def test_registry_names():
    assert set(TOPOLOGIES) <= set(topology_names())
    with pytest.raises(KeyError, match="unknown topology"):
        get_topology("ntu26")


@pytest.mark.parametrize("name", TOPOLOGIES)
def test_topology_invariants(name):
    """Shapes, normalization reach and self-consistent CSR factorization
    for every registry skeleton."""
    tp = get_topology(name)
    V, K = tp.num_joints, tp.num_subsets
    assert tp.adjacency.shape == (K, V, V)
    assert tp.parents.shape == (V,)
    assert tp.valid.all() and tp.valid.shape == (V,)
    assert 0.0 < tp.density < 0.5          # skeletons are genuinely sparse
    # the summed subsets reach every joint (no orphaned row)
    assert (np.abs(tp.adjacency).sum(axis=(0, 1)) > 0).all()
    # CSR roundtrips to the dense stack exactly
    from repro.core.agcn.graph import csr_to_dense
    np.testing.assert_array_equal(
        csr_to_dense(tp.indptr, tp.indices, tp.values), tp.adjacency)


def test_ntu25_matches_legacy_graph_and_bone_stream():
    """The registry's ntu25 IS the legacy skeleton: same adjacency bytes
    as static_graph(), and the parent-map bone stream reproduces the
    hard-coded bone_stream bitwise."""
    tp = get_topology("ntu25")
    np.testing.assert_array_equal(tp.adjacency, np.asarray(static_graph()))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 25, 3))
    np.testing.assert_array_equal(
        np.asarray(M.bone_stream(x)),
        np.asarray(M.bone_stream_parents(x, tp.parents)))


def test_ntu50_is_two_person_block_diagonal_with_one_link():
    """The two-person graph: each 25×25 person block equals the
    single-person graph, and exactly one inter-person bond ties the
    spines."""
    tp25, tp50 = get_topology("ntu25"), get_topology("ntu50")
    a = tp50.adjacency
    off = np.abs(a[:, :25, 25:]).sum(axis=0) + np.abs(a[:, 25:, :25]).sum(axis=0)
    # the spine link makes the coupled rows' normalization differ from the
    # single-person graph only where the bond lands
    assert (off > 0).sum() >= 1
    assert tp50.num_joints == 50
    # person 2's parent chain mirrors person 1's, shifted by 25
    assert (tp50.parents[25:][tp25.parents != np.arange(25)]
            == tp25.parents[tp25.parents != np.arange(25)] + 25).all()


# ------------------------------------------------------- CSR ↔ dense parity

# Full matrix: topology × backend × {dense, pruned+quant}.  Reference
# cells are cheap; pallas-interpret cells beyond the canonical ntu25
# dense cell ride the slow tier.
_FAST = {("ntu25", "reference", False), ("ntu25", "reference", True),
         ("ntu50", "reference", False), ("hand21", "reference", False),
         ("body_hand46", "reference", False), ("ntu25", "pallas", False)}
MATRIX = [
    pytest.param(name, backend, quant,
                 id=f"{name}-{backend}-{'quant' if quant else 'dense'}",
                 marks=() if (name, backend, quant) in _FAST
                 else pytest.mark.slow)
    for name in TOPOLOGIES
    for backend in ("reference", "pallas")
    for quant in (False, True)
]


def _build_pair(name, backend, quant, csr_eps=0.0):
    """Dense-path and (forced) CSR-path plans from identical params."""
    tp = get_topology(name)
    cfg = _cfg_for(tp)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prune = None
    if quant:
        sw = [np.asarray(b["Wk"]) for b in params["blocks"]]
        prune = build_prune_plan(sw, cfg.gcn_channels,
                                 [1.0] + [0.5] * (len(cfg.gcn_channels) - 1),
                                 "cav-70-1", input_skip=2)
    dense = engine.build_execution_plan(
        params, cfg, prune, quant=quant, backend=backend,
        topology=tp, sconv="dense")
    csr = engine.build_execution_plan(
        params, cfg, prune, quant=quant, backend=backend,
        topology=tp, sconv="csr", csr_eps=csr_eps)
    return tp, cfg, dense, csr


@pytest.mark.parametrize("name,backend,quant", MATRIX)
def test_csr_matches_dense(name, backend, quant):
    tp, cfg, dense, csr = _build_pair(name, backend, quant)
    assert all(b.sconv == "dense" for b in dense.static.blocks)
    assert any(b.sconv == "csr" for b in csr.static.blocks)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (2, cfg.gcn_frames, tp.num_joints, 3))
    np.testing.assert_allclose(
        np.asarray(engine.execute(dense, x)),
        np.asarray(engine.execute(csr, x)), atol=1e-3, rtol=1e-3)


def test_csr_with_true_sparsity_threshold():
    """csr_eps above the dense-B_k noise floor drops the 1e-6 init noise:
    the CSR plan runs the genuinely sparse skeleton graph and still
    matches the dense path ≤1e-3."""
    tp, cfg, dense, csr = _build_pair("ntu25", "reference", False,
                                      csr_eps=1e-5)
    E_full = tp.num_joints * tp.num_joints
    ba = csr.arrays["blocks"][0]
    assert ba["csr_indices"].shape[-1] < E_full    # actually pruned
    x = jax.random.normal(jax.random.PRNGKey(2),
                          (2, cfg.gcn_frames, tp.num_joints, 3))
    np.testing.assert_allclose(
        np.asarray(engine.execute(dense, x)),
        np.asarray(engine.execute(csr, x)), atol=1e-3, rtol=1e-3)


def test_auto_selector_density_crossover():
    """sconv="auto": the learned B_k is dense at init (1e-6 everywhere),
    so the legacy zero-eps build keeps every block on the dense path —
    existing plans change nothing — while a real sparsity threshold
    flips the (sparse-skeleton) blocks to CSR."""
    tp = get_topology("ntu25")
    cfg = _cfg_for(tp)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    legacy = engine.build_execution_plan(params, cfg, backend="reference")
    assert all(b.sconv == "dense" for b in legacy.static.blocks)
    sparse = engine.build_execution_plan(params, cfg, backend="reference",
                                         topology=tp, csr_eps=1e-5)
    assert all(b.sconv == "csr" for b in sparse.static.blocks
               if not b.use_ck)


def test_graph_sconv_subset_mismatch_error_names_topology():
    """The satellite bugfix: a K-axis mismatch between graph and weights
    raises a topology-named ValueError instead of an opaque shape error
    deep inside the kernel."""
    x = np.zeros((1, 2, 25, 4), np.float32)
    g = np.zeros((2, 25, 25), np.float32)        # K=2
    w = np.zeros((3, 4, 4), np.float32)          # K=3
    with pytest.raises(ValueError, match="subsets.*'ntu25'"):
        ops.graph_sconv(x, g, w, interpret=ops.interpret_mode(),
                        topology="ntu25")
    idx = np.zeros((2, 32, 1), np.int32)
    val = np.zeros((2, 32, 1), np.float32)
    with pytest.raises(ValueError, match="subsets.*'ntu50'"):
        ops.graph_sconv_csr(x, idx, val, w, interpret=ops.interpret_mode(),
                            topology="ntu50")


# ------------------------------------------------------- pad-to-Vmax plans

def test_padded_plan_streams_bit_exact_on_reference():
    """A plan padded to a wider slab (pad_joints=Vmax) with joint-validity
    masking reproduces the narrow dedicated plan bit-for-bit on the
    streaming path (frozen BN stats — what the slab actually runs)."""
    tp = get_topology("ntu25")
    cfg = _cfg_for(tp)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    narrow = engine.build_execution_plan(params, cfg, backend="reference",
                                         topology=tp)
    padded = engine.build_execution_plan(params, cfg, backend="reference",
                                         topology=tp, pad_joints=50)
    assert padded.static.joints == 50
    assert padded.static.valid_joints == 25
    xc = jax.random.normal(jax.random.PRNGKey(3),
                           (2, cfg.gcn_frames, 25, 3))
    bn = engine.collect_bn_stats(narrow, xc)
    st_n = engine.init_stream_state(narrow, 1, bn_stats=bn)
    st_p = engine.init_stream_state(padded, 1, bn_stats=bn)
    clip = np.asarray(jax.random.normal(jax.random.PRNGKey(4),
                                        (cfg.gcn_frames, 25, 3)))
    T = cfg.gcn_frames
    for t in range(T + 45):                      # clip + flush drain
        valid = t < T
        f_n = clip[t][None] if valid else np.zeros((1, 25, 3), np.float32)
        f_p = np.zeros((1, 50, 3), np.float32)
        f_p[:, :25] = f_n
        st_n, log_n = engine.step_frame(narrow, st_n, f_n, valid=valid)
        st_p, log_p = engine.step_frame(padded, st_p, f_p, valid=valid)
        # XLA's CPU matmul is not bit-identical across padded operand
        # shapes (the padded plan differs by 1 ulp), so this compare is
        # ulp-tight rather than exact
        np.testing.assert_allclose(np.asarray(log_n), np.asarray(log_p),
                                   rtol=1e-6, atol=1e-6)


# ------------------------------------------------------- SLO in-flight unit

def test_slo_inflight_age_breaches_and_blocks_recovery():
    """The admitted-but-unlatched blind spot: a session committed past
    the target must read as a breach even though it is in neither the
    queue nor the latency window."""
    c = SloController(SloConfig(target_p99_ticks=50, window=8,
                                breach_patience=2, recover_patience=4,
                                shed_mode="reject"), tiers=(2,))
    assert not c.breached()
    assert not c.breached(inflight_age=50)       # at the bound is healthy
    assert c.breached(inflight_age=51)
    # a persistent in-flight breach at the (single-tier) top sheds
    for t in range(2):
        c.observe(2, 0, t, inflight_age=60)
    assert c.shedding
    # healthy latched samples alone cannot un-shed while an in-flight
    # session is still committed to breaching
    for _ in range(8):
        c.record_first_logit(1, 10)
    for t in range(2, 6):
        c.observe(2, 0, t, inflight_age=60)
    assert c.shedding
    # once the in-flight signal clears, the recovery streak un-sheds
    for t in range(6, 10):
        c.observe(2, 0, t, inflight_age=0)
    assert not c.shedding


# ----------------------------------------------------- mixed-skeleton slab

def _final_logits(svc, h):
    st = svc.poll(h)
    assert st.state == "done"
    return np.asarray(st.record.logits)


@pytest.fixture(scope="module")
def mixed_runs():
    """One mixed ntu25+ntu50 service under preemption, the same schedule
    without the preemptor, and dedicated single-topology baselines."""
    rng = np.random.default_rng(5)
    clip25 = rng.standard_normal((10, 25, 3)).astype(np.float32)
    clip50 = rng.standard_normal((12, 50, 3)).astype(np.float32)
    clip25b = rng.standard_normal((8, 25, 3)).astype(np.float32)

    def build_mixed():
        return GcnService(CFG, backend="reference", qos="preempt",
                          capacity_tiers=(2,),
                          topologies=("ntu25", "ntu50"), seed=0)

    # run A: X(ntu25, pri 0) + Y(ntu50, pri 1) fill both slots; Z(ntu25,
    # pri 2) arrives mid-flight and preempts X
    svc = build_mixed()
    x_h = svc.open_session(priority=0, topology="ntu25")
    svc.submit_clip(x_h, clip25)
    y_h = svc.open_session(priority=1, topology="ntu50")
    svc.submit_clip(y_h, clip50)
    for _ in range(5):
        svc.tick()
    z_h = svc.open_session(priority=2, topology="ntu25")
    svc.submit_clip(z_h, clip25b)
    while not svc.idle():
        svc.tick()
    run_a = {"svc": svc,
             "X": _final_logits(svc, x_h), "Y": _final_logits(svc, y_h),
             "Z": _final_logits(svc, z_h)}

    # run B: identical schedule minus the preemptor
    svc_b = build_mixed()
    x2 = svc_b.open_session(priority=0, topology="ntu25")
    svc_b.submit_clip(x2, clip25)
    y2 = svc_b.open_session(priority=1, topology="ntu50")
    svc_b.submit_clip(y2, clip50)
    while not svc_b.idle():
        svc_b.tick()
    run_b = {"X": _final_logits(svc_b, x2), "Y": _final_logits(svc_b, y2)}

    # dedicated single-topology baselines (fifo, one session at a time —
    # per-slot clocks make staggered/mixed serving equivalent to these)
    ded = {}
    svc25 = GcnService(CFG, backend="reference", qos="fifo",
                       capacity_tiers=(2,), topologies=("ntu25",), seed=0)
    for key, clip in (("X", clip25), ("Z", clip25b)):
        h = svc25.open_session()
        svc25.submit_clip(h, clip)
        while not svc25.idle():
            svc25.tick()
        ded[key] = _final_logits(svc25, h)
    svc50 = GcnService(CFG, backend="reference", qos="fifo",
                       capacity_tiers=(2,), topologies=("ntu50",), seed=0)
    h = svc50.open_session()
    svc50.submit_clip(h, clip50)
    while not svc50.idle():
        svc50.tick()
    ded["Y"] = _final_logits(svc50, h)
    return run_a, run_b, ded


def test_mixed_slab_matches_dedicated_runs(mixed_runs):
    """Acceptance: every session served from the mixed ntu25+ntu50 slab —
    including across a preemption — matches its dedicated
    single-topology service ≤1e-3."""
    run_a, _, ded = mixed_runs
    assert run_a["svc"].metrics()["preemptions"] >= 1
    for key in ("X", "Y", "Z"):
        np.testing.assert_allclose(run_a[key], ded[key],
                                   atol=1e-3, rtol=1e-3)


def test_mixed_slab_bystander_bit_identical_across_preemption(mixed_runs):
    """The non-preempted ntu50 session's logits are bit-identical whether
    or not a preemption churned the neighbouring slot."""
    run_a, run_b, _ = mixed_runs
    np.testing.assert_array_equal(run_a["Y"], run_b["Y"])


def test_open_session_rejects_unknown_topology():
    svc = GcnService(CFG, backend="reference", capacity_tiers=(2,),
                     topologies=("ntu25",), seed=0)
    with pytest.raises(ValueError, match="unknown topology"):
        svc.open_session(topology="ntu50")


def test_submit_validates_frame_shape_per_topology():
    svc = GcnService(CFG, backend="reference", capacity_tiers=(2,),
                     topologies=("ntu25", "hand21"), seed=0)
    h = svc.open_session(topology="hand21")
    with pytest.raises(ValueError, match="hand21"):
        svc.submit(h, np.zeros((25, 3), np.float32))
    svc.submit(h, np.zeros((21, 3), np.float32))


def test_metrics_carry_topology_axes():
    svc = GcnService(CFG, backend="reference", capacity_tiers=(2,),
                     topologies=("ntu25", "ntu50"), seed=0)
    m = svc.metrics()
    assert m["topologies"] == "ntu25,ntu50"
    assert m["joints"] == 50
