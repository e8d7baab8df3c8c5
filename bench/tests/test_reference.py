"""The plain reference against the program at a small size on the CPU: the
same graph, cavity taps and parents from the configuration alone, and the
same logits as the program's reference backend on the benchmark's weights
(which is what lets the chip comparison be tight)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import layout, program, reference, traffic

TINY = {"gcn_joints": 25, "gcn_frames": 32, "gcn_persons": 2,
        "gcn_in_channels": 3, "gcn_num_classes": 10,
        "gcn_channels": [8, 8, 16, 16], "gcn_strides": [1, 1, 2, 1],
        "gcn_kv": 3, "gcn_tkernel": 9, "use_ck": False,
        "prune_channel_fracs": [1.0, 0.5, 0.5, 0.5],
        "cavity_pattern": "cav-70-1", "input_skip": 2, "rfc_bank": 16,
        "gcn_stream_pool": 0}


def conf(**kw):
    c = {"name": "tiny", "base": "agcn-2s", "model": dict(TINY),
         "quant": True, "backend": "reference",
         "numerics": {"storage": "float32", "matmul_operands": "float32"}}
    c["model"].update(kw)
    return c


def test_graph_parents_and_cavity_match_the_configuration_semantics():
    from repro.core.agcn.graph import build_ntu_subsets, get_topology
    from repro.core.pruning.cavity import cavity_pattern

    np.testing.assert_allclose(reference.ntu_subsets(), build_ntu_subsets(),
                               atol=1e-7)
    np.testing.assert_array_equal(reference.ntu_parents(),
                                  get_topology("ntu25").parents)
    for name in ("cav-70-1", "cav-75-1", "cav-70-2", "cav-50-1", ""):
        np.testing.assert_array_equal(layout.cavity_mask(name, 9),
                                      cavity_pattern(name, kernel=9))


@pytest.mark.parametrize("kw", [{}, {"prune_channel_fracs": [],
                                     "cavity_pattern": "", "input_skip": 1}])
def test_clip_logits_match_the_program(kw):
    from repro.train.steps import make_gcn_infer_step

    c = conf(**kw)
    model = c["model"]
    cfg = program.model_config(c)
    params2 = reference.make_stream_params(model, 2 ** 31 + 5)
    plans = program.build_plans(cfg, params2, c)
    x = traffic.clip_batch(jax.random.PRNGKey(3), 4, 32, 25, 3)
    got = np.asarray(jax.jit(make_gcn_infer_step(cfg))(plans, x))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.clip_logits(params2, x, model, True))
    assert np.max(np.abs(got - want)) < 1e-4


def test_stream_logits_match_the_program_mid_stream():
    from repro.serving import GcnService

    c = conf()
    model = c["model"]
    cfg = program.model_config(c)
    params2 = reference.make_stream_params(model, 11)
    plans = program.build_plans(cfg, params2, c)
    src = traffic.SessionFrames(11, 6, 25, 3, 30.0)
    xc = src.clips(np.arange(4, 6), 32)
    stats = program.calibrate(plans, jnp.asarray(xc))
    svc = GcnService(cfg, backend="reference", capacity_tiers=(4,),
                     plans=plans, bn_stats=stats, warm=False)
    hs = [svc.open_session() for _ in range(2)]
    seen = [57, 60]           # past the first-logit delay (41 frames)
    fr = src.clips(np.arange(2), 64)
    got = []
    for t in range(max(seen)):
        for i, h in enumerate(hs):
            if t < seen[i]:
                svc.submit(h, fr[i, t])
        svc.tick()
        for i, h in enumerate(hs):
            if t == seen[i] - 1:
                got.append(np.asarray(svc.poll(h, wait=True).logits))
    cal = reference.calibrate(params2, jnp.asarray(xc), model, True)
    d = np.array([layout.emitted(model, n) for n in seen])
    feats = reference.stream_features(params2, cal, jnp.asarray(fr), model,
                                      True)
    want = np.asarray(reference.read_logits(
        params2, feats, jnp.arange(2), jnp.asarray(d)))
    assert d.min() > 0
    assert np.max(np.abs(np.stack(got) - want)) < 1e-4
