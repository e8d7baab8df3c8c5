"""2s-AGCN model tests: shapes, pruning consistency, quantization, C_k,
input-skip, bone stream, feature sparsity probe."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import ModelConfig
from repro.configs import get_config
from repro.core.agcn import model as M
from repro.core.agcn.graph import build_ntu_subsets, graph_sparsity
from repro.core.pruning.plan import build_prune_plan

CFG = get_config("agcn-2s", reduced=True)


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def x():
    return jax.random.normal(jax.random.PRNGKey(1), (4, CFG.gcn_frames, 25, 3))


def test_static_graph_properties():
    A = build_ntu_subsets()
    assert A.shape == (3, 25, 25)
    # column-normalized D^-1·A: each column of the merged graph sums to 1
    merged = A.sum(0)
    np.testing.assert_allclose(merged.sum(0), np.ones(25), atol=1e-5)
    assert graph_sparsity(A) > 0.8                 # A_k sparse (paper §I)


def test_forward_shapes(params, x):
    logits = M.forward(params, x, CFG)
    assert logits.shape == (4, CFG.gcn_num_classes)
    assert not bool(jnp.isnan(logits).any())


def test_full_keep_plan_matches_dense(params, x):
    """keep_frac=1 + no cavity = numerically identical to dense forward."""
    sw = [np.asarray(b["Wk"]) for b in params["blocks"]]
    plan = build_prune_plan(sw, CFG.gcn_channels, [1.0] * 4, "none",
                            input_skip=1)
    dense = M.forward(params, x, dataclasses.replace(CFG, input_skip=1))
    pruned = M.forward(params, x, dataclasses.replace(CFG, input_skip=1),
                       plan=plan)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(pruned),
                               atol=1e-4, rtol=1e-4)


def test_pruned_plan_reduces_and_runs(params, x):
    sw = [np.asarray(b["Wk"]) for b in params["blocks"]]
    plan = build_prune_plan(sw, CFG.gcn_channels, [1.0, 0.5, 0.5, 0.5],
                            "cav-70-1", input_skip=2)
    logits = M.forward(params, x, CFG, plan=plan)
    assert logits.shape == (4, CFG.gcn_num_classes)
    assert not bool(jnp.isnan(logits).any())
    s = plan.summary(CFG.gcn_channels, 3)
    assert s["compression_ratio"] > 2.0
    assert s["graph_skip_efficiency"] > 0.3


def test_quantization_small_error(params, x):
    a = M.forward(params, x, CFG)
    b = M.forward(params, x, CFG, quant=True)
    rel = float(jnp.abs(a - b).mean() / (jnp.abs(a).mean() + 1e-9))
    assert rel < 0.1                              # Q8.8: negligible loss


def test_ck_path(x):
    cfg = dataclasses.replace(CFG, use_ck=True)
    p = M.init_params(cfg, jax.random.PRNGKey(0))
    logits = M.forward(p, x, cfg)
    assert not bool(jnp.isnan(logits).any())


def test_input_skip_halves_frames(params, x):
    cfg2 = dataclasses.replace(CFG, input_skip=2)
    # runs and differs from non-skipped
    a = M.forward(params, x, dataclasses.replace(CFG, input_skip=1))
    b = M.forward(params, x, cfg2)
    assert not np.allclose(np.asarray(a), np.asarray(b))


def test_bone_stream_and_ensemble(params, x):
    pb = M.init_params(CFG, jax.random.PRNGKey(7))
    bones = M.bone_stream(x)
    assert bones.shape == x.shape
    ens = M.two_stream_logits(params, pb, x, CFG)
    assert ens.shape == (4, CFG.gcn_num_classes)


def test_feature_sparsity_probe(params, x):
    s = M.feature_sparsity_per_block(params, x, CFG)
    assert len(s) == len(CFG.gcn_channels)
    assert all(0.0 <= v <= 1.0 for v in s)
    assert any(v > 0.1 for v in s)                # ReLU produces real zeros


def _unit_gcn_numpy(x, A, PA, Wa, ba, Wb, bb, Wd, flip_ck=False):
    """The published ``unit_gcn`` graph sum, before its batch norm, in
    numpy and in its own (N, C, T, V) layout (lshiwjx/2s-AGCN,
    model/agcn.py): ``A``/``PA`` (K, V, V) weight joint v into joint w at
    [k, v, w]; ``Wa``/``Wb`` (K, Ce, C) and ``ba``/``bb`` (K, Ce) are
    conv_a/conv_b; ``Wd`` (K, Cout, C) is conv_d without its bias.
    ``flip_ck`` adds C_k transposed: the fault the comparison must see."""
    N, C, T, V = x.shape
    y = 0.0
    for i in range(A.shape[0]):
        a1 = np.einsum("ec,nctv->netv", Wa[i], x) + ba[i][:, None, None]
        ce = a1.shape[1]
        a1 = a1.transpose(0, 3, 1, 2).reshape(N, V, ce * T)
        a2 = (np.einsum("ec,nctv->netv", Wb[i], x)
              + bb[i][:, None, None]).reshape(N, ce * T, V)
        s = a1 @ a2 / a1.shape[-1]
        s = np.exp(s - s.max(axis=-2, keepdims=True))
        s = s / s.sum(axis=-2, keepdims=True)           # Softmax(-2)
        g = (np.swapaxes(s, 1, 2) if flip_ck else s) + A[i] + PA[i]
        z = (x.reshape(N, C * T, V) @ g).reshape(N, C, T, V)
        y = y + np.einsum("oc,nctv->notv", Wd[i], z)
    return y


@pytest.fixture(scope="module")
def published_block():
    """A one-block ck_form='clip' model with biases and θ/φ at a scale
    that makes C_k far from uniform, and a (N, T, V, C) input."""
    cfg = dataclasses.replace(CFG, gcn_channels=(8,), gcn_strides=(1,),
                              gcn_in_channels=6, use_ck=True,
                              ck_form="clip")
    p = M.init_params(cfg, jax.random.PRNGKey(3))
    blk = p["blocks"][0]
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    blk["theta"] = 3.0 * blk["theta"]
    blk["phi"] = 3.0 * blk["phi"]
    blk["theta_b"] = jax.random.normal(ks[0], blk["theta_b"].shape)
    blk["phi_b"] = jax.random.normal(ks[1], blk["phi_b"].shape)
    blk["Bk"] = 0.1 * jax.random.normal(ks[2], blk["Bk"].shape)
    x = jax.random.normal(ks[3], (2, 16, 25, 6))
    return cfg, p, x


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_published_ck_matches_unit_gcn(published_block, backend):
    """The clip-form C_k block's graph sum equals the published unit_gcn
    rendered independently; C_k entering the graph untransposed (or
    softmaxed over the output joint) would not match."""
    from repro.core.agcn import engine
    from repro.core.agcn.graph import get_topology

    cfg, p, x = published_block
    plan = engine.build_execution_plan(p, cfg, None, backend=backend)
    ba, bs = plan.arrays["blocks"][0], plan.static.blocks[0]
    be = engine.get_backend(backend, plan.static.interpret)
    got = np.asarray(be.spatial(x, ba, bs, ck=be.clip_ck(x, ba, 0)))

    blk = {k: np.asarray(v, np.float64) for k, v in p["blocks"][0].items()
           if not isinstance(v, dict)}
    A = np.asarray(get_topology("ntu25", cfg.gcn_kv).adjacency, np.float64)
    args = (np.transpose(A, (0, 2, 1)), np.transpose(blk["Bk"], (0, 2, 1)),
            np.transpose(blk["theta"], (0, 2, 1)), blk["theta_b"],
            np.transpose(blk["phi"], (0, 2, 1)), blk["phi_b"],
            np.transpose(blk["Wk"], (0, 2, 1)))
    xn = np.transpose(np.asarray(x, np.float64), (0, 3, 1, 2))
    want = np.transpose(_unit_gcn_numpy(xn, *args), (0, 2, 3, 1))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    # the comparison has power: C_k in the other orientation is far off
    flipped = np.transpose(_unit_gcn_numpy(xn, *args, flip_ck=True),
                           (0, 2, 3, 1))
    assert np.abs(flipped - want).max() > 0.1 * np.abs(want).max()


def test_published_ck_forward_backends_agree(published_block):
    """The whole clip-form forward: the Pallas backend's kernels
    (ck_proj, ck_sim, graph_sconv_rows) agree with the reference."""
    cfg, p, x = published_block
    ref = M.forward(p, x, cfg, backend="reference")
    pal = M.forward(p, x, cfg, backend="pallas")
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    off = M.forward(p, x, dataclasses.replace(cfg, use_ck=False))
    assert np.abs(np.asarray(off) - np.asarray(ref)).max() > 1e-2


def test_ck_form_is_validated():
    with pytest.raises(ValueError, match="ck_form"):
        dataclasses.replace(CFG, ck_form="frame")
