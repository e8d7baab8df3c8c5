"""Per-kernel allclose tests: shape/dtype sweeps against the pure-jnp
oracles in repro.kernels.ref (interpret-mode Pallas on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pruning.cavity import cavity_pattern, tile_pattern
from repro.kernels import ops, ref


@pytest.mark.parametrize("rows,cols", [(8, 16), (32, 64), (100, 48), (7, 160)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rfc_encode_matches_ref(rows, cols, dtype):
    x = jax.random.normal(jax.random.PRNGKey(rows * cols), (rows, cols), dtype)
    v_k, h_k = ops.rfc_encode(x, interpret=ops.interpret_mode())
    v_r, h_r = ref.rfc_encode_ref(x.astype(jnp.float32))
    np.testing.assert_allclose(
        np.asarray(v_k, np.float32), np.asarray(v_r), atol=1e-2)
    np.testing.assert_array_equal(np.asarray(h_k) > 0, np.asarray(h_r) > 0)


@pytest.mark.parametrize("rows,cols", [(8, 16), (32, 64), (100, 48)])
def test_rfc_roundtrip(rows, cols):
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, cols))
    v, h = ops.rfc_encode(x, interpret=ops.interpret_mode())
    out = ops.rfc_decode(v, h, interpret=ops.interpret_mode())
    np.testing.assert_allclose(np.asarray(out), np.maximum(np.asarray(x), 0),
                               atol=1e-6)


def test_rfc_multidim():
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 64))
    v, h = ops.rfc_encode(x, interpret=ops.interpret_mode())
    out = ops.rfc_decode(v, h, interpret=ops.interpret_mode())
    assert out.shape == x.shape
    np.testing.assert_allclose(np.asarray(out), np.maximum(np.asarray(x), 0),
                               atol=1e-6)


@pytest.mark.parametrize("pattern", ["cav-50-1", "cav-70-1", "cav-75-1"])
@pytest.mark.parametrize("F,C,T,stride", [
    (16, 16, 64, 1), (24, 32, 48, 2), (8, 8, 32, 1),
])
def test_cavity_tconv_matches_ref(pattern, F, C, T, stride):
    k = jax.random.PRNGKey(F * C + stride)
    w = np.asarray(jax.random.normal(k, (F, C, 9)), np.float32)
    mask = tile_pattern(cavity_pattern(pattern), F)
    wm = w * mask[:, None, :]
    x = jax.random.normal(k, (4, T, C))
    out_ref = ref.cavity_tconv_ref(x, jnp.asarray(wm), stride=stride)
    wp, taps, inv = ops.pack_cavity_weights(wm, mask)
    out = ops.cavity_tconv(x, jnp.asarray(wp), jnp.asarray(taps), inv, F,
                           stride=stride, interpret=ops.interpret_mode())
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("R,V,Ci,Co,K", [
    (32, 25, 16, 32, 3), (64, 25, 64, 64, 3), (16, 25, 3, 8, 3),
    # odd batch×time products: row axis > one tile and not a tile multiple
    # must be padded by ops.graph_sconv, not handed to the grid raw
    (260, 25, 8, 16, 3), (130, 25, 4, 8, 3),
])
def test_graph_sconv_matches_ref(R, V, Ci, Co, K):
    k = jax.random.PRNGKey(R + Ci)
    x = jax.random.normal(k, (2, R // 2, V, Ci))
    g = jax.random.normal(k, (K, V, V))
    w = jax.random.normal(k, (K, Ci, Co))
    out = ops.graph_sconv(x, g, w, interpret=ops.interpret_mode())
    expected = ref.graph_sconv_ref(x.reshape(R, V, Ci), g, w).reshape(
        2, R // 2, V, Co)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("B,S,Hkv,G,D,valid", [
    (1, 512, 2, 4, 32, 512),
    (2, 1024, 4, 3, 64, 700),
    (3, 512, 1, 1, 128, 17),
])
def test_flash_decode_matches_ref(B, S, Hkv, G, D, valid):
    from repro.kernels.flash_decode import flash_decode_pallas
    ks = jax.random.split(jax.random.PRNGKey(B * S), 3)
    q = jax.random.normal(ks[0], (B, Hkv, G, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    out = flash_decode_pallas(q, k, v, jnp.asarray(valid, jnp.int32),
                              interpret=ops.interpret_mode())
    expected = ref.flash_decode_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=3e-5, rtol=3e-5)


def test_cavity_flop_skip_ratio():
    """The packed kernel issues n_keep taps instead of 9 — the paper's
    compute skip, visible in the packed weight shapes."""
    mask = cavity_pattern("cav-70-1")
    F = 32
    w = np.ones((F, 8, 9), np.float32) * tile_pattern(mask, F)[:, None, :]
    wp, taps, _ = ops.pack_cavity_weights(w, tile_pattern(mask, F))
    assert wp.shape[1] <= 4          # ≤4 kept taps vs 9 → ≥55% skipped
    assert wp.shape[1] >= 2
