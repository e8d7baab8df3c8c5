"""Per-kernel allclose tests: shape/dtype sweeps against the pure-jnp
oracles in repro.kernels.ref (interpret-mode Pallas on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pruning.cavity import cavity_pattern, tile_pattern
from repro.kernels import ops, ref


def _rfc_input(rows, cols, dtype, bank=16):
    """Seeded normal rows, the last five replaced by edge rows: all zero,
    all hot, one hot lane at in-bank index 15 (the longest move),
    alternating hot and cold, and hot only in the last bank."""
    x = np.array(jax.random.normal(jax.random.PRNGKey(rows * cols),
                                   (rows, cols)), np.float32)
    lane = np.arange(cols) % bank
    mag = np.abs(x[-5:]) + 0.5
    x[-5] = 0.0
    x[-4] = mag[1]
    x[-3] = np.where(lane == bank - 1, mag[2], -mag[2])
    x[-2] = np.where(lane % 2 == 0, mag[3], -mag[3])
    x[-1] = np.where(np.arange(cols) >= cols - bank, mag[4], -mag[4])
    return jnp.asarray(x, dtype)


# ragged row counts (none a multiple of the 256-row tile) at the model's
# channel widths, besides small and odd shapes
RFC_SHAPES = [(8, 16), (32, 64), (100, 48), (7, 160),
              (1000, 64), (1600, 128), (30400, 256), (1000, 256)]


@pytest.mark.parametrize("rows,cols", RFC_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rfc_encode_matches_ref(rows, cols, dtype):
    x = _rfc_input(rows, cols, dtype)
    v_k, h_k = ops.rfc_encode(x, interpret=ops.interpret_mode())
    v_r, h_r = ref.rfc_encode_ref(x.astype(jnp.float32))
    assert v_k.dtype == h_k.dtype == dtype
    np.testing.assert_array_equal(np.asarray(v_k, np.float32),
                                  np.asarray(v_r))
    np.testing.assert_array_equal(np.asarray(h_k, np.float32),
                                  np.asarray(h_r))


@pytest.mark.parametrize("rows,cols", RFC_SHAPES[:3] + RFC_SHAPES[4:7])
def test_rfc_roundtrip(rows, cols):
    for dtype in (jnp.float32, jnp.bfloat16):
        x = _rfc_input(rows, cols, dtype)
        v, h = ops.rfc_encode(x, interpret=ops.interpret_mode())
        out = ops.rfc_decode(v, h, interpret=ops.interpret_mode())
        assert out.dtype == dtype
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.maximum(np.asarray(x, np.float32),
                                                 0))


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


@pytest.mark.parametrize("N,T,V,Ci,Co,K", [
    (2, 16, 25, 8, 16, 3),
    (3, 75, 25, 6, 8, 3),         # T with odd divisors only: tile 75 or 25
    (2, 12, 25, 130, 256, 3),     # two Cout tiles; a VMEM-capped row tile
    (1, 7, 21, 4, 8, 2),          # prime T, a narrower skeleton
])
def test_graph_sconv_rows_matches_ref(N, T, V, Ci, Co, K):
    """Per-sample graphs: every row of sample n takes g[n], also when a
    row tile is smaller than a sample."""
    ks = jax.random.split(jax.random.PRNGKey(T + Ci), 3)
    x = jax.random.normal(ks[0], (N, T, V, Ci))
    g = jax.random.normal(ks[1], (N, K, V, V))
    w = jax.random.normal(ks[2], (K, Ci, Co))
    out = ops.graph_sconv_rows(x, g, w, interpret=ops.interpret_mode())
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.graph_sconv_rows_ref(x, g, w)),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("S,V,E,valid,scale", [
    (3, 25, 4800, 25, 1.0 / 4800),
    (4, 25, 200, 20, 0.05),
    (2, 8, 128, 8, None),
])
def test_similarity_k1_scaled_matches_jnp(S, V, E, valid, scale):
    """The similarity kernel with K = 1 and a stated scale: a masked
    softmax over the columns of scale·Θ·Φᵀ."""
    from repro.kernels.window_sim import windowed_similarity_pallas

    ks = jax.random.split(jax.random.PRNGKey(E + S), 2)
    vp = -(-V // 8) * 8
    th = jax.random.normal(ks[0], (S, 1, vp, E))
    ph = jax.random.normal(ks[1], (S, 1, vp, E))
    out = windowed_similarity_pallas(th, ph, valid, scale=scale,
                                     name="ck_sim",
                                     interpret=ops.interpret_mode())
    sc = E ** -0.5 if scale is None else scale
    logits = jnp.einsum("sie,sje->sij", th[:, 0], ph[:, 0]) * sc
    logits = jnp.where(jnp.arange(vp) < valid, logits, -1e30)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jax.nn.softmax(logits, -1)),
                               atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("N,T,V,C,Ce,K,valid", [
    (2, 16, 25, 6, 2, 3, 0),
    (1, 40, 25, 16, 8, 3, 22),    # padded input joints masked
    (3, 9, 21, 5, 4, 2, 0),
])
def test_clip_similarity_matches_adaptive(N, T, V, C, Ce, K, valid):
    """ops.clip_similarity (ck_proj + ck_sim) against its jnp twin
    adaptive.clip_ck."""
    from repro.core.agcn import adaptive

    ks = jax.random.split(jax.random.PRNGKey(N * T + C), 3)
    x = jax.random.normal(ks[0], (N, T, V, C))
    w = 2.0 * jax.random.normal(ks[1], (C, 2 * K * Ce)) / np.sqrt(C)
    b = jax.random.normal(ks[2], (2 * K * Ce,))
    out = ops.clip_similarity(x, w, b, K, valid,
                              interpret=ops.interpret_mode())
    want = adaptive.clip_ck(x, w, b, K, valid)
    assert out.shape == (N, K, V, V)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-4)


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
