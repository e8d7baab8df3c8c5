"""The main-path Pallas kernels compile for a TPU v5e at published widths.

Interpret mode cannot see what the chip's compiler refuses (unaligned
tiles, unsupported vector reshapes, VMEM overuse), so every kernel of the
default Pallas serving path is compiled here for a *described* v5e chip —
no chip attached — at the 2s-AGCN's channel widths (64/128/256 plus pruned
kept widths), both skeleton sizes (V = 25, 50) and T = 300, with
``interpret=False`` forced by the test (the platform here is the CPU).

The topology is described inside a module fixture, never while a module
is imported: only one process may load the TPU compiler library, and with
several test workers the others must collect the same tests and never
touch it.  Keep these tests in this one file.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.pruning.cavity import cavity_pattern, tile_pattern
from repro.kernels import ops

T = 300
N = 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(one_chip, fn, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("V,cin,cout", [
    (25, 3, 64),          # block 0: the 3 input coordinates
    (25, 38, 64),         # pruned kept width 0.6 × 64
    (25, 128, 128),
    (25, 90, 256),        # pruned kept width 0.35 × 256
    (50, 64, 128),
    (50, 256, 256),
])
def test_graph_sconv_dense_compiles(one_chip, V, cin, cout):
    vp = -(-V // 8) * 8
    text = _compiled_text(
        one_chip,
        lambda x, g, w: ops.graph_sconv(x, g, w, interpret=False),
        ((N, T, V, cin), jnp.float32), ((3, vp, vp), jnp.float32),
        ((3, cin, cout), jnp.float32))
    assert ops.kernel_counts(text) == {"graph_sconv": 1}


@pytest.mark.parametrize("V,cin,cout", [(25, 38, 64), (50, 256, 256)])
def test_graph_sconv_csr_compiles(one_chip, V, cin, cout):
    vp = -(-V // 8) * 8
    text = _compiled_text(
        one_chip,
        lambda x, i, v, w: ops.graph_sconv_csr(x, i, v, w, interpret=False),
        ((N, T, V, cin), jnp.float32), ((3, vp, 4), jnp.int32),
        ((3, vp, 4), jnp.float32), ((3, cin, cout), jnp.float32))
    assert ops.kernel_counts(text) == {"graph_sconv_csr": 1}


def _packed(C: int, F: int):
    mask = tile_pattern(cavity_pattern("cav-70-1"), F)
    w = np.ones((F, C, 9), np.float32) * mask[:, None, :]
    wp, taps, inv = ops.pack_cavity_weights(w, mask)
    return wp.shape, taps.shape, inv


@pytest.mark.parametrize("V,C,F,stride", [
    (25, 64, 38, 1),      # block 1: kept filters = next block's kept_in
    (25, 64, 64, 2),
    (25, 128, 51, 1),
    (25, 256, 77, 2),
    (50, 256, 256, 1),    # last block keeps every filter
])
def test_cavity_tconv_clip_compiles(one_chip, V, C, F, stride):
    wp, taps, inv = _packed(C, F)
    text = _compiled_text(
        one_chip,
        lambda x, w, t: ops.cavity_tconv(x, w, t, inv, F, stride=stride,
                                         interpret=False),
        ((N * V, T, C), jnp.float32), (wp, jnp.float32), (taps, jnp.int32))
    assert ops.kernel_counts(text) == {"cavity_tconv": 1}


@pytest.mark.parametrize("V,C,F", [(25, 64, 38), (50, 256, 77)])
def test_cavity_tconv_step_compiles(one_chip, V, C, F):
    wp, taps, inv = _packed(C, F)
    slots = 16
    text = _compiled_text(
        one_chip,
        lambda x, w, t: ops.cavity_tconv_step(x, w, t, inv, F,
                                              interpret=False),
        ((slots * V, 9, C), jnp.float32), (wp, jnp.float32),
        (taps, jnp.int32))
    assert ops.kernel_counts(text) == {"cavity_tconv_step": 1}


@pytest.mark.parametrize("V,C", [
    (25, 64), (25, 128), (50, 256),
    (200, 64), (200, 256),    # clip-sized: 2·300·200 = 120 000 rows, not a
])                            # multiple of the 256-row tile
def test_rfc_roundtrip_compiles(one_chip, V, C):
    def roundtrip(h):
        vals, hot = ops.rfc_encode(h, interpret=False)
        return ops.rfc_decode(vals, hot, interpret=False)

    for dtype in (jnp.float32, jnp.bfloat16):
        text = _compiled_text(one_chip, roundtrip, ((N, T, V, C), dtype))
        assert ops.kernel_counts(text) == {"rfc_encode": 1, "rfc_decode": 1}
        # ragged row tiles: no padding to whole tiles, no slicing back
        assert not re.search(r"\b(pad|slice|dynamic-slice)\(", text)


@pytest.mark.parametrize("V,ce", [(25, 16), (50, 64)])
def test_window_sim_compiles(one_chip, V, ce):
    slots = 16
    text = _compiled_text(
        one_chip,
        lambda a, b: ops.windowed_similarity(a, b, valid_joints=V,
                                             interpret=False),
        ((slots, 9, V, ce), jnp.float32), ((slots, 9, V, ce), jnp.float32))
    assert ops.kernel_counts(text) == {"window_sim": 1}


@pytest.mark.parametrize("T,cin,cout", [
    (300, 3, 64),         # block 0
    (300, 64, 64),
    (150, 128, 128),
    (75, 256, 256),
])
def test_graph_sconv_rows_compiles(one_chip, T, cin, cout):
    V, vp = 25, 32
    text = _compiled_text(
        one_chip,
        lambda x, g, w: ops.graph_sconv_rows(x, g, w, interpret=False),
        ((N, T, V, cin), jnp.float32), ((N, 3, vp, vp), jnp.float32),
        ((3, cin, cout), jnp.float32))
    assert ops.kernel_counts(text) == {"graph_sconv_rows": 1}


@pytest.mark.parametrize("T,cin,cout", [
    (300, 3, 64),         # Ce·T = 4800 at every published block
    (300, 64, 64),
    (150, 128, 128),
    (75, 256, 256),
    (300, 64, 128),       # Ce·T = 9600
])
def test_clip_similarity_compiles(one_chip, T, cin, cout):
    ce = cout // 4
    text = _compiled_text(
        one_chip,
        lambda x, w, b: ops.clip_similarity(x, w, b, 3, interpret=False),
        ((N, T, 25, cin), jnp.float32), ((cin, 6 * ce), jnp.float32),
        ((6 * ce,), jnp.float32))
    assert ops.kernel_counts(text) == {"ck_proj": 1, "ck_sim": 1}

