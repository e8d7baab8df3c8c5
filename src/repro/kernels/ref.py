"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the per-kernel allclose tests
(tests/test_kernels.py sweeps shapes/dtypes against them).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def rfc_encode_ref(x: jnp.ndarray, bank: int = 16):
    """ReLU + stable in-bank compaction.  x: (rows, C)."""
    x = jnp.maximum(x, 0.0)
    rows, cols = x.shape
    b = x.reshape(rows, cols // bank, bank)
    hot = b > 0
    order = jnp.argsort(~hot, axis=-1, stable=True)
    vals = jnp.take_along_axis(b, order, axis=-1)
    return vals.reshape(rows, cols), hot.astype(x.dtype).reshape(rows, cols)


def rfc_decode_ref(values: jnp.ndarray, hot: jnp.ndarray, bank: int = 16):
    """Scatter front-packed bank values back to their hot positions —
    the decode oracle; (rows, C) in, (rows, C) out."""
    rows, cols = values.shape
    v = values.reshape(rows, cols // bank, bank)
    h = hot.reshape(rows, cols // bank, bank) > 0
    pos = jnp.cumsum(h.astype(jnp.int32), axis=-1) - 1
    out = jnp.where(h, jnp.take_along_axis(v, jnp.maximum(pos, 0), axis=-1), 0)
    return out.reshape(rows, cols)


def cavity_tconv_ref(
    x: jnp.ndarray,        # (B, T, C) — *unpadded*
    w: jnp.ndarray,        # (F, C, K) masked weights (zeros at pruned taps)
    stride: int = 1,
) -> jnp.ndarray:
    """Dense masked temporal conv, 'same' padding — (B, T_out, F)."""
    K = w.shape[-1]
    pad = K // 2
    rhs = jnp.transpose(w, (2, 1, 0))[:, None, :, :]  # (K, 1, C, F)
    out = jax.lax.conv_general_dilated(
        x[:, :, None, :], rhs,
        window_strides=(stride, 1),
        padding=((pad, pad), (0, 0)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return out[:, :, 0, :]


def graph_sconv_ref(x: jnp.ndarray, g: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """out = sum_k (G_k·x)·W_k.  x: (R, V, Cin), g: (K, V, V), w: (K, Cin, Co)."""
    y = jnp.einsum("rvc,kwv->krwc", x, g)
    return jnp.einsum("krwc,kco->rwo", y, w)


def graph_sconv_rows_ref(x: jnp.ndarray, g: jnp.ndarray,
                         w: jnp.ndarray) -> jnp.ndarray:
    """Per-sample graphs: out[n, t] = Σ_k (G_k[n]·x[n, t])·W_k.
    x: (N, T, V, Cin), g: (N, K, V, V), w: (K, Cin, Co)."""
    y = jnp.einsum("ntvc,nkwv->ntkwc", x, g)
    return jnp.einsum("ntkwc,kco->ntwo", y, w)


def graph_sconv_csr_ref(x, indptr, indices, values, w):
    """CSR spatial conv: gather-accumulate over indptr/indices per subset.

    x: (R, Vx, Cin) with Vx >= V (extra rows are padding the graph never
    references), indptr: (K, V+1), indices/values: (K, E) zero-padded,
    w: (K, Cin, Co).  Returns (R, V, Co).
    """
    K, E = indices.shape
    V = indptr.shape[1] - 1
    R, _, C = x.shape
    out = jnp.zeros((R, V, w.shape[-1]), jnp.float32)
    for k in range(K):
        # entry e lives on output row w iff indptr[k,w] <= e < indptr[k,w+1];
        # zero-padded entries map past the last row and are dropped.
        rows = jnp.searchsorted(indptr[k], jnp.arange(E), side="right") - 1
        gathered = jnp.take(x, indices[k], axis=1) * values[k][None, :, None]
        agg = jnp.zeros((R, V, C), x.dtype).at[:, rows, :].add(
            gathered, mode="drop")
        out = out + jnp.einsum("rvc,co->rvo", agg, w[k],
                               preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


def flash_decode_ref(q, k, v, valid):
    """GQA decode attention oracle.  q: (B,Hkv,G,D), k/v: (B,S,Hkv,D)."""
    D = q.shape[-1]
    S = k.shape[1]
    s = jnp.einsum("bhgd,bshd->bhgs", q, k) / np.sqrt(D)
    s = jnp.where(jnp.arange(S) < valid, s, -1e30)
    return jnp.einsum("bhgs,bshd->bhgd", jax.nn.softmax(s, -1), v)
