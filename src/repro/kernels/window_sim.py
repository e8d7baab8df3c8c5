"""Pallas TPU kernels for the data-dependent similarity graph C_k.

``windowed_similarity_pallas`` fuses one similarity evaluation per grid
row in one VMEM pass: the K-deep reduction of two embedding blocks, the
Θ·Φᵀ similarity matmul, the padded-joint column mask and the row softmax
never round-trip the intermediates to HBM — per row the kernel reads two
(K, Vp, E) blocks and writes one (Vp, Vp) normalized graph.  It serves
both forms of C_k (``repro.core.agcn.adaptive``):

  window  the streaming reformulation: rows are slab slots, the K axis
          is the per-slot embedding ring (any ring phase — the window sum
          is phase-invariant), E = Ce, scale 1/√Ce; kernel ``window_sim``.
  clip    the published whole-clip C_k: rows are (sample, subset) pairs,
          K = 1 and E = Ce·T (the embeddings flattened over the clip),
          scale 1/(Ce·T); kernel ``ck_sim``.

``ck_projection_pallas`` computes the clip form's embeddings — every
subset's θ_k and φ_k 1×1 convolutions, biases included, as one matmul
per row tile; kernel ``ck_proj``.

Layouts:
  ring_th: (S, K, Vp, E)    row-side embeddings (Θ of the windowed form)
  ring_ph: (S, K, Vp, E)    column-side embeddings: the softmax axis
  out:     (S, Vp, Vp)
Grid: (S,) — one program per row; K is a static in-kernel loop.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.graph_sconv import _lanes, budget_rows


def _kernel(th_ref, ph_ref, out_ref, *, kwin: int, valid: int,
            scale: float):
    # window reduction: the ring rows sum to Θ(t)/Φ(t) regardless of phase
    th = th_ref[0, 0].astype(jnp.float32)              # (Vp, Ce)
    ph = ph_ref[0, 0].astype(jnp.float32)
    for k in range(1, kwin):                           # K static
        th = th + th_ref[0, k].astype(jnp.float32)
        ph = ph + ph_ref[0, k].astype(jnp.float32)
    logits = jax.lax.dot_general(
        th, ph, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * jnp.float32(scale)                             # (Vp, Vp)
    # mask dead input-joint columns (slab padding + the 8-sublane pad)
    vp = logits.shape[-1]
    col = jax.lax.broadcasted_iota(jnp.int32, (vp, vp), 1)
    logits = jnp.where(col < valid, logits, jnp.float32(-1e30))
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    out = e / jnp.sum(e, axis=-1, keepdims=True)
    out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("valid", "scale", "name", "interpret"))
def windowed_similarity_pallas(
    ring_th: jnp.ndarray,    # (S, K, Vp, E)
    ring_ph: jnp.ndarray,    # (S, K, Vp, E)
    valid: int,              # live input-joint count (columns >= it masked)
    *,
    interpret: bool,
    scale: Optional[float] = None,
    name: str = "window_sim",
) -> jnp.ndarray:
    """Fused K-sum → similarity → masked softmax per row:
    (S, K, Vp, E) blocks -> (S, Vp, Vp) normalized graphs,
    ``out[s, i, j] = softmax_j(scale · Θ_s[i]·Φ_s[j])`` over the columns
    j < ``valid``, with Θ/Φ the K-sums of ``ring_th``/``ring_ph``.

    ``scale`` defaults to 1/√E (the windowed form); ``name`` is the
    kernel's name in compiled programs and traces.  The reference twins
    are ``adaptive.windowed_ck(ring.sum(1), ...)`` and
    ``adaptive.clip_ck``; parity is locked by tests/test_kernels.py.
    Callers pad the joint axis (the ``ops`` wrappers do this) so Vp is
    sublane-aligned."""
    S, K, Vp, E = ring_th.shape
    if scale is None:
        scale = float(E) ** -0.5
    spec = pl.BlockSpec((1, K, Vp, E), lambda s: (s, 0, 0, 0))
    out_spec = pl.BlockSpec((1, Vp, Vp), lambda s: (s, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, kwin=K, valid=valid, scale=float(scale)),
        grid=(S,),
        in_specs=[spec, spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((S, Vp, Vp), ring_th.dtype),
        interpret=interpret,
        name=name,
    )(ring_th, ring_ph)


def proj_row_tile(vp: int, c: int, f: int) -> int:
    """Largest power-of-two row tile of :func:`ck_projection_pallas` whose
    double-buffered x and out blocks and f32 result fit the VMEM budget."""
    return budget_rows(4 * vp * (2 * _lanes(c) + 3 * _lanes(f)))


def _proj_kernel(x_ref, w_ref, b_ref, out_ref):
    r, vp, c = x_ref.shape
    y = jnp.dot(x_ref[...].reshape(r * vp, c), w_ref[...],
                preferred_element_type=jnp.float32)
    y = y + b_ref[...].astype(jnp.float32)
    out_ref[...] = y.reshape(out_ref.shape).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def ck_projection_pallas(
    x: jnp.ndarray,          # (R, Vp, C)
    w: jnp.ndarray,          # (C, F)
    b: jnp.ndarray,          # (1, F)
    *,
    row_tile: int,
    interpret: bool,
) -> jnp.ndarray:
    """``x · w + b`` per joint row: (R, Vp, C) -> (R, Vp, F), the clip
    form's θ/φ embeddings of every subset in one pass (F = 2·K·Ce).
    ``row_tile`` must divide R (ops.clip_similarity pads R)."""
    R, Vp, C = x.shape
    F = w.shape[-1]
    return pl.pallas_call(
        _proj_kernel,
        grid=(R // row_tile,),
        in_specs=[pl.BlockSpec((row_tile, Vp, C), lambda r: (r, 0, 0)),
                  pl.BlockSpec((C, F), lambda r: (0, 0)),
                  pl.BlockSpec((1, F), lambda r: (0, 0))],
        out_specs=pl.BlockSpec((row_tile, Vp, F), lambda r: (r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, Vp, F), x.dtype),
        interpret=interpret,
        name="ck_proj",
    )(x, w, b)
