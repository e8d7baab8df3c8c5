"""Pallas TPU kernel for the streaming windowed-similarity graph C(t).

Fuses the adaptive-streaming C_k evaluation (repro.core.agcn.adaptive)
over the per-slot embedding rings in one VMEM pass: the K-deep window
reduction, the Θ·Φᵀ similarity matmul, the padded-joint column mask and
the row softmax never round-trip the (V, Ce) intermediates to HBM —
per slot the kernel reads two (K, Vp, Ce) rings and writes one (Vp, Vp)
normalized graph.

Layouts:
  ring_th: (S, K, Vp, Ce)   per-slot θ-embedding ring (any ring phase —
  ring_ph: (S, K, Vp, Ce)    the window sum is phase-invariant)
  out:     (S, Vp, Vp)
Grid: (S,) — one program per slab slot; K is a static in-kernel loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(th_ref, ph_ref, out_ref, *, kwin: int, valid: int):
    # window reduction: the ring rows sum to Θ(t)/Φ(t) regardless of phase
    th = th_ref[0, 0].astype(jnp.float32)              # (Vp, Ce)
    ph = ph_ref[0, 0].astype(jnp.float32)
    for k in range(1, kwin):                           # K static
        th = th + th_ref[0, k].astype(jnp.float32)
        ph = ph + ph_ref[0, k].astype(jnp.float32)
    ce = th_ref.shape[-1]
    logits = jax.lax.dot_general(
        th, ph, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * jax.lax.rsqrt(jnp.float32(ce))                 # (Vp, Vp)
    # mask dead input-joint columns (slab padding + the 8-sublane pad)
    vp = logits.shape[-1]
    col = jax.lax.broadcasted_iota(jnp.int32, (vp, vp), 1)
    logits = jnp.where(col < valid, logits, jnp.float32(-1e30))
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    out = e / jnp.sum(e, axis=-1, keepdims=True)
    out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("valid", "interpret"))
def windowed_similarity_pallas(
    ring_th: jnp.ndarray,    # (S, K, Vp, Ce)
    ring_ph: jnp.ndarray,    # (S, K, Vp, Ce)
    valid: int,              # live input-joint count (columns >= it masked)
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Fused window-sum → similarity → masked softmax per slab slot:
    (S, K, Vp, Ce) rings -> (S, Vp, Vp) normalized graphs.

    The reference twin is ``adaptive.windowed_ck(ring.sum(1), ...)``;
    parity ≤1e-3 is locked by tests/test_kernels.py.  Callers pad the
    joint axis (ops.windowed_similarity does this) so Vp is sublane-
    aligned."""
    S, K, Vp, Ce = ring_th.shape
    spec = pl.BlockSpec((1, K, Vp, Ce), lambda s: (s, 0, 0, 0))
    out_spec = pl.BlockSpec((1, Vp, Vp), lambda s: (s, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, kwin=K, valid=valid),
        grid=(S,),
        in_specs=[spec, spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((S, Vp, Vp), ring_th.dtype),
        interpret=interpret,
        name="window_sim",
    )(ring_th, ring_ph)
