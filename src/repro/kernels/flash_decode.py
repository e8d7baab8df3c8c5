"""Pallas TPU kernel: fused GQA decode attention (one query token against a
long KV cache) with online softmax — the serving hot spot of the decode_32k
/ long_500k cells.

Unfused decode attention materialises the (H × S) score row in HBM; this
kernel streams KV blocks through VMEM and keeps the running max/sum/acc in
scratch, so HBM traffic is exactly one read of the KV cache — the roofline
floor for decode.

Layouts:
  q:     (B, Hkv, G, D)    grouped query heads (G = H // Hkv)
  k, v:  (B, S, Hkv, D)    cache
  valid: (1, 1) int32      number of valid cache slots
  out:   (B, Hkv, G, D)

Grid: (B, Hkv, S_blocks) — the S axis is the innermost (sequential) axis so
the scratch accumulator carries across KV blocks of one (batch, kv-head).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

S_BLOCK = 512
NEG_INF = -1e30


def _kernel(valid_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, s_block: int, scale: float):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                # (G, D)
    k = k_ref[0, :, 0, :]                          # (Sblk, D)
    v = v_ref[0, :, 0, :]                          # (Sblk, D)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                      # (G, Sblk)
    pos = s_idx * s_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < valid_ref[0, 0], s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]        # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)                         # (G, Sblk)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                              # (G, D)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(s_idx == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_decode_pallas(
    q: jnp.ndarray,        # (B, Hkv, G, D)
    k: jnp.ndarray,        # (B, S, Hkv, D)
    v: jnp.ndarray,        # (B, S, Hkv, D)
    valid: jnp.ndarray,    # scalar int32
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Fused GQA decode attention with online softmax: one query token per
    (batch, kv-head, group) against a ``valid``-masked KV cache — q
    (B, Hkv, G, D), k/v (B, S, Hkv, D) -> (B, Hkv, G, D), with exactly one
    HBM read of the cache (running max/sum/acc live in VMEM scratch)."""
    B, Hkv, G, D = q.shape
    S = k.shape[1]
    s_block = min(S_BLOCK, S)
    if S % s_block:
        raise ValueError(f"S={S} not divisible by block {s_block}")
    grid = (B, Hkv, S // s_block)
    scale = D ** -0.5

    return pl.pallas_call(
        functools.partial(_kernel, s_block=s_block, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, h, s: (0, 0)),
            pl.BlockSpec((1, 1, G, D), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, s_block, 1, D), lambda b, h, s: (b, s, h, 0)),
            pl.BlockSpec((1, s_block, 1, D), lambda b, h, s: (b, s, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, s: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),       # running max
            pltpu.VMEM((G, 1), jnp.float32),       # running sum
            pltpu.VMEM((G, D), jnp.float32),       # output accumulator
        ],
        interpret=interpret,
    )(valid.reshape(1, 1), q, k, v)
