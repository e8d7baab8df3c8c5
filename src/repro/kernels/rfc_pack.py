"""Pallas TPU kernels for RFC encode/decode (paper §V-C → DESIGN.md §2).

TPU-native formulation: in-bank compaction is a *permutation*, and
permutations are matmuls — so instead of a sort (which lowers poorly to the
VPU) every step is a 2-D dot on the MXU:

  * the in-bank position of each non-zero is ``hot @ U`` with ``U`` the
    block-diagonal upper-triangular ones matrix (a per-bank inclusive
    prefix count, exact in any precision: 0/1 inputs, sums ≤ bank);
  * the compaction sums, per target slot q, the one value of each bank
    whose position is q: ``where(pos == q, x, 0) @ S`` with ``S`` the
    block-diagonal same-bank ones matrix, kept at the lanes ``≡ q (mod
    bank)``.  These dots run at HIGHEST precision, so each output is its
    input value exactly (one non-zero term per sum).

All tiles are 2-D (rows, columns), so no lane-splitting reshape reaches
the compiler; the bank width 16 divides the 128-lane vreg, mirroring the
paper's "one-cycle aligned access" property.

Layouts:
  x:       (rows, C)            activations, C % bank == 0
  values:  (rows, C)            compacted banks (front-packed, zero padded)
  hot:     (rows, C) float mask (1.0 where the ReLU output was non-zero)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BANK = 16
ROW_TILE = 256
COL_TILE = 128
_HIGHEST = jax.lax.Precision.HIGHEST


def _bank_mats(cols: int, bank: int):
    """(U, S): block-diagonal inclusive-prefix and same-bank ones matrices."""
    i = jax.lax.broadcasted_iota(jnp.int32, (cols, cols), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (cols, cols), 1)
    same = (i // bank) == (j // bank)
    upper = jnp.where(same & (i <= j), 1.0, 0.0).astype(jnp.float32)
    return upper, same.astype(jnp.float32)


def _positions(hot, upper):
    """Slot of each element inside its bank's compacted stream (-1 = cold)."""
    pos = jnp.dot(hot, upper, preferred_element_type=jnp.float32) - 1.0
    return jnp.where(hot > 0.0, pos, -1.0)


def _encode_kernel(x_ref, vals_ref, hot_ref, *, bank: int):
    x = jnp.maximum(x_ref[...].astype(jnp.float32), 0.0)  # fused ReLU (paper:
    rows, cols = x.shape                                  # encode is combined
    hot = (x > 0.0).astype(jnp.float32)                   # with ReLU)
    upper, same = _bank_mats(cols, bank)
    pos = _positions(hot, upper)
    slot = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1) % bank
    vals = jnp.zeros((rows, cols), jnp.float32)
    for q in range(bank):
        # each bank's q-th non-zero, broadcast over its bank, kept at slot q
        s = jnp.dot(jnp.where(pos == q, x, 0.0), same,
                    preferred_element_type=jnp.float32, precision=_HIGHEST)
        vals = jnp.where(slot == q, s, vals)
    vals_ref[...] = vals.astype(vals_ref.dtype)
    hot_ref[...] = hot.astype(hot_ref.dtype)


def _decode_kernel(vals_ref, hot_ref, out_ref, *, bank: int):
    vals = vals_ref[...].astype(jnp.float32)
    hot = hot_ref[...].astype(jnp.float32)
    rows, cols = vals.shape
    upper, same = _bank_mats(cols, bank)
    pos = _positions(hot, upper)
    slot = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1) % bank
    out = jnp.zeros((rows, cols), jnp.float32)
    for q in range(bank):
        # slot q of each bank, broadcast back to the element at position q
        b = jnp.dot(jnp.where(slot == q, vals, 0.0), same,
                    preferred_element_type=jnp.float32, precision=_HIGHEST)
        out = jnp.where(pos == q, b, out)
    out_ref[...] = out.astype(out_ref.dtype)


def _spec(rows: int, cols: int):
    """Grid and block: callers pad rows to the row tile (ops.rfc_encode);
    a column count that is not a whole number of 128-lane tiles rides as
    one full-width block."""
    r_tile = min(ROW_TILE, rows)
    c_tile = COL_TILE if cols % COL_TILE == 0 else cols
    if rows % r_tile:
        raise ValueError(f"rows={rows} not a multiple of the row tile "
                         f"{r_tile}; pad them (ops.rfc_encode does this)")
    grid = (rows // r_tile, cols // c_tile)
    return grid, pl.BlockSpec((r_tile, c_tile), lambda r, c: (r, c))


@functools.partial(jax.jit, static_argnames=("bank", "interpret"))
def rfc_encode_pallas(x: jnp.ndarray, *, bank: int = BANK, interpret: bool):
    """ReLU + bank-compact.  x: (rows, C) -> (values, hot) both (rows, C)."""
    rows, cols = x.shape
    if cols % bank:
        raise ValueError(f"C={cols} not divisible by bank={bank}")
    grid, spec = _spec(rows, cols)
    return pl.pallas_call(
        functools.partial(_encode_kernel, bank=bank),
        grid=grid,
        in_specs=[spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), x.dtype),
            jax.ShapeDtypeStruct((rows, cols), x.dtype),
        ],
        interpret=interpret,
        name="rfc_encode",
    )(x)


@functools.partial(jax.jit, static_argnames=("bank", "interpret"))
def rfc_decode_pallas(values: jnp.ndarray, hot: jnp.ndarray, *,
                      bank: int = BANK, interpret: bool) -> jnp.ndarray:
    """Bank-decompact via the same one-hot matmuls in reverse:
    (values, hot) (rows, C) -> dense (rows, C).  Exact inverse of
    :func:`rfc_encode_pallas` on post-ReLU data."""
    rows, cols = values.shape
    grid, spec = _spec(rows, cols)
    return pl.pallas_call(
        functools.partial(_decode_kernel, bank=bank),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, cols), values.dtype),
        interpret=interpret,
        name="rfc_decode",
    )(values, hot)
