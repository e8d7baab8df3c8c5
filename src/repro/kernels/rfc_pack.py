"""Pallas TPU kernels for RFC encode/decode (paper §V-C → DESIGN.md §2).

TPU-native formulation: in-bank compaction is a fixed lane-shift network
on the VPU, so each kernel costs about the bytes it moves:

  * the in-bank position of each non-zero is an inclusive prefix count of
    the hot lanes, by log-step shifted adds (s = 1, 2, 4, 8 within each
    bank);
  * hot lane i moves left by its displacement d = (i mod bank) − position,
    one bit at a time, lowest first: at step s an element whose d has bit
    s set moves s lanes, carrying its value and d.  Encode compacts;
    decode compacts d alone, then replays the steps in reverse (highest
    bit first, moving right) to put each front-packed value back.

No two elements ever meet: for hot i < j, 0 ≤ d_j − d_i < j − i, so after
the low k bits they could collide only if j − i ≡ d_j − d_i (mod 2^k),
impossible for 0 < j − i < 2^k; and d ≤ i mod bank keeps every move
inside its bank.  Moves are selects, so every output bit is an input bit.

All tiles are 2-D (rows, columns), so no lane-splitting reshape reaches
the compiler; the bank width 16 divides the 128-lane vreg, mirroring the
paper's "one-cycle aligned access" property.  Rows ride a ragged grid:
the last row tile may overhang, its extra rows are never written.  A C
below 128 that divides it is folded, 128 // C rows to a 128-lane row,
since a narrower block fills part of each vreg and its rolls are
emulated (C = 64 ran ~3.5× slower per element unfolded on a v5e).

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
from jax.experimental.pallas import tpu as pltpu

BANK = 16
ROW_TILE = 1024
COL_TILE = 128


def _steps(bank: int):
    """Shift distances 1, 2, 4, … < bank, one per bit of a displacement."""
    return [1 << k for k in range(bank.bit_length() - 1)]


def _left(a, s: int):
    """Lane p takes lane p + s (wrapping lanes are never selected)."""
    return pltpu.roll(a, a.shape[1] - s, 1)


def _right(a, s: int):
    """Lane p takes lane p − s (wrapping lanes are never selected)."""
    return pltpu.roll(a, s, 1)


def _tags(hot, bank: int):
    """Per lane: bank + d for a hot lane (d its left displacement), 0 for a
    cold one — ``bank`` is a flag bit above every shift bit."""
    lane = jax.lax.broadcasted_iota(jnp.int32, hot.shape, 1) & (bank - 1)
    count = hot.astype(jnp.int32)
    for s in _steps(bank):                 # inclusive in-bank prefix count
        count = count + jnp.where(lane >= s, _right(count, s), 0)
    return jnp.where(hot, lane - count + 1 + bank, 0)


def _shift(tag, payload, s: int, move):
    """One network step: elements whose tag has bit ``s`` move by ``s``
    (``move`` is :func:`_left` or :func:`_right`); vacated lanes read 0."""
    leaving = (tag & s) != 0
    incoming = move(tag, s)
    arriving = (incoming & s) != 0
    tag = jnp.where(arriving, incoming, jnp.where(leaving, 0, tag))
    return tag, [jnp.where(arriving, move(p, s), jnp.where(leaving, 0, p))
                 for p in payload]


def _encode_kernel(x_ref, vals_ref, hot_ref, *, bank: int):
    x = jnp.maximum(x_ref[...].astype(jnp.float32), 0.0)  # fused ReLU (paper:
    hot = x > 0.0                                         # encode is combined
    tag = _tags(hot, bank)                                # with ReLU)
    vals = (jnp.where(hot, x, 0.0),)
    for s in _steps(bank):
        tag, vals = _shift(tag, vals, s, _left)
    vals_ref[...] = vals[0].astype(vals_ref.dtype)
    hot_ref[...] = hot.astype(hot_ref.dtype)


def _decode_kernel(vals_ref, hot_ref, out_ref, *, bank: int):
    tag = _tags(hot_ref[...].astype(jnp.float32) > 0.0, bank)
    for s in _steps(bank):                 # each slot learns its d
        tag, _ = _shift(tag, (), s, _left)
    vals = (jnp.where(tag != 0, vals_ref[...].astype(jnp.float32), 0.0),)
    for s in reversed(_steps(bank)):
        tag, vals = _shift(tag, vals, s, _right)
    out_ref[...] = vals[0].astype(out_ref.dtype)


def _spec(rows: int, cols: int, bank: int):
    """Grid and block: a ragged grid of row tiles (the row count rounded
    down to 8, at most ``ROW_TILE``); a column count that is not a whole
    number of 128-lane tiles rides as one full-width block."""
    c_tile = COL_TILE if cols % COL_TILE == 0 else cols
    if c_tile % bank or bank & (bank - 1):
        raise ValueError(f"C={cols} not divisible by bank={bank}, or the "
                         f"bank is not a power of two")
    r_tile = rows if rows < 8 else min(ROW_TILE, rows) // 8 * 8
    grid = (pl.cdiv(rows, r_tile), cols // c_tile)
    return grid, pl.BlockSpec((r_tile, c_tile), lambda r, c: (r, c))


def _lane_dense(x):
    """(rows, C) seen 128 lanes wide where C divides 128 and the rows fold
    evenly: a narrower block would leave vreg lanes idle, and banks never
    straddle a fold since the bank divides C."""
    rows, cols = x.shape
    if cols < COL_TILE and COL_TILE % cols == 0 and rows * cols % COL_TILE == 0:
        return x.reshape(-1, COL_TILE)
    return x


@functools.partial(jax.jit, static_argnames=("bank", "interpret"))
def rfc_encode_pallas(x: jnp.ndarray, *, bank: int = BANK, interpret: bool):
    """ReLU + bank-compact.  x: (rows, C) -> (values, hot) both (rows, C)."""
    if x.shape[1] % bank:
        raise ValueError(f"C={x.shape[1]} not divisible by bank={bank}")
    xd = _lane_dense(x)
    grid, spec = _spec(*xd.shape, bank)
    vals, hot = pl.pallas_call(
        functools.partial(_encode_kernel, bank=bank),
        grid=grid,
        in_specs=[spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(xd.shape, x.dtype)] * 2,
        interpret=interpret,
        name="rfc_encode",
    )(xd)
    return vals.reshape(x.shape), hot.reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("bank", "interpret"))
def rfc_decode_pallas(values: jnp.ndarray, hot: jnp.ndarray, *,
                      bank: int = BANK, interpret: bool) -> jnp.ndarray:
    """Bank-decompact by the encode network in reverse:
    (values, hot) (rows, C) -> dense (rows, C).  Exact inverse of
    :func:`rfc_encode_pallas` on post-ReLU data."""
    if values.shape[1] % bank:
        raise ValueError(f"C={values.shape[1]} not divisible by bank={bank}")
    vd, hd = _lane_dense(values), _lane_dense(hot)
    grid, spec = _spec(*vd.shape, bank)
    return pl.pallas_call(
        functools.partial(_decode_kernel, bank=bank),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(vd.shape, values.dtype),
        interpret=interpret,
        name="rfc_decode",
    )(vd, hd).reshape(values.shape)
