"""Pallas TPU kernel for the cavity-pruned temporal convolution (paper C2).

The cavity pattern is a recurring loop of ``L`` (=8) tap masks, so filters
fall into L *groups with identical tap sets* (filter f -> group f % L after
the ops-layer permutation).  Within a group the conv is a dense
gather-over-kept-taps + matmul — exactly the FLOP skip of the paper with
full MXU utilisation and static, balanced per-group work (the paper's
"balanced pruning" requirement becomes tile balance here; DESIGN.md §2).

Layouts (after the ops.py re-pack):
  x:    (B, T_pad, C)            input, already zero-padded by K//2 on T
  wp:   (L, n_keep, C, Fg)       packed kept-tap weights per group (taps with
                                 zero weight pad groups that keep fewer taps)
  taps: (L, n_keep) int32        kept tap offsets per group, scalar-prefetched
                                 into SMEM
  out:  (L, B, T_out, Fg)        per-group outputs (ops.py un-permutes); the
                                 group axis leads so every block's last two
                                 dims are whole array dims

Grid: (B tiles, L groups, C chunks).  Each grid step reads its group's tap
offsets from SMEM and issues ``n_keep`` shifted (C×Fg) matmuls instead of
K=9 — the paper's skip ratio.  Channels are cut into 128-lane chunks (the
TPU loads a dynamically offset or strided T window only from a 128-lane
buffer) and accumulated in the resident output block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

B_TILE = 16
C_CHUNK = 128


def _accumulate(out_ref, acc):
    """Write the first channel chunk's partial sum, add the later ones."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        out_ref[0] = acc.astype(out_ref.dtype)

    @pl.when(c > 0)
    def _():
        out_ref[0] += acc.astype(out_ref.dtype)


def _kernel(taps_ref, x_ref, w_ref, out_ref, *, n_keep: int, t_out: int,
            stride: int):
    g = pl.program_id(1)
    bt, _, ct = x_ref.shape
    acc = jnp.zeros((bt * t_out, w_ref.shape[-1]), jnp.float32)
    for j in range(n_keep):                        # static loop over kept taps
        off = taps_ref[g * n_keep + j]
        xs = x_ref[:, pl.ds(off, t_out, stride=stride), :]
        acc += jnp.dot(xs.reshape(bt * t_out, ct), w_ref[0, j],
                       preferred_element_type=jnp.float32)
    _accumulate(out_ref, acc.reshape(bt, t_out, -1))


def _step_kernel(taps_ref, x_ref, w_ref, out_ref, *, n_keep: int):
    """Single-timestep variant: the window (b_tile, K, C) IS the receptive
    field, so each group is just ``n_keep`` gathered (C×Fg) matmuls — no
    temporal slide, no stride (the streaming engine gates emission)."""
    g = pl.program_id(1)
    acc = jnp.zeros((x_ref.shape[0], w_ref.shape[-1]), jnp.float32)
    for j in range(n_keep):                        # static loop over kept taps
        off = taps_ref[g * n_keep + j]
        xs = x_ref[:, pl.ds(off, 1), :][:, 0, :]
        acc += jnp.dot(xs, w_ref[0, j], preferred_element_type=jnp.float32)
    _accumulate(out_ref, acc)


def _tiles(B: int, C: int):
    b_tile = B_TILE if B % B_TILE == 0 else B
    c_tile = C_CHUNK if C % C_CHUNK == 0 else C
    return b_tile, c_tile


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnames=("interpret",))
def cavity_tconv_step_pallas(
    x: jnp.ndarray,        # (B, K, C) chronological window, oldest first
    wp: jnp.ndarray,       # (L, n_keep, C, Fg) — same packing as the clip path
    taps: jnp.ndarray,     # (L, n_keep) int32
    *,
    interpret: bool,
) -> jnp.ndarray:
    """One output timestep per row from a full K-frame window: (L, B, Fg).

    This is the streaming engine's per-frame temporal conv: the packed
    cavity weights and tap sets are byte-identical to the clip kernel's, so
    a plan compiled once serves both dataflows."""
    B, K, C = x.shape
    L, n_keep, _, Fg = wp.shape
    b_tile, c_tile = _tiles(B, C)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B // b_tile, L, C // c_tile),
        in_specs=[
            pl.BlockSpec((b_tile, K, c_tile), lambda b, g, c, t: (b, 0, c)),
            pl.BlockSpec((1, n_keep, c_tile, Fg),
                         lambda b, g, c, t: (g, 0, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, b_tile, Fg), lambda b, g, c, t: (g, b, 0)),
    )
    return pl.pallas_call(
        functools.partial(_step_kernel, n_keep=n_keep),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((L, B, Fg), x.dtype),
        compiler_params=_PARAMS,
        interpret=interpret,
        name="cavity_tconv_step",
    )(taps.reshape(-1), x, wp)


@functools.partial(jax.jit,
                   static_argnames=("t_out", "stride", "interpret"))
def cavity_tconv_pallas(
    x: jnp.ndarray,        # (B, T_pad, C)
    wp: jnp.ndarray,       # (L, n_keep, C, Fg)
    taps: jnp.ndarray,     # (L, n_keep) int32
    *,
    t_out: int,
    stride: int,
    interpret: bool,
) -> jnp.ndarray:
    """Clip-mode packed cavity tconv: (B, T_pad, C) -> (L, B, t_out, Fg).

    ``wp``/``taps`` are the ops.pack_cavity_weights layout — group g holds
    filters g, g+L, g+2L… sharing one kept-tap set; each grid step issues
    only those ``n_keep`` shifted (C×Fg) matmuls (the C2 FLOP skip).  The
    caller (ops.cavity_tconv) provides 'same'+stride zero padding on T so
    that every tap window ``[tap, tap + t_out·stride)`` is in bounds, keeps
    ``t_out`` a sublane multiple, and un-permutes the (L, Fg) filter axis."""
    B, T_pad, C = x.shape
    L, n_keep, _, Fg = wp.shape
    b_tile, c_tile = _tiles(B, C)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B // b_tile, L, C // c_tile),
        in_specs=[
            pl.BlockSpec((b_tile, T_pad, c_tile),
                         lambda b, g, c, t: (b, 0, c)),
            pl.BlockSpec((1, n_keep, c_tile, Fg),
                         lambda b, g, c, t: (g, 0, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, b_tile, t_out, Fg),
                               lambda b, g, c, t: (g, b, 0, 0)),
    )
    kern = functools.partial(_kernel, n_keep=n_keep, t_out=t_out,
                             stride=stride)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((L, B, t_out, Fg), x.dtype),
        compiler_params=_PARAMS,
        interpret=interpret,
        name="cavity_tconv",
    )(taps.reshape(-1), x, wp)
