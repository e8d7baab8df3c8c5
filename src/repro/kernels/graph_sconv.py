"""Pallas TPU kernel for the fused, reorganized graph + 1×1 spatial conv
(paper C1, eq. (5)).

Computes   out = Σ_k (G_k · x) · W_k   in one VMEM pass: the graph matmul
(V×V, V=25 padded to 32 sublanes) and the pruned 1×1 conv share the x
tile, so the intermediate (G·x) never round-trips to HBM — the TPU analogue
of the paper's on-chip dataflow where graph results feed Mult-PEs directly.

Channel compaction happens in ops.py (kept channels gathered before the
call), so Cin here is the *kept* channel count — the graph-skip is already
realised in the shapes.  Both matmuls are plain dots the TPU compiler
lowers at any channel width: the graph matmul is a row-batched
(Vp×Vp)·(Vp×Cin) dot, and the 1×1 conv one (r·Vp×Cin)·(Cin×co) dot whose
row merge is free because Vp is a sublane multiple.

Layouts:
  x:   (R, Vp, Cin)   rows = N*T (flattened batch×time)
  g:   (K, Vp, Vp)    static + learned graph, padded to Vp
  w:   (K, Cin, Cout)
  out: (R, Vp, Cout)
Grid: (R tiles, Cout tiles); K is a static in-kernel loop.  The row tile
is chosen from a VMEM budget (:func:`row_tile`), not fixed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

CO_TILE = 128
VMEM_BUDGET = 8 * 2 ** 20     # half the default scoped VMEM of a v5e core
MAX_ROW_TILE = 256


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def row_tile(vp: int, cin: int, co: int) -> int:
    """Largest power-of-two row tile whose VMEM footprint fits the budget:
    double-buffered x and out blocks, the broadcast graph, the graph-matmul
    result and the f32 accumulator, each lane-padded to 128."""
    per_row = 4 * vp * (3 * _lanes(cin) + 4 * _lanes(co) + _lanes(vp))
    r = MAX_ROW_TILE
    while r > 8 and r * per_row > VMEM_BUDGET:
        r //= 2
    return r


def _graph_conv(x, g_of_k, w_ref, kv: int):
    """Σ_k (G_k·x)·W_k on one (r, Vp, Cin) tile; ``g_of_k(k)`` yields the
    (Vp, Vp) graph of subset k.  Returns the (r·Vp, co) f32 accumulator."""
    r, vp, cin = x.shape
    acc = jnp.zeros((r * vp, w_ref.shape[-1]), jnp.float32)
    for k in range(kv):                             # K_v = 3, static
        gb = jnp.broadcast_to(g_of_k(k), (r, vp, vp))
        # graph matmul, batched over rows: y[r, w, c] = Σ_v g[w, v] x[r, v, c]
        y = jax.lax.dot_general(
            gb, x, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc += jnp.dot(y.reshape(r * vp, cin), w_ref[k],
                       preferred_element_type=jnp.float32)
    return acc


def _kernel(x_ref, g_ref, w_ref, out_ref, *, kv: int):
    x = x_ref[...]
    acc = _graph_conv(x, lambda k: g_ref[k], w_ref, kv)
    out_ref[...] = acc.reshape(out_ref.shape).astype(out_ref.dtype)


def _grid(R: int, Vp: int, Cin: int, Cout: int):
    co_tile = CO_TILE if Cout % CO_TILE == 0 else Cout
    r_tile = min(row_tile(Vp, Cin, co_tile), R)
    if R % r_tile:
        raise ValueError(
            f"row axis R={R} is not a multiple of the row tile {r_tile}; "
            f"pad the flattened N*T axis (ops.graph_sconv does this)")
    return (R // r_tile, Cout // co_tile), r_tile, co_tile


@functools.partial(jax.jit, static_argnames=("interpret",))
def graph_sconv_pallas(
    x: jnp.ndarray,      # (R, Vp, Cin)
    g: jnp.ndarray,      # (K, Vp, Vp)
    w: jnp.ndarray,      # (K, Cin, Cout)
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Fused Σ_k (G_k·x)·W_k in one VMEM pass: (R, Vp, Cin) -> (R, Vp, Cout).

    The graph matmul and the 1×1 conv share each x tile, so the (G·x)
    intermediate never leaves VMEM; callers pad R/V (ops.graph_sconv) so
    the (R tiles, Cout tiles) grid divides exactly."""
    R, Vp, Cin = x.shape
    K, _, Cout = w.shape
    grid, r_tile, co_tile = _grid(R, Vp, Cin, Cout)

    in_spec = pl.BlockSpec((r_tile, Vp, Cin), lambda r, c: (r, 0, 0))
    g_spec = pl.BlockSpec((K, Vp, Vp), lambda r, c: (0, 0, 0))
    w_spec = pl.BlockSpec((K, Cin, co_tile), lambda r, c: (0, 0, c))
    out_spec = pl.BlockSpec((r_tile, Vp, co_tile), lambda r, c: (r, 0, c))

    return pl.pallas_call(
        functools.partial(_kernel, kv=K),
        grid=grid,
        in_specs=[in_spec, g_spec, w_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((R, Vp, Cout), x.dtype),
        interpret=interpret,
        name="graph_sconv",
    )(x, g, w)


def _csr_kernel(x_ref, idx_ref, val_ref, w_ref, out_ref, *, kv: int,
                deg: int):
    x = x_ref[...]
    vp = x.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (vp, vp), 1)

    def ell_graph(k):
        # ELL -> (Vp, Vp) graph: one one-hot row scatter per ELL slot, so
        # the neighbour gather runs on the MXU inside the graph matmul
        ids = idx_ref[k]                            # (Vp, D)
        vals = val_ref[k].astype(jnp.float32)
        g = jnp.zeros((vp, vp), jnp.float32)
        for d in range(deg):                        # static ELL-slot loop
            g = g + jnp.where(ids[:, d:d + 1] == col, vals[:, d:d + 1], 0.0)
        return g.astype(x.dtype)

    acc = _graph_conv(x, ell_graph, w_ref, kv)
    out_ref[...] = acc.reshape(out_ref.shape).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def graph_sconv_csr_pallas(
    x: jnp.ndarray,        # (R, Vp, Cin)
    idx: jnp.ndarray,      # (K, Vp, D) int32 ELL neighbor indices
    val: jnp.ndarray,      # (K, Vp, D) f32 edge weights, zero-padded
    w: jnp.ndarray,        # (K, Cin, Cout)
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Sparse Σ_k (G_k·x)·W_k over an ELL-packed graph.

    The graph crosses HBM as its ELL pack (D = max row degree, from
    ops.pack_csr_ell) and is expanded in VMEM into a one-hot (Vp, Vp)
    matrix — D compare-selects per subset — so the neighbour gather is an
    MXU matmul; the TPU has no vector gather across sublanes.  Grid and
    tiling mirror :func:`graph_sconv_pallas`; idx/val ride whole in VMEM.
    """
    R, Vp, Cin = x.shape
    K, _, Cout = w.shape
    D = idx.shape[-1]
    grid, r_tile, co_tile = _grid(R, Vp, Cin, Cout)

    in_spec = pl.BlockSpec((r_tile, Vp, Cin), lambda r, c: (r, 0, 0))
    idx_spec = pl.BlockSpec((K, Vp, D), lambda r, c: (0, 0, 0))
    val_spec = pl.BlockSpec((K, Vp, D), lambda r, c: (0, 0, 0))
    w_spec = pl.BlockSpec((K, Cin, co_tile), lambda r, c: (0, 0, c))
    out_spec = pl.BlockSpec((r_tile, Vp, co_tile), lambda r, c: (r, 0, c))

    return pl.pallas_call(
        functools.partial(_csr_kernel, kv=K, deg=D),
        grid=grid,
        in_specs=[in_spec, idx_spec, val_spec, w_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((R, Vp, Cout), x.dtype),
        interpret=interpret,
        name="graph_sconv_csr",
    )(x, idx, val, w)
