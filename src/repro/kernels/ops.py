"""Jit'd public wrappers around the Pallas kernels.

These handle layout adaptation (padding, filter-group permutation, kept-tap
packing) so callers use natural shapes; the kernels see hardware-aligned
tiles.  Every wrapper takes ``interpret`` explicitly: the execution plan
derives it once from the platform (:func:`interpret_mode`) — the Pallas
interpreter on the CPU, compiled Mosaic kernels on a TPU.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.cavity_tconv import (B_TILE, cavity_tconv_pallas,
                                        cavity_tconv_step_pallas)
from repro.kernels.graph_sconv import (CO_TILE, graph_sconv_csr_pallas,
                                       graph_sconv_pallas, row_tile)
from repro.kernels.rfc_pack import (ROW_TILE, rfc_decode_pallas,
                                    rfc_encode_pallas)
from repro.kernels.window_sim import windowed_similarity_pallas


def interpret_mode(platform: Optional[str] = None) -> bool:
    """Whether Pallas kernels run in the interpreter, from the platform
    (default: JAX's default backend).  The CPU has no Mosaic compiler, so
    it interprets; a TPU compiles.  Any other platform has no Pallas path
    here and raises rather than silently interpreting."""
    platform = platform or jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise ValueError(f"no Pallas kernel path for platform {platform!r} "
                     f"(expected 'cpu' or 'tpu')")


_KERNEL_OP = re.compile(r'op_name="[^"]*/(\w+)/pallas_call"')


def kernel_counts(hlo_text: str) -> Dict[str, int]:
    """Compiled Pallas kernels per kernel name (``graph_sconv``,
    ``cavity_tconv``, ``rfc_encode`` …) in a compiled program's HLO text —
    each ``tpu_custom_call`` is one Mosaic kernel; interpreted kernels
    leave none."""
    counts: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = _KERNEL_OP.search(line)
            name = m.group(1) if m else "?"
            counts[name] = counts.get(name, 0) + 1
    return counts


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# RFC
# ---------------------------------------------------------------------------

def _pad_rfc(x: jnp.ndarray, bank: int) -> jnp.ndarray:
    """(..., C) -> (rows, C') with C' a bank multiple and rows whole tiles."""
    flat = _pad_to(x.reshape(-1, x.shape[-1]), 1, bank)
    return _pad_to(flat, 0, ROW_TILE if flat.shape[0] > ROW_TILE else 8)


def rfc_encode(x: jnp.ndarray, bank: int = 16, *, interpret: bool):
    """Encode activations of any (..., C) shape; returns (values, hot)."""
    shape = x.shape
    rows = int(np.prod(shape[:-1]))
    vals, hot = rfc_encode_pallas(_pad_rfc(x, bank), bank=bank,
                                  interpret=interpret)
    vals = vals[:rows, : shape[-1]].reshape(shape)
    hot = hot[:rows, : shape[-1]].reshape(shape)
    return vals, hot


def rfc_decode(values: jnp.ndarray, hot: jnp.ndarray, bank: int = 16, *,
               interpret: bool) -> jnp.ndarray:
    """Inverse of :func:`rfc_encode`: scatter each bank's front-packed
    values back to their hot positions.  Any (..., C) shape; lossless on
    post-ReLU activations (the roundtrip contract in test_rfc_format)."""
    shape = values.shape
    out = rfc_decode_pallas(_pad_rfc(values, bank), _pad_rfc(hot, bank),
                            bank=bank, interpret=interpret)
    return out[: int(np.prod(shape[:-1])), : shape[-1]].reshape(shape)


# ---------------------------------------------------------------------------
# Cavity temporal conv
# ---------------------------------------------------------------------------

def pack_cavity_weights(
    w: np.ndarray,           # (F, C, K) dense weights of the *kept* filters
    tap_mask: np.ndarray,    # (F, K) bool — cavity pattern tiled to F
    loop: int = 8,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Group filters by recurring pattern row (f % loop) and pack kept taps.

    Returns (wp (L, n_keep, C, Fg), taps (L, n_keep) int32, perm (F,) int32)
    where out_dense[..., perm] reassembles the natural filter order from the
    (L, Fg) kernel output.  Filters are zero-padded to a multiple of loop.
    """
    F, C, K = w.shape
    Fp = ((F + loop - 1) // loop) * loop
    if Fp != F:
        w = np.concatenate([w, np.zeros((Fp - F, C, K), w.dtype)], 0)
        tap_mask = np.concatenate(
            [tap_mask, np.tile(tap_mask[:1], (Fp - F, 1))], 0
        )
    Fg = Fp // loop
    n_keep = int(tap_mask[:loop].sum(axis=1).max())
    wp = np.zeros((loop, n_keep, C, Fg), w.dtype)
    taps = np.zeros((loop, n_keep), np.int32)
    for g in range(loop):
        kept = np.flatnonzero(tap_mask[g])
        taps[g, : len(kept)] = kept
        for j, k in enumerate(kept):
            # filters g, g+loop, g+2*loop, ... share this tap set
            wp[g, j] = w[g::loop, :, k].T          # (C, Fg)
    # kernel output flattens (L, Fg): slot g*Fg+i holds filter g + loop*i
    inv = np.empty(Fp, np.int32)
    order = np.arange(Fp).reshape(Fg, loop).T.reshape(-1)  # (L, Fg) flat -> f
    inv[order] = np.arange(Fp)
    return wp, taps, inv[:Fp]


def cavity_tconv(
    x: jnp.ndarray,          # (B, T, C)
    wp: jnp.ndarray,
    taps: jnp.ndarray,
    inv_perm: np.ndarray,
    num_filters: int,
    kernel_size: int = 9,
    stride: int = 1,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Cavity-pruned temporal conv, 'same' padding.  Returns (B, T_out, F).

    T_out follows conv semantics, ``(T + 2·pad − K)//stride + 1``.  The
    kernel computes a sublane multiple of outputs, so the right pad is
    extended with zeros until every tap window of that many outputs is in
    bounds; the surplus outputs are sliced off here (for a stride that
    doesn't divide the window count this also keeps reference and pallas —
    and streaming parity with them — on the same trailing output)."""
    pad = kernel_size // 2
    B, T, _ = x.shape
    t_out = (T + 2 * pad - kernel_size) // stride + 1
    t_blk = -(-t_out // 8) * 8
    # the last tap window of t_blk outputs ends at K-1 + t_blk·stride
    t_pad = kernel_size - 1 + t_blk * stride
    xp = jnp.pad(x, ((0, 0), (pad, t_pad - T - pad), (0, 0)))
    xp = _pad_to(xp, 0, B_TILE if B > B_TILE else 8)
    out = cavity_tconv_pallas(
        xp, wp, taps, t_out=t_blk, stride=stride, interpret=interpret,
    )                                                 # (L, Bp, t_blk, Fg)
    L, _, _, Fg = out.shape
    flat = jnp.transpose(out, (1, 2, 0, 3))[:B, :t_out]
    flat = flat.reshape(B, t_out, L * Fg)
    flat = jnp.take(flat, jnp.asarray(inv_perm), axis=-1)
    return flat[..., :num_filters]


def cavity_tconv_step(
    x: jnp.ndarray,          # (B, K, C) chronological window (oldest first)
    wp: jnp.ndarray,
    taps: jnp.ndarray,
    inv_perm: np.ndarray,
    num_filters: int,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Single-timestep cavity tconv over a full window.  Returns (B, F).

    The streaming engine's per-frame path: no padding (the window already
    holds K frames — ring-buffer zeros stand in for the clip's 'same'
    padding) and no stride (emission gating lives in the engine).  Same
    packed weights / tap sets / filter permutation as :func:`cavity_tconv`."""
    B = x.shape[0]
    xp = _pad_to(x, 0, B_TILE if B > B_TILE else 8)
    out = cavity_tconv_step_pallas(xp, wp, taps, interpret=interpret)
    L, _, Fg = out.shape                              # (L, Bp, Fg)
    flat = jnp.transpose(out, (1, 0, 2))[:B].reshape(B, L * Fg)
    flat = jnp.take(flat, jnp.asarray(inv_perm), axis=-1)
    return flat[:, :num_filters]


# ---------------------------------------------------------------------------
# Windowed similarity (streaming C_k)
# ---------------------------------------------------------------------------

def windowed_similarity(
    ring_th: jnp.ndarray,    # (S, K, V, Ce) per-slot θ-embedding ring
    ring_ph: jnp.ndarray,    # (S, K, V, Ce) per-slot φ-embedding ring
    valid_joints: int = 0,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Streaming windowed C_k from the embedding rings.  Returns (S, V, V).

    One fused pass per slab slot: ring window sum → Θ·Φᵀ/√Ce → masked
    row softmax (input-joint columns ≥ ``valid_joints`` excluded; 0 = all
    of V live).  The joint axis is sublane-padded here and the padded
    columns are always masked, so the sliced result equals the reference
    ``adaptive.windowed_ck(ring.sum(1), ...)`` twin ≤1e-3."""
    S, K, V, Ce = ring_th.shape
    th = _pad_to(ring_th, 2, 8)
    ph = _pad_to(ring_ph, 2, 8)
    valid = valid_joints if 0 < valid_joints < V else V
    out = windowed_similarity_pallas(th, ph, valid=int(valid),
                                     interpret=interpret)
    return out[:, :V, :V]


# ---------------------------------------------------------------------------
# Fused graph + spatial conv
# ---------------------------------------------------------------------------

def _pad_rows(x: jnp.ndarray, cout: int):
    """Flatten (N, T, V, Cin) to kernel rows: joints sublane-aligned, N*T
    padded to whole row tiles of the kernel's VMEM-budgeted row tile.
    Returns (xr, R, Vp)."""
    N, T, V, Cin = x.shape
    Vp = ((V + 7) // 8) * 8                          # sublane-align joints
    R = N * T
    rt = row_tile(Vp, Cin, CO_TILE if cout % CO_TILE == 0 else cout)
    xr = _pad_to(x.reshape(R, V, Cin), 1, 8)
    # row axis: whole tiles when more than one, else one 8-aligned tile
    xr = _pad_to(xr, 0, rt if R > rt else 8)
    return xr, R, Vp


def graph_sconv(
    x: jnp.ndarray,          # (N, T, V, Cin) — kept channels already gathered
    g: jnp.ndarray,          # (K, V, V) or prepadded (K, Vp, Vp) from a plan
    w: jnp.ndarray,          # (K, Cin, Cout)
    *,
    interpret: bool,
    topology: str = "",
) -> jnp.ndarray:
    """Fused Σ_k (G_k·x)·W_k.  Returns (N, T, V, Cout).

    Both blocked axes are padded here: joints to the 8-sublane multiple and
    the flattened N*T row axis to a whole number of row tiles — an odd
    batch×time product must never reach the kernel as one giant tile (or a
    non-dividing grid).  ``g`` may arrive already padded to (K, Vp, Vp) from
    an ExecutionPlan, or wider still when the plan is padded to a slab Vmax
    and ``x`` runs at the topology's own joint count (the wider graph is
    zero outside its valid joints, so slicing to Vp is exact); raw (K, V, V)
    graphs are padded on the fly.  ``topology`` only decorates the
    mismatched-shape errors so mixed-slab bugs name the offending skeleton.
    """
    N, T, V, Cin = x.shape
    xr, R, Vp = _pad_rows(x, w.shape[-1])
    note = f" for topology {topology!r}" if topology else ""
    if g.shape[0] != w.shape[0]:
        raise ValueError(
            f"graph has K={g.shape[0]} subsets but w has K={w.shape[0]}"
            f"{note}; the plan packed weights against a different topology")
    if g.shape[-1] == V:
        gp = jnp.zeros((g.shape[0], Vp, Vp), g.dtype).at[:, :V, :V].set(g)
    elif g.shape[-1] == Vp:
        gp = g
    elif g.shape[-1] > Vp:
        gp = g[:, :Vp, :Vp]              # plan padded to a wider slab Vmax
    else:
        raise ValueError(
            f"graph{note} padded to {g.shape[-1]}, expected >= {V} "
            f"(x runs {V} joints, sublane-aligned to {Vp})")
    out = graph_sconv_pallas(xr, gp, w.astype(x.dtype), interpret=interpret)
    return out[:R, :V, :].reshape(N, T, V, -1)


def pack_csr_ell(
    indptr: np.ndarray,      # (K, V+1) int32
    indices: np.ndarray,     # (K, E) int32
    values: np.ndarray,      # (K, E) f32, zero-padded
    vp: int,                 # padded joint count (multiple of 8)
) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side CSR → ELL repack for :func:`graph_sconv_csr_pallas`.

    Each output row gets its neighbor list padded to the max row degree D
    (idx 0 / val 0 — a harmless gather of joint 0 scaled by zero), and rows
    are padded to ``vp``.  Returns (idx (K, vp, D) int32, val (K, vp, D)
    f32)."""
    indptr = np.asarray(indptr)
    indices = np.asarray(indices)
    values = np.asarray(values)
    K, V1 = indptr.shape
    V = V1 - 1
    deg = int(max(1, (indptr[:, 1:] - indptr[:, :-1]).max()))
    idx = np.zeros((K, vp, deg), np.int32)
    val = np.zeros((K, vp, deg), np.float32)
    for k in range(K):
        for r in range(V):
            lo, hi = int(indptr[k, r]), int(indptr[k, r + 1])
            idx[k, r, : hi - lo] = indices[k, lo:hi]
            val[k, r, : hi - lo] = values[k, lo:hi]
    return idx, val


def graph_sconv_csr(
    x: jnp.ndarray,          # (N, T, V, Cin) — kept channels already gathered
    idx: jnp.ndarray,        # (K, Vp', D) ELL indices, Vp' >= roundup8(V)
    val: jnp.ndarray,        # (K, Vp', D) ELL values
    w: jnp.ndarray,          # (K, Cin, Cout)
    *,
    interpret: bool,
    topology: str = "",
) -> jnp.ndarray:
    """Sparse Σ_k (G_k·x)·W_k over an ELL-packed graph.  Returns
    (N, T, V, Cout).

    Row/joint padding mirrors :func:`graph_sconv`; an ELL pack wider than
    x's padded joint count (a plan padded to slab Vmax) is sliced down —
    exact because padded rows are all-zero and indices only reference valid
    joints."""
    N, T, V, Cin = x.shape
    xr, R, Vp = _pad_rows(x, w.shape[-1])
    note = f" for topology {topology!r}" if topology else ""
    if idx.shape[0] != w.shape[0]:
        raise ValueError(
            f"ELL graph has K={idx.shape[0]} subsets but w has "
            f"K={w.shape[0]}{note}")
    if idx.shape[1] < Vp:
        raise ValueError(
            f"ELL graph{note} packed to {idx.shape[1]} joints, expected "
            f">= {Vp} (x runs {V} joints, sublane-aligned to {Vp})")
    out = graph_sconv_csr_pallas(
        xr, idx[:, :Vp], val[:, :Vp].astype(x.dtype), w.astype(x.dtype),
        interpret=interpret)
    return out[:R, :V, :].reshape(N, T, V, -1)
