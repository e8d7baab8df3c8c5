"""The data-dependent graph C_k of 2s-AGCN, in its two forms
(``ModelConfig.ck_form``).

**clip** — the published C_k (Shi et al., arXiv:1805.07694, the
reference code's ``unit_gcn``), for the clip path only: a live stream has
no whole clip, so the session slab and ``GcnService`` refuse this form.
Per subset k, with
θ_k/φ_k 1×1 convolutions C_in → Ce = C_out/4 with biases:

    C_k[v, w] = softmax_v( Σ_{c<Ce, t<T} θ_k[c,t,v]·φ_k[c,t,w] / (Ce·T) )

pooled over the *whole clip*, and the block aggregates
``out[w] = Σ_v x[v]·(A_k + B_k + C_k)[v, w]``.  This repo's graphs are
``G[w, v]`` (joint v weighted into joint w), so :func:`clip_ck` returns
the transpose, one graph per sample and subset, softmax over its last
(input-joint) axis; the engine adds it to ``A_k + B_k`` and the Pallas
backend computes it with ``ops.clip_similarity`` and aggregates with the
per-sample ``ops.graph_sconv_rows``.

**window** — the adaptive-streaming reformulation, for live streams.
The paper drops C_k at deployment (Table I: 88.9% w/o C_k) because the
published form pools over the whole clip — a live stream has no clip to
pool over.  This form pools over a **trailing window** instead, so the
same graph is computable per frame from the streaming engine's existing
ring buffers (Continual ST-GCN, PAPERS.md 2203.11009, applies the same
per-frame continual rewrite to these blocks), with one θ/φ per block
shared by the subsets, Ce = C_in/4 and no biases:

    Θ(t) = Σ_{u=t−K+1..t} θ(x_u)          (zeros before the stream starts)
    Φ(t) = Σ_{u=t−K+1..t} φ(x_u)
    C(t) = softmax(Θ(t)·Φ(t)ᵀ / √Ce)      (per output joint, over inputs)

with K = the block's temporal kernel size — the window the block's tconv
ring already spans, so the streaming state only adds two (S, K, V, Ce)
embedding rings per C_k block.  Both execution modes use the *same*
definition: clip mode evaluates the recurrence at every frame index
(:func:`clip_windowed_ck`), streaming evaluates it incrementally from the
embedding rings (:func:`windowed_ck` on the ring sums, or the fused pallas
kernel ``repro.kernels.ops.windowed_similarity``), which is why post-drain
streaming logits match clip logits ≤1e-3 with C_k **on**
(tests/test_streaming.py) — the invariant the full-clip eq. (1) could
never satisfy.

In both forms the softmax runs over the input-joint axis (the last axis
of a ``G[out, in]`` graph), max-subtracted, and slab-padded joints are
masked out of the softmax *columns* so a padded plan's graph rows never
pool from dead joints.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["windowed_ck", "clip_windowed_ck", "clip_ck"]


def windowed_ck(win_th: jnp.ndarray, win_ph: jnp.ndarray,
                valid_joints: int = 0) -> jnp.ndarray:
    """C = softmax(Θ·Φᵀ/√Ce) from pooled window embeddings.

    ``win_th`` / ``win_ph`` are (..., V, Ce) trailing-window embedding
    sums (the streaming engine's ``ck_th``/``ck_ph`` rings summed over
    their K axis; clip mode builds them with
    :func:`_trailing_window_sum`).  ``valid_joints`` > 0 masks the
    input-joint *columns* ≥ it to −inf before the softmax — a slab-padded
    plan's zero rows would otherwise flatten every row's softmax toward
    the padded joints.  Returns the (..., V, V) normalized graph added to
    ``A_k + B_k`` per subset."""
    ce = win_th.shape[-1]
    logits = jnp.einsum("...ve,...we->...vw", win_th, win_ph) / jnp.sqrt(
        jnp.asarray(ce, win_th.dtype))
    V = logits.shape[-1]
    if 0 < valid_joints < V:
        dead = jnp.arange(V) >= valid_joints            # (V,) input joints
        logits = jnp.where(dead, jnp.asarray(-1e30, logits.dtype), logits)
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    return (e / jnp.sum(e, axis=-1, keepdims=True)).astype(win_th.dtype)


def _trailing_window_sum(e: jnp.ndarray, k: int) -> jnp.ndarray:
    """Per-frame trailing-K window sums of (N, T, V, Ce) embeddings:
    ``out[:, t] = Σ_{d=0..K−1} e[:, t−d]`` with zeros before frame 0 —
    exactly the streaming embedding ring's content at block clock t
    (fresh rings are zero-initialized), built as K−1 shifted adds so clip
    mode never materializes a (T, K) window tensor."""
    out = e
    T = e.shape[1]
    for d in range(1, k):
        out = out + jnp.pad(e, ((0, 0), (d, 0), (0, 0), (0, 0)))[:, :T]
    return out


def clip_windowed_ck(x: jnp.ndarray, w_theta: jnp.ndarray,
                     w_phi: jnp.ndarray, k: int,
                     valid_joints: int = 0) -> jnp.ndarray:
    """Per-frame windowed C_k for clip mode: (N, T, V, C) -> (N, T, V, V).

    Evaluates the module recurrence at every frame index — embedding
    projections θ/φ per frame, trailing-K window sums (zeros before the
    clip starts), then :func:`windowed_ck` — so a clip-mode forward with
    ``use_ck`` is frame-for-frame the reference twin of the streaming
    embedding rings (the parity contract in tests/test_streaming.py).
    ``x`` is the block input with kept channels already gathered;
    ``w_theta``/``w_phi`` are the plan's (C_kept, Ce) projections."""
    th = jnp.einsum("ntvc,ce->ntve", x, w_theta.astype(x.dtype))
    ph = jnp.einsum("ntvc,ce->ntve", x, w_phi.astype(x.dtype))
    return windowed_ck(_trailing_window_sum(th, k),
                       _trailing_window_sum(ph, k),
                       valid_joints=valid_joints)


def clip_ck(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, kv: int,
            valid_joints: int = 0) -> jnp.ndarray:
    """The published whole-clip C_k: (N, T, V, C) -> (N, K, V, V).

    ``w`` (C, 2·K·Ce) holds θ_0 … θ_{K-1} then φ_0 … φ_{K-1} and ``b``
    their biases (the plan's ``ck_w`` / ``ck_b``).  Returns
    ``out[n, k, i, j] = softmax_j(Σ_{c,t} φ_k[n,t,i,c]·θ_k[n,t,j,c] /
    (Ce·T))`` — the published ``C_k[j, i]`` in this repo's orientation.
    Input-joint columns ≥ ``valid_joints`` (0 = all of V) are masked, as
    in :func:`windowed_ck`.  The Pallas twin is
    ``repro.kernels.ops.clip_similarity``."""
    N, T, V, _ = x.shape
    e = jnp.einsum("ntvc,cf->ntvf", x, w.astype(x.dtype)) + b.astype(x.dtype)
    ce = e.shape[-1] // (2 * kv)
    e = e.reshape(N, T, V, 2, kv, ce)
    logits = jnp.einsum("ntike,ntjke->nkij", e[..., 1, :, :],
                        e[..., 0, :, :]) / jnp.asarray(ce * T, x.dtype)
    if 0 < valid_joints < V:
        dead = jnp.arange(V) >= valid_joints            # (V,) input joints
        logits = jnp.where(dead, jnp.asarray(-1e30, logits.dtype), logits)
    return jax.nn.softmax(logits, axis=-1).astype(x.dtype)
