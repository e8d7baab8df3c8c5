"""Plain 2s-AGCN as published, with its adaptive graph C_k, in jax.numpy:
the yardstick that decides ``correct`` for the ``agcn2s-ck``
configuration.  Like :mod:`benchlib.reference` it imports nothing of the
program and takes nothing the program made; it reuses that module's
skeleton graph, batch norm, numerics and head by import.

Model (Shi et al., arXiv:1805.07694; code lshiwjx/2s-AGCN, model/agcn.py,
``unit_gcn`` with ``coff_embedding=4``, ``num_subset=3``), per stream, on
(N, T, V, C) rows: as :func:`benchlib.reference.features`, except that
each block's graph sum is

  theta_k(x), phi_k(x) = 1x1 convolutions C_in -> Ce = C_out / 4, with bias
  C_k[v, w]  = softmax over v of sum_{c<Ce, t<T} theta_k[c,t,v] phi_k[c,t,w]
               / (Ce * T)
  s          = sum_k W_k(x ._V (A_k + B_k + C_k))   (out[w] = sum_v x[v] G[v,w])

with T the block's own time length: the similarity pools over the whole
clip.  Here graphs are ``G[k, i, j]`` (joint j weighted into joint i, as in
:func:`benchlib.reference.ntu_subsets`), so C_k enters transposed: entry
[i, j] is the softmax over j of phi_k[i] . theta_k[j].

Departures from ``agcn.py``:

- rows are bodies: the two persons of a clip are two rows, each with its
  own logits, not averaged before the fc (as in ``woc-clip``);
- weights are random from the seed (:func:`make_stream_params`), not a
  trained checkpoint: theta/phi He-normal, their biases N(0, 1) (the
  code starts them at 0);
- as in :mod:`benchlib.reference`: the stem batch norm is over V*C per
  body (the code's is over M*V*C), block 0 has the 1x1 shortcut its
  width change asks for (the code's ``l1`` has none), the learned graph
  B_k is random (the code's starts at 1e-6), and conv_d has no bias (a
  per-channel constant that the next batch norm, in batch mode, removes
  exactly).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import layout, reference
from benchlib.reference import _BN, EXACT, Numerics, _head, _mm, bone

CK_FOLD = 0xC4          # folded into the seed's key for theta/phi


def make_ck_params(model: dict, key) -> List[Dict]:
    """Per block, the published theta_k/phi_k of one stream, in the layout
    the program's plan builder reads: ``theta``/``phi`` (K, C_in, Ce),
    He-normal, and ``theta_b``/``phi_b`` (K, Ce), N(0, 1).  At this scale
    no C_k is near uniform: one softmaxed over the wrong axis moves the
    logits by far more than the cell's limit (``bench/tests``)."""
    K = int(model["gcn_kv"])
    out = []
    for b, blk in enumerate(layout.blocks(model)):
        ce = blk.cout // 4
        k = jax.random.split(jax.random.fold_in(key, b), 4)
        he = np.sqrt(2.0 / blk.cin)
        out.append({
            "theta": he * jax.random.normal(k[0], (K, blk.cin, ce)),
            "phi": he * jax.random.normal(k[1], (K, blk.cin, ce)),
            "theta_b": jax.random.normal(k[2], (K, ce)),
            "phi_b": jax.random.normal(k[3], (K, ce))})
    return out


def make_stream_params(model: dict, seed: int) -> Tuple[Dict, Dict]:
    """(joint, bone) weights from ``seed``: exactly
    :func:`benchlib.reference.make_stream_params`' (so a seed gives the
    same base weights as ``agcn2s-woc``), each block joined by its
    theta/phi, drawn after them from the seed's key folded with
    :data:`CK_FOLD`."""
    base = reference.make_stream_params(model, seed)

    def ck(key):
        kj, kb = jax.random.split(jax.random.fold_in(key, CK_FOLD))
        return make_ck_params(model, kj), make_ck_params(model, kb)

    extra = jax.jit(ck)(jax.random.PRNGKey(seed % (2 ** 32)))
    return tuple({**p, "blocks": [{**b, **e} for b, e in
                                  zip(p["blocks"], ex)]}
                 for p, ex in zip(base, extra))


def ck_graph(x, pb, num: Numerics, axis: int = -1):
    """C_k of every row and subset, transposed into ``G[i, j]`` form:
    (N, T, V, C) -> (N, K, V, V).  ``axis`` is the softmax axis: -1 (the
    input joint j, as published); -2 only to build a wrong program."""
    T = x.shape[1]
    c = x.shape[-1]
    th = _mm("ntvc,kce->nktve", x, pb["theta"][:, :c], num=num,
             kernel=True) + pb["theta_b"][None, :, None, None, :]
    ph = _mm("ntvc,kce->nktve", x, pb["phi"][:, :c], num=num,
             kernel=True) + pb["phi_b"][None, :, None, None, :]
    ce = th.shape[-1]
    logits = _mm("nktie,nktje->nkij", ph, th, num=num, kernel=True) \
        / jnp.asarray(ce * T, num.dtype)
    return jax.nn.softmax(logits, axis=axis).astype(num.dtype)


def features(params, x, model: dict, quant: bool, bn: _BN,
             num: Numerics = EXACT, ck_axis: int = -1):
    """Last-block outputs of one stream, averaged over joints:
    (N, T_last, C_last).  ``ck_axis`` as in :func:`ck_graph`."""
    blocks = layout.blocks(model)
    skip = int(model.get("input_skip", 1))
    K = int(model["gcn_tkernel"])
    pad = K // 2
    dtype = num.dtype
    A = jnp.asarray(reference.ntu_subsets(), dtype)
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    h = x.astype(dtype)[:, ::skip]
    N, T, V, C = h.shape
    h = bn("stem", h.reshape(N, T, V * C), p["data_bn"]).reshape(N, T, V, C)
    for b, (blk, pb) in enumerate(zip(blocks, p["blocks"])):
        Wk, tw = pb["Wk"], pb["tconv_w"]
        if quant:
            Wk, tw = reference.q88(Wk), reference.q88(tw)
        xin = h[..., :blk.n_in]
        G = (A + pb["Bk"])[None] + ck_graph(xin, pb, num, ck_axis)
        agg = _mm("ntvc,nkwv->ntkwc", xin, G, num=num, kernel=True)
        s = _mm("ntkwc,kco->ntwo", agg, Wk[:, :blk.n_in], num=num,
                kernel=True)
        s = bn(f"{b}/s", s, pb["bn_s"])
        down = (bn(f"{b}/down", _mm("ntvc,co->ntvo", h, pb["down_w"],
                                    num=num), pb["bn_down"])
                if "down_w" in pb else h)
        s = jax.nn.relu(s + down)
        # temporal conv at the kept filters and taps, 'same' zero padding
        w = tw[:blk.n_filters] * jnp.asarray(blk.taps, dtype)[:, None, :]
        sp = jnp.pad(s, ((0, 0), (pad, pad), (0, 0), (0, 0)))
        t_out = (s.shape[1] - 1) // blk.stride + 1
        span = blk.stride * (t_out - 1) + 1
        t = sum(_mm("ntvc,fc->ntvf", sp[:, k:k + span:blk.stride], w[..., k],
                    num=num, kernel=True) for k in range(K))
        t = t + pb["tconv_b"][:blk.n_filters]
        t = jnp.pad(t, ((0, 0), (0, 0), (0, 0), (0, blk.cout - blk.n_filters)))
        t = bn(f"{b}/t", t, pb["bn_t"])
        hs = h[:, ::blk.stride]
        res = (bn(f"{b}/short", _mm("ntvc,co->ntvo", hs, pb["short_w"],
                                    num=num), pb["bn_short"])
               if "short_w" in pb else hs)
        h = jax.nn.relu(t + res)
    return h.mean(axis=2)


def clip_logits(params2, x, model: dict, quant: bool,
                num: Numerics = EXACT, ck_axis: int = -1):
    """Two-stream clip logits with batch statistics over ``x``'s rows:
    (N, classes) float32."""
    out = []
    for params, xs in zip(params2, (x, bone(x))):
        f = features(params, xs, model, quant, _BN(), num, ck_axis)
        out.append(_head(params, f.mean(axis=1), num))
    return (0.5 * (out[0] + out[1])).astype(jnp.float32)
