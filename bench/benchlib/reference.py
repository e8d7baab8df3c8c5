"""Plain 2s-AGCN reference in jax.numpy: the yardstick that decides
``correct``.  It imports nothing of the program and takes nothing the
program made: the weights come from :func:`make_params` (the benchmark's own,
from the seed), the skeleton graph, the cavity taps, the kept channels and
the Q8.8 rounding are built here from the configuration file.

Model (Shi et al., arXiv:1805.07694; pruned as in arXiv:2108.01020), per
stream, on (N, T, V, C) rows (one body per row):

  x        -> keep raw frames 0, s, 2s, ... (input skip s)
  stem     -> batch norm over the flattened (V*C) joint-major features
  block b  -> s = relu(bn_s(sum_k (G_k x[..., :n_in]) W_k[:n_in]) + down(x))
              t = bn_t(temporal conv of s over T at the kept filters and
                  kept taps, 'same' zero padding, stride; pruned filters 0)
              x = relu(t + shortcut(x))
  head     -> mean over T and V, then fc.

G_k = A_k + B_k with A_k the NTU-25 spatial-configuration subsets (self /
centripetal / centrifugal, each column normalized by in-degree), B_k the
learned graph.  down(x) = bn(x W_down) when cin != cout, else x; the
shortcut is bn(x[::stride] W_short) when cin != cout or stride != 1, else
x[::stride].  Q8.8 configurations round W_k and the temporal weights to
1/256 (clipped to 16 bits).  Batch norm is (x - mean) * rsqrt(var + 1e-5)
* scale + bias, with the batch's statistics over every axis but the
channel ("batch" mode) or with statistics recorded from a calibration batch
("frozen" mode, the streaming model).

The joint and bone streams are averaged: bone = x - x[parent].

Streaming logits after ``n`` raw frames are the fc of the mean, over the
last-block outputs completed by then (:func:`layout.emitted`), of their
joint means; a completed output's receptive field lies inside the frames
already seen, so one clip forward over a session's frames gives every
output that any of its reads needs.

Numerics (:class:`Numerics`) follow what a configuration states: its
storage dtype, and the dtype its matmul operands are rounded to (the TPU's
default precision rounds float32 operands to bfloat16 and sums in
float32); :func:`control` is the same one storage step lower.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import layout

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-5

# NTU RGB+D 25-joint skeleton: (joint, parent), 1-indexed; joint 21 (spine)
# is the centre and its own parent.
NTU_BONES = (
    (1, 2), (2, 21), (3, 21), (4, 3), (5, 21), (6, 5), (7, 6), (8, 7),
    (9, 21), (10, 9), (11, 10), (12, 11), (13, 1), (14, 13), (15, 14),
    (16, 15), (17, 1), (18, 17), (19, 18), (20, 19), (22, 23), (23, 8),
    (24, 25), (25, 12),
)
NTU_CENTRE = 21


def ntu_parents() -> np.ndarray:
    """(25,) 0-indexed parent of every joint (the centre parents itself)."""
    par = np.arange(25)
    for j, p in NTU_BONES:
        par[j - 1] = p - 1
    return par


def ntu_subsets() -> np.ndarray:
    """(3, 25, 25) A_k: entry [k, i, j] weights joint j's feature into
    joint i; neighbours within one hop (self included) split by whether j
    is as far from, nearer to or farther from the centre than i, each
    entry divided by j's neighbour count."""
    V = 25
    nbr = np.eye(V, dtype=bool)
    for j, p in NTU_BONES:
        nbr[j - 1, p - 1] = nbr[p - 1, j - 1] = True
    # hop distance to the centre by breadth-first search
    depth = np.full(V, -1)
    depth[NTU_CENTRE - 1] = 0
    frontier = [NTU_CENTRE - 1]
    while frontier:
        nxt = []
        for u in frontier:
            for w in np.flatnonzero(nbr[u]):
                if depth[w] < 0:
                    depth[w] = depth[u] + 1
                    nxt.append(w)
        frontier = nxt
    deg = nbr.sum(axis=0)
    A = np.zeros((3, V, V), np.float32)
    for i in range(V):
        for j in np.flatnonzero(nbr[i]):
            k = 0 if depth[j] == depth[i] else (1 if depth[j] < depth[i] else 2)
            A[k, i, j] = 1.0 / deg[j]
    return A


def q88(w):
    """Q8.8 fixed point: round to 1/256, clip to the signed 16-bit range."""
    return jnp.clip(jnp.round(w * 256.0), -32768, 32767) / 256.0


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------

def make_params(model: dict, key) -> Dict:
    """One stream's weights, in the layout the program's plan builder
    reads: He-normal convolutions, random batch-norm affine terms, a small
    random learned graph B_k and random temporal biases, so every term of
    the model moves the logits."""
    ch = list(model["gcn_channels"])
    strides = list(model["gcn_strides"])
    V, K, TK = (int(model["gcn_joints"]), int(model["gcn_kv"]),
                int(model["gcn_tkernel"]))
    keys = iter(jax.random.split(key, 16 * len(ch) + 4))

    def he(shape, fan_in):
        return jax.random.normal(next(keys), shape) * np.sqrt(2.0 / fan_in)

    def bn(c):
        return {"scale": jax.random.uniform(next(keys), (c,), minval=0.7,
                                            maxval=1.3),
                "bias": 0.1 * jax.random.normal(next(keys), (c,))}

    blocks = []
    cin = int(model["gcn_in_channels"])
    for b, cout in enumerate(ch):
        blk = {"Bk": 0.02 * jax.random.normal(next(keys), (K, V, V)),
               "Wk": he((K, cin, cout), cin), "bn_s": bn(cout),
               "tconv_w": he((cout, cout, TK), cout * TK),
               "tconv_b": 0.05 * jax.random.normal(next(keys), (cout,)),
               "bn_t": bn(cout)}
        if cin != cout:
            blk["down_w"] = he((cin, cout), cin)
            blk["bn_down"] = bn(cout)
        if cin != cout or strides[b] != 1:
            blk["short_w"] = he((cin, cout), cin)
            blk["bn_short"] = bn(cout)
        blocks.append(blk)
        cin = cout
    C = int(model["gcn_in_channels"])
    return {"data_bn": bn(C * V), "blocks": blocks,
            "fc_w": he((ch[-1], int(model["gcn_num_classes"])), ch[-1]),
            "fc_b": 0.05 * jax.random.normal(
                next(keys), (int(model["gcn_num_classes"]),))}


def make_stream_params(model: dict, seed: int) -> Tuple[Dict, Dict]:
    """(joint, bone) weights from ``seed``, made on the device in one
    jitted call."""
    def both(key):
        kj, kb = jax.random.split(key)
        return make_params(model, kj), make_params(model, kb)

    return jax.jit(both)(jax.random.PRNGKey(seed % (2 ** 32)))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

class _BN:
    """Batch norm in one of two modes: record the batch's statistics per
    site (``stats`` None -> fills ``self.recorded``) or apply frozen ones."""

    def __init__(self, stats: Optional[Dict] = None):
        self.stats = stats
        self.recorded: Dict[str, Tuple] = {}

    def __call__(self, site, x, p):
        if self.stats is None:
            axes = tuple(range(x.ndim - 1))
            mean = jnp.mean(x, axes)
            var = jnp.mean(jnp.square(x - mean), axes)
            inv = jax.lax.rsqrt(var.astype(jnp.float32) + EPS).astype(x.dtype)
            self.recorded[site] = (mean, inv)
        else:
            mean, inv = self.stats[site]
        return (x - mean) * inv * p["scale"].astype(x.dtype) \
            + p["bias"].astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class Numerics:
    """How the reference computes: the storage dtype of activations and
    weights, and the dtype every matmul operand is rounded to (None: the
    storage dtype).  Products are exact and sums float32, as on the MXU;
    the result is rounded back to the storage dtype.  ``kernel_exact``
    leaves the operands of the graph and temporal matmuls unrounded."""

    dtype: object = jnp.float32
    operand: object = None
    kernel_exact: bool = False


EXACT = Numerics()                       # float32 throughout
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# the nearest storage precision below each one a configuration may state
BELOW = {"float32": "bfloat16"}


def stated(conf: dict) -> Numerics:
    """The numerics a configuration file states (``numerics``): its
    storage dtype and its matmul operand dtype."""
    n = conf["numerics"]
    return Numerics(DTYPES[n["storage"]], DTYPES[n["matmul_operands"]])


def control(conf: dict) -> Numerics:
    """The control: the stated numerics with storage one step lower."""
    n = conf["numerics"]
    low = DTYPES[BELOW[n["storage"]]]
    op = DTYPES[n["matmul_operands"]]
    return Numerics(low, op if jnp.finfo(op).bits < jnp.finfo(low).bits
                    else low)


def _mm(eq, *args, num: Numerics, kernel: bool = False):
    op = num.operand or num.dtype
    if num.kernel_exact and kernel:
        op = jnp.float32
    args = [a.astype(op) for a in args]
    return jnp.einsum(eq, *args, precision=HIGHEST,
                      preferred_element_type=jnp.float32).astype(num.dtype)


def features(params, x, model: dict, quant: bool, bn: _BN,
             num: Numerics = EXACT):
    """Last-block outputs of one stream, averaged over joints:
    (N, T_last, C_last)."""
    blocks = layout.blocks(model)
    skip = int(model.get("input_skip", 1))
    K = int(model["gcn_tkernel"])
    pad = K // 2
    dtype = num.dtype
    A = jnp.asarray(ntu_subsets(), dtype)
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    h = x.astype(dtype)[:, ::skip]
    N, T, V, C = h.shape
    h = bn("stem", h.reshape(N, T, V * C), p["data_bn"]).reshape(N, T, V, C)
    for b, (blk, pb) in enumerate(zip(blocks, p["blocks"])):
        Wk, tw = pb["Wk"], pb["tconv_w"]
        if quant:
            Wk, tw = q88(Wk), q88(tw)
        G = A + pb["Bk"]
        agg = _mm("ntvc,kwv->ntkwc", h[..., :blk.n_in], G, num=num,
                  kernel=True)
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


def _head(params, pooled, num: Numerics):
    return (_mm("nc,co->no", pooled, params["fc_w"].astype(num.dtype),
                num=num) + params["fc_b"].astype(num.dtype))


def bone(x):
    """Bone vectors: joint minus parent joint."""
    return x - x[..., ntu_parents(), :]


def clip_logits(params2, x, model: dict, quant: bool,
                num: Numerics = EXACT):
    """Two-stream clip logits with batch statistics over ``x``'s rows:
    (N, classes) float32."""
    out = []
    for params, xs in zip(params2, (x, bone(x))):
        f = features(params, xs, model, quant, _BN(), num)
        out.append(_head(params, f.mean(axis=1), num))
    return (0.5 * (out[0] + out[1])).astype(jnp.float32)


def calibrate(params2, x_calib, model: dict, quant: bool,
              num: Numerics = EXACT) -> List[Dict]:
    """Per-stream batch-norm statistics of one calibration batch."""
    stats = []
    for params, xs in zip(params2, (x_calib, bone(x_calib))):
        rec = _BN()
        features(params, xs, model, quant, rec, num)
        stats.append(rec.recorded)
    return stats


def stream_features(params2, stats2, frames, model: dict, quant: bool,
                    num: Numerics = EXACT) -> List:
    """Per stream, the joint-averaged last-block outputs of a forward over
    every frame of ``frames`` (N, L, V, C) with frozen batch norm:
    (N, T_last, C) each.  The output a streaming session completes after
    ``n`` raw frames (:func:`layout.emitted`) has its receptive field
    inside those frames, so it is the same here whatever follows them."""
    return [features(params, xs, model, quant, _BN(stats), num)
            for params, stats, xs in zip(params2, stats2,
                                         (frames, bone(frames)))]


def read_logits(params2, feats2, rows, done, num: Numerics = EXACT):
    """Two-stream running logits of reads: read ``j`` is of session
    ``rows[j]`` once it has completed ``done[j]`` last-block outputs, the
    fc of their mean (summed in float32): (len(rows), classes) float32."""
    out = []
    for params, f in zip(params2, feats2):
        cs = jnp.cumsum(f.astype(jnp.float32), axis=1)
        pooled = cs[rows, jnp.maximum(done, 1) - 1] \
            / jnp.maximum(done, 1).astype(jnp.float32)[:, None]
        out.append(_head(params, pooled.astype(num.dtype), num))
    return (0.5 * (out[0] + out[1])).astype(jnp.float32)
