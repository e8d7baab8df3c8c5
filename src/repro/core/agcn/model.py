"""2s-AGCN in JAX (paper §II), with the hybrid pruning plan (C1+C2) applied
as static channel compaction, optional windowed C_k self-similarity graph
(``repro.core.agcn.adaptive`` — streaming/clip parity by construction), Q8.8
quantization and input-skipping (C5).

Data layout: (N, T, V, C) with the person axis M folded into N (NTU clips are
(N, C, T, V, M); the loader reshapes).  Ten TCN-GCN blocks + global pool + FC,
channels (64,)*4 + (128,)*3 + (256,)*3, temporal strides 1,1,1,1,2,1,1,2,1,1
as in the reference implementation of Shi et al. [9]:

    block(x) = relu( bn(tconv(gcnunit(x), stride)) + residual(x) )
    gcnunit(x) = relu( bn(sum_k (G_k·x)·W_k) + down(x) )

BatchNorm is implemented statelessly (batch statistics at both train and
inference time — the paper's accelerator runs fixed batches, and this keeps
the step functions pure); the learned scale/bias are real parameters.

This module owns parameters and the public API; the per-op math lives in
``repro.core.agcn.engine`` behind a backend-dispatched ExecutionPlan:
``forward`` compiles the plan (or takes a prebuilt one) and executes it.
The default ``reference`` backend is fully traceable/differentiable — the
train path is unchanged; the ``pallas`` backend runs the fused kernels.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import ModelConfig
from repro.core.pruning.plan import PrunePlan

AGCN_CHANNELS = (64, 64, 64, 64, 128, 128, 128, 256, 256, 256)
AGCN_STRIDES = (1, 1, 1, 1, 2, 1, 1, 2, 1, 1)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _conv_init(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * np.sqrt(2.0 / fan_in)


def _bn_init(c: int) -> Dict[str, jnp.ndarray]:
    return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}


def init_params(cfg: ModelConfig, key: jax.Array) -> Dict[str, Any]:
    """Parameter pytree for one (single-stream) AGCN model."""
    channels = cfg.gcn_channels or AGCN_CHANNELS
    strides = cfg.gcn_strides or AGCN_STRIDES
    V, K, TK = cfg.gcn_joints, cfg.gcn_kv, cfg.gcn_tkernel
    cin = cfg.gcn_in_channels
    keys = jax.random.split(key, len(channels) * 8 + 2)
    ki = iter(range(len(keys)))

    blocks = []
    for b, cout in enumerate(channels):
        blk: Dict[str, Any] = {
            "Bk": jnp.full((K, V, V), 1e-6, jnp.float32),
            "Wk": _conv_init(keys[next(ki)], (K, cin, cout), cin),
            "bn_s": _bn_init(cout),
            "tconv_w": _conv_init(keys[next(ki)], (cout, cout, TK), cout * TK),
            "tconv_b": jnp.zeros((cout,), jnp.float32),
            "bn_t": _bn_init(cout),
        }
        if cfg.use_ck:
            ce = max(4, cin // 4)
            blk["theta"] = _conv_init(keys[next(ki)], (cin, ce), cin)
            blk["phi"] = _conv_init(keys[next(ki)], (cin, ce), cin)
        if cin != cout:
            blk["down_w"] = _conv_init(keys[next(ki)], (cin, cout), cin)
            blk["bn_down"] = _bn_init(cout)
        if cin != cout or strides[b] != 1:
            blk["short_w"] = _conv_init(keys[next(ki)], (cin, cout), cin)
            blk["bn_short"] = _bn_init(cout)
        blocks.append(blk)
        cin = cout

    return {
        "data_bn": _bn_init(cfg.gcn_in_channels * V),
        "blocks": blocks,
        "fc_w": _conv_init(keys[next(ki)], (channels[-1], cfg.gcn_num_classes), channels[-1]),
        "fc_b": jnp.zeros((cfg.gcn_num_classes,), jnp.float32),
    }


# ---------------------------------------------------------------------------
# model — a thin dispatcher over the execution engine
# ---------------------------------------------------------------------------

def forward(
    params: Dict[str, Any],
    x: jnp.ndarray,                       # (N, T, V, C)
    cfg: ModelConfig,
    plan: Optional[PrunePlan] = None,
    quant: bool = False,
    backend: Optional[str] = None,
    exec_plan=None,
) -> jnp.ndarray:
    """Logits (N, num_classes).

    ``backend`` selects the engine implementation (``reference`` |
    ``pallas``); ``None`` falls back to ``cfg.gcn_backend``.  A prebuilt
    ``exec_plan`` (see ``engine.build_execution_plan``) skips plan
    compilation entirely — the serving hot path; otherwise the plan is
    compiled here from ``(params, plan, cfg)``, which for the reference
    backend stays traceable (so the differentiable train path is this same
    call).  Pallas plans must be compiled outside jit.
    """
    from repro.core.agcn import engine
    if exec_plan is not None:
        return engine.execute(exec_plan, x)
    name = backend or cfg.gcn_backend or "reference"
    ep = engine.build_execution_plan(
        params, cfg, plan, quant=quant, backend=name)
    return engine.execute(ep, x)


def init_stream(
    params: Dict[str, Any],
    cfg: ModelConfig,
    x_calib: jnp.ndarray,                 # (N, T, V, C) representative clip
    plan: Optional[PrunePlan] = None,
    quant: bool = False,
    backend: Optional[str] = None,
    exec_plan=None,
):
    """State-init API for per-frame continual inference (engine streaming
    mode).  Returns ``(exec_plan, StreamState)``.

    ``x_calib`` fixes the stream's batch size and calibrates the frozen
    batch-norm statistics that make ``engine.step_frame`` reproduce the
    clip engine post-drain (the streaming correctness contract, locked in
    tests/test_streaming.py).  A prebuilt ``exec_plan`` skips plan
    compilation; otherwise one is compiled exactly as in :func:`forward`."""
    from repro.core.agcn import engine
    ep = exec_plan
    if ep is None:
        name = backend or cfg.gcn_backend or "reference"
        ep = engine.build_execution_plan(
            params, cfg, plan, quant=quant, backend=name)
    state = engine.init_stream_state(ep, x_calib.shape[0], x_calib=x_calib)
    return ep, state


def bone_stream(x: jnp.ndarray) -> jnp.ndarray:
    """Second stream of 2s-AGCN: bone vectors = joint − parent joint
    (the fixed NTU-25 skeleton; see :func:`bone_stream_parents` for any
    other topology)."""
    from repro.core.agcn.graph import NTU_EDGES
    out = jnp.zeros_like(x)
    for j, p in NTU_EDGES:
        out = out.at[..., j - 1, :].set(x[..., j - 1, :] - x[..., p - 1, :])
    return out


def bone_stream_parents(x: jnp.ndarray, parents) -> jnp.ndarray:
    """Topology-generic bone stream: one gather against a (V,) parent map
    (``GraphTopology.parents`` / ``plan.arrays["parents"]``).  Roots parent
    themselves, so their bone vector is zero — identical to
    :func:`bone_stream` on the NTU-25 map."""
    return x - jnp.take(x, jnp.asarray(parents, jnp.int32), axis=-2)


def two_stream_logits(params_joint, params_bone, x, cfg, plan=None,
                      quant=False, backend=None):
    """Ensemble of the joint and bone streams (the '2s' in 2s-AGCN)."""
    lj = forward(params_joint, x, cfg, plan, quant, backend=backend)
    lb = forward(params_bone, bone_stream(x), cfg, plan, quant,
                 backend=backend)
    return 0.5 * (lj + lb)


def feature_sparsity_per_block(params, x, cfg, plan=None) -> List[float]:
    """Post-ReLU sparsity per block output — drives RFC mini-bank sizing and
    the Drop-* channel schedules (paper Fig. 9, Table III)."""
    from repro.core.agcn import engine
    ep = engine.build_execution_plan(params, cfg, plan, backend="reference")
    return [float((h == 0).mean()) for h in engine.block_outputs(ep, x)]
