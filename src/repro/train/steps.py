"""Step-function factories: train_step (fwd+bwd+AdamW, optional gradient
accumulation over microbatches) and serve_step (one decode token against a
KV cache, cache donated)."""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig, TrainConfig
from repro.models import registry
from repro.optim import adamw


def make_loss_fn(cfg: ModelConfig) -> Callable:
    def loss(params, batch):
        return registry.loss_fn(params, batch, cfg)
    return loss


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig,
                    grad_shardings=None) -> Callable:
    """grad_shardings: optional sharding tree applied to the gradients before
    the optimizer update — lets XLA reduce-scatter the data-parallel grad
    sync straight into the (2D-sharded) moment update instead of
    all-reducing full gradients (ZeRO-2).

    When ``cfg.use_ck`` is set the loss differentiates through the
    windowed C_k similarity graph (``adaptive.clip_windowed_ck`` in the
    model forward), so the per-block theta/phi projections train jointly
    with the conv weights — no separate step is needed for the adaptive
    graph."""
    loss_fn = make_loss_fn(cfg)
    nmb = max(1, tcfg.microbatches)

    def train_step(params, opt_state, batch):
        if nmb == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            def mb(carry, mb_batch):
                gacc, lacc = carry
                (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mb_batch)
                gacc = jax.tree_util.tree_map(jnp.add, gacc, g)
                if grad_shardings is not None:
                    # keep the accumulator 2D-sharded: each microbatch's
                    # grad sync lowers as a reduce-scatter into the shard
                    gacc = jax.lax.with_sharding_constraint(
                        gacc, grad_shardings)
                return (gacc, lacc + l), None

            split = jax.tree_util.tree_map(
                lambda x: x.reshape(nmb, x.shape[0] // nmb, *x.shape[1:]),
                batch,
            )
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            if grad_shardings is not None:
                zeros = jax.lax.with_sharding_constraint(zeros, grad_shardings)
            (grads, loss), _ = jax.lax.scan(mb, (zeros, jnp.zeros(())), split)
            grads = jax.tree_util.tree_map(lambda g: g / nmb, grads)
            loss = loss / nmb
            metrics = {"loss": loss}
        if tcfg.grad_compression == "bf16":
            # compress before the DP sync: the reduce happens on bf16
            # payloads (half the collective bytes); AdamW accumulates its
            # moments in f32 regardless
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.bfloat16), grads)
        if grad_shardings is not None:
            grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
        params, opt_state, opt_metrics = adamw.update(
            params, grads, opt_state, tcfg)
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        return params, opt_state, metrics

    return train_step


def _gcn_bone_fn(plans) -> Callable:
    """Bone transform for the ensemble's second stream: the plan's own
    (V,) parent map when present (any topology — the map rides as a plan
    leaf, so no retrace), else the fixed NTU-25 :func:`bone_stream`."""
    from repro.core.agcn.model import bone_stream, bone_stream_parents

    parents = plans[1].arrays.get("parents") if len(plans) > 1 else None
    if parents is None:
        return bone_stream
    return lambda x: bone_stream_parents(x, parents[: x.shape[-2]])


def make_gcn_infer_step(cfg: ModelConfig) -> Callable:
    """Batched GCN inference step over prebuilt ExecutionPlans.

    Returns ``step(plans, x) -> logits`` where ``plans`` is a tuple of one
    (joint) or two (joint, bone) engine ExecutionPlans.  The plans ride as
    pytree *arguments*, so the jit cache is keyed on their shapes/static
    metadata — rebuilding an identical plan never retraces, and no packing
    happens inside the step (engine invariant, tested in test_engine.py).
    """
    from repro.core.agcn import engine

    def infer_step(plans, x):
        logits = engine.execute(plans[0], x)
        if len(plans) > 1:
            logits = 0.5 * (logits + engine.execute(
                plans[1], _gcn_bone_fn(plans)(x)))
        return logits

    return infer_step


def make_gcn_stream_step(cfg: ModelConfig) -> Callable:
    """Per-frame continual-inference step over prebuilt ExecutionPlans.

    Returns ``step(plans, states, frame, valid=True) -> (states, logits)``
    where ``plans``/``states`` are matched tuples of one (joint) or two
    (joint, bone) engine ExecutionPlans and StreamStates, and ``frame`` is
    one raw (N, V, C) skeleton frame.  The bone transform is frame-local
    (joint − parent joint), so the two-stream ensemble streams too.  Like
    the clip step, everything rides as pytree arguments: one compilation
    per plan pair serves the whole stream, and ``valid=False`` drains the
    per-block latency after the clip ends (engine.stream_flush_frames)."""
    from repro.core.agcn import engine

    def stream_step(plans, states, frame, valid=True):
        s0, logits = engine.step_frame(plans[0], states[0], frame,
                                       valid=valid)
        if len(plans) > 1:
            s1, lb = engine.step_frame(plans[1], states[1],
                                       _gcn_bone_fn(plans)(frame),
                                       valid=valid)
            return (s0, s1), 0.5 * (logits + lb)
        return (s0,), logits

    return stream_step


def make_gcn_slab_step(cfg: ModelConfig) -> Callable:
    """Multi-session slab step over prebuilt ExecutionPlans.

    Returns ``step(plans, slabs, frames, valid, reset, hold=None) ->
    (slabs, logits)`` — the scheduler-tick form of
    :func:`make_gcn_stream_step`: ``frames`` is one raw (S, V, C) frame per
    slab slot, ``valid`` (S,) marks slots feeding real clip frames (False =
    flush drain or free slot), ``reset`` (S,) zeroes this tick's admissions
    before the frame lands (engine.reset_slots — a traced mask, so
    admissions never retrace), and ``hold`` (S,) freezes starved open
    sessions in place (engine.step_frames hold).  Both ensemble streams
    (joint + bone) share the same slot schedule; the host-side
    admission/eviction logic lives in ``repro.serving``.

    ``stats`` (keyword, optional) is a per-stream tuple of frozen BN
    statistics overriding each slab's own calibration for this tick — how
    the service steps its bare slabs (:func:`on_packed_constants`), one
    topology's statistics per dispatch; ``None`` keeps the slabs' own
    stats."""
    from repro.core.agcn import engine

    def slab_step(plans, slabs, frames, valid, reset, hold=None, stats=None):
        st = stats or (None,) * len(plans)
        s0, logits = engine.step_frames(plans[0], slabs[0], frames, valid,
                                        reset, hold, bn_stats=st[0])
        if len(plans) > 1:
            s1, lb = engine.step_frames(plans[1], slabs[1],
                                        _gcn_bone_fn(plans)(frames), valid,
                                        reset, hold, bn_stats=st[1])
            return (s0, s1), 0.5 * (logits + lb)
        return (s0,), logits

    return slab_step


def make_gcn_fused_tick(cfg: ModelConfig) -> Callable:
    """One-dispatch multi-session serving tick over prebuilt ExecutionPlans.

    Returns ``tick(plans, slabs, frames, valid, reset, hold, snap_order,
    rest_order, rings) -> (slabs, logits, rings)`` — the fused form of
    :func:`make_gcn_slab_step`: the tick's snapshot gathers, restore
    scatters, admission resets, hold masking and the slab step execute as
    a single jitted call per ensemble stream (engine.fused_tick), with
    the snapshot captures living in preallocated on-device rings (one per
    stream, ``engine.init_snapshot_ring``).  ``snap_order``/``rest_order``
    are fixed-shape (E, 2) sentinel-padded event buffers shared by both
    ensemble streams (joint + bone ride the same slot schedule).  Jit it
    with ``donate_argnums=(1, 8)`` so the slab and ring pytrees update in
    place; the caller must never re-read the donated inputs.  ``stats``
    (keyword — kwargs are never donated) mirrors
    :func:`make_gcn_slab_step`'s per-stream BN-stats override."""
    from repro.core.agcn import engine

    def fused_tick(plans, slabs, frames, valid, reset, hold,
                   snap_order, rest_order, rings, stats=None):
        st = stats or (None,) * len(plans)
        s0, logits, r0 = engine.fused_tick(
            plans[0], slabs[0], frames, valid, reset, hold,
            snap_order, rest_order, rings[0], bn_stats=st[0])
        if len(plans) > 1:
            s1, lb, r1 = engine.fused_tick(
                plans[1], slabs[1], _gcn_bone_fn(plans)(frames), valid,
                reset, hold, snap_order, rest_order, rings[1],
                bn_stats=st[1])
            return (s0, s1), 0.5 * (logits + lb), (r0, r1)
        return (s0,), logits, (r0,)

    return fused_tick


def on_packed_constants(step: Callable) -> Callable:
    """The serving tick's call form of :func:`make_gcn_slab_step` or
    :func:`make_gcn_fused_tick`: ``tick(consts, slabs, *args)``, where
    ``consts`` is an ``engine.PackedConstants`` of ``(plans, stats)`` —
    the ExecutionPlans and their frozen BN statistics in one buffer per
    dtype — and ``slabs`` carry per-slot state only (empty ``bn_stats``).
    The plans and statistics are unpacked inside the trace and the
    statistics ride ``step``'s ``stats`` override, so the jitted call
    takes and returns a third of the arrays the unpacked form does and
    computes the same thing bit for bit.  ``consts`` is argument 0 and is
    never donated."""

    def packed(consts, slabs, *args):
        plans, stats = consts.unpack()
        return step(plans, slabs, *args, stats=stats)

    return packed


def make_serve_step(cfg: ModelConfig) -> Callable:
    def serve_step(params, cache, batch):
        logits, new_cache = registry.serve_fn(params, batch, cache, cfg)
        # greedy next token (sampling handled by the serving loop)
        next_tok = jnp.argmax(logits[:, -1, : cfg.padded_vocab], axis=-1)
        return next_tok.astype(jnp.int32), new_cache

    return serve_step


def make_prefill_step(cfg: ModelConfig) -> Callable:
    """Forward pass producing logits only (the prefill_32k cells)."""
    def prefill_step(params, batch):
        loss, metrics = registry.loss_fn(params, batch, cfg, inference=True)
        return metrics

    return prefill_step
