"""The system under test, reached through its public entry points only:
``repro.configs.get_config``, ``repro.core.pruning.plan.plan_from_config``,
``repro.core.agcn.engine`` (plan build, BN calibration, slab shapes),
``repro.core.agcn.model.bone_stream_parents``, ``repro.serving.GcnService``,
``repro.train.steps`` (the two-stream clip and slab steps) and
``repro.kernels.ops.kernel_counts``."""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Tuple

import numpy as np


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file: its ``base``
    config with every key of ``model`` set as the file states it."""
    from repro.configs import get_config

    base = get_config(conf["base"], reduced=False)
    fields = {f.name for f in dataclasses.fields(base)}
    kw = {}
    for k, v in conf["model"].items():
        if k not in fields:
            raise KeyError(f"{conf['name']}: model key {k!r} is not a "
                           "ModelConfig field")
        kw[k] = tuple(v) if isinstance(v, list) else v
    return dataclasses.replace(base, **kw)


def build_plans(cfg, params2, conf: dict) -> Tuple:
    """(joint, bone) ExecutionPlans from the benchmark's weights."""
    from repro.core.agcn import engine
    from repro.core.pruning.plan import plan_from_config

    prune = plan_from_config(cfg)
    return tuple(engine.build_execution_plan(
        p, cfg, prune, quant=bool(conf["quant"]), backend=conf["backend"])
        for p in params2)


def calibrate(plans, x_calib):
    """Frozen BN statistics per stream from one calibration batch, the
    program's ``collect_bn_stats`` traced once under ``jax.jit`` (its
    recorder returns the statistics as outputs) instead of op by op."""
    import jax

    from repro.core.agcn import engine
    from repro.core.agcn.model import bone_stream_parents

    def both(plans, x):
        xb = bone_stream_parents(x, plans[1].arrays["parents"])
        return (engine.collect_bn_stats(plans[0], x),
                engine.collect_bn_stats(plans[1], xb))

    return jax.block_until_ready(jax.jit(both)(plans, x_calib))


def kernel_counts(compiled) -> Dict[str, int]:
    """Compiled Pallas kernels per family in a compiled program."""
    from repro.kernels.ops import kernel_counts as kc

    return kc(compiled.as_text())


def slab_program(cfg, plans, stats, slots: int):
    """The service's event-free tick program, compiled the way the service
    jits it (``make_gcn_slab_step``), for its kernel counts.  Shapes only:
    nothing is allocated.  Returns (compiled, per-slot state bytes)."""
    import jax
    import jax.numpy as jnp

    from repro.core.agcn import engine
    from repro.train.steps import make_gcn_slab_step

    slabs = jax.eval_shape(lambda: tuple(
        engine.init_session_slab(p, slots, bn_stats=s)
        for p, s in zip(plans, stats)))
    V, C = int(cfg.gcn_joints), int(cfg.gcn_in_channels)
    frames = jax.ShapeDtypeStruct((slots, V, C), jnp.float32)
    mask = jax.ShapeDtypeStruct((slots,), jnp.bool_)
    compiled = jax.jit(make_gcn_slab_step(cfg)).lower(
        plans, slabs, frames, mask, mask, mask).compile()
    def nbytes(tree):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(tree))

    per_slot = sum(nbytes(s) - nbytes(s.bn_stats) for s in slabs)
    return compiled, per_slot // slots


def service(cfg, plans, stats, slots: int, qos: str):
    """A one-tier GcnService on the given plans and calibration; nothing
    is warmed here — the caller's first tick compiles its one program."""
    from repro.serving import GcnService

    return GcnService(cfg, backend=plans[0].static.backend, qos=qos,
                      capacity_tiers=(slots,), plans=plans, bn_stats=stats,
                      warm=False)


def clip_step(cfg, plans, x):
    """The compiled two-stream clip step for ``x``'s shape."""
    import jax

    from repro.train.steps import make_gcn_infer_step

    return jax.jit(make_gcn_infer_step(cfg)).lower(plans, x).compile()


class Clock:
    """Set-up split: named phases in seconds."""

    def __init__(self):
        self.phases: Dict[str, float] = {}

    def phase(self, name: str):
        clock = self

        class _P:
            def __enter__(self):
                self.t = time.monotonic()

            def __exit__(self, *exc):
                clock.phases[name] = clock.phases.get(name, 0.0) + (
                    time.monotonic() - self.t)

        return _P()
