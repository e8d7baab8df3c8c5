"""Logical-axis sharding (MaxText-style rules), used everywhere in the zoo.

Model code annotates tensors with *logical* axis names via ``constrain``;
a context (set by the launcher / dry-run) maps logical names to mesh axes.
Outside any context ``constrain`` is a no-op, so unit tests and smoke tests
run unchanged on one CPU device.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rules = Dict[str, Union[None, str, Tuple[str, ...]]]

# Default rules for the production mesh (data, model) [+ optional pod axis].
# Design: batch over (pod, data); big weight dims + sequence-between-blocks
# over model (sequence parallelism); vocab/ffn/experts/kv-flat over model.
DEFAULT_RULES: Rules = {
    "batch": ("pod", "data"),
    "seq": None,               # activations' sequence dim inside blocks
    "seq_shard": "model",      # sequence dim *between* blocks (SP regions)
    "embed": None,             # activation d_model dim
    "vocab": "model",
    "ffn": "model",
    "heads": None,
    "qkv_flat": "model",       # flattened heads*head_dim weight dim
    "kv_flat": "model",        # flattened kv_heads*head_dim (cache + weights)
    "expert": "model",
    "embed_fsdp": "data",      # weight d_model dim (ZeRO-3 over data)
    "layers": None,
    "state": "model",          # ssm state dims (divisible for all archs)
    "kv_seq": None,            # decode KV cache sequence dim (hillclimb knob)
    "kv_hd": "model",          # KV cache head_dim (divides 16 for all archs)
}


class _Ctx(threading.local):
    mesh: Optional[Mesh] = None
    rules: Optional[Rules] = None


_CTX = _Ctx()


@contextlib.contextmanager
def axis_rules(mesh: Mesh, rules: Optional[Rules] = None):
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, dict(rules or DEFAULT_RULES)
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def _resolve(axis: Optional[str]) -> Union[None, str, Tuple[str, ...]]:
    if axis is None or _CTX.rules is None:
        return None
    spec = _CTX.rules.get(axis)
    if spec is None:
        return None
    # drop mesh axes that don't exist (e.g. "pod" on the single-pod mesh);
    # a tuple left with one member normalizes to the bare string so specs
    # compare equal to hand-written P("data", ...) forms
    names = _CTX.mesh.axis_names
    if isinstance(spec, tuple):
        kept = tuple(s for s in spec if s in names)
        if not kept:
            return None
        return kept[0] if len(kept) == 1 else kept
    return spec if spec in names else None


def logical_spec(*axes: Optional[str]) -> P:
    return P(*[_resolve(a) for a in axes])


def constrain(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Apply a logical sharding constraint; no-op outside axis_rules()."""
    if _CTX.mesh is None:
        return x
    if len(axes) != x.ndim:
        raise ValueError(f"{len(axes)} axes for rank-{x.ndim} tensor")
    spec = logical_spec(*axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_CTX.mesh, spec))


def named_sharding(*axes: Optional[str]) -> Optional[NamedSharding]:
    if _CTX.mesh is None:
        return None
    return NamedSharding(_CTX.mesh, logical_spec(*axes))


def shard_batch(fn, batched, *consts):
    """``fn(batched, *consts)``, run once per device shard of the leading
    (logical ``"batch"``) axis of every leaf of ``batched`` when the active
    rules shard that axis over more than one device; ``consts`` (weights)
    are replicated.  XLA cannot partition a compiled Pallas (Mosaic)
    kernel, so each kernel call inside a sharded step runs under
    ``shard_map`` this way — every device on its own slots.  Outside the
    rules, on one device, or when the axis does not divide, it is a plain
    call."""
    spec = _resolve("batch") if _CTX.mesh is not None else None
    if spec is None:
        return fn(batched, *consts)
    n = 1
    for a in (spec if isinstance(spec, tuple) else (spec,)):
        n *= _CTX.mesh.shape[a]
    lead = {leaf.shape[0] for leaf in jax.tree_util.tree_leaves(batched)}
    if n == 1 or any(d % n for d in lead):
        return fn(batched, *consts)
    return jax.shard_map(
        fn, mesh=_CTX.mesh, in_specs=(P(spec),) + (P(),) * len(consts),
        out_specs=P(spec), check_vma=False)(batched, *consts)


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def divisible(dim: int, axis: Optional[str]) -> bool:
    """Would sharding `dim` over logical `axis` divide evenly on this mesh?"""
    if _CTX.mesh is None:
        return True
    spec = _resolve(axis)
    if spec is None:
        return True
    axes = spec if isinstance(spec, tuple) else (spec,)
    n = 1
    for a in axes:
        n *= _CTX.mesh.shape[a]
    return dim % n == 0
