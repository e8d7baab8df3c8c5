"""Mesh-sharded session serving: the slab tick as an SPMD program.

The paper's throughput story — every layer resident, runtime-compressed
features, many streams at once — caps out at one device's slab capacity.
This module scales the *slot axis* instead of per-clip batches (the
continual-inference regime of CoST-GCN): a 1-D device mesh shards the
session slab's leading S axis, so one :class:`repro.serving.GcnService`
tick runs as a single SPMD dispatch across every mesh device, while the
host-side scheduler stays exactly the single-device scheduler (slots are
global indices; XLA routes each row's work to its shard).

Wiring (all of it reuses existing machinery):

* the engine's ``step_frame`` already constrains frames/logits to the
  logical ``"batch"`` axis (``repro.distributed.sharding.constrain``);
  under :func:`make_batch_mesh` those hints resolve to the mesh's
  ``data`` axis at trace time,
* ``GcnService(mesh=...)`` places the live slab, tier slabs and snapshot
  rings (slot leaves sharded, BN stats + ring rows replicated) and pins
  matching ``out_shardings`` on every jitted entry point, so donation
  and the one-compilation-per-tier property survive sharding,
* admission resets, preemption snapshot/restore and elastic tier
  migration are traced gathers/scatters over the sharded slab — XLA
  inserts the collectives; the host never notices.

No hardware needed: ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
makes the mesh real on CPU (how tests/test_distributed.py and the
``--dist`` CI tier run).  :func:`collective_cost_ms` measures what the
sharding costs per tick — the ``collective_ms_per_tick`` axis of
``BENCH_sessions.json``.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

BATCH_AXIS = "data"


def make_batch_mesh(n_devices: Optional[int] = None):
    """Build the 1-D slot mesh: ``n_devices`` devices under the single
    axis ``"data"`` (the axis the logical ``"batch"`` rule resolves to,
    see ``repro.distributed.sharding.DEFAULT_RULES``).

    ``n_devices`` defaults to every visible device.  Raises with the
    ``--xla_force_host_platform_device_count`` hint when the platform
    exposes fewer devices than asked — on CPU the fake-device flag is
    how a mesh becomes real."""
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    n_devices = int(n_devices)
    if n_devices < 1:
        raise ValueError(f"mesh needs at least 1 device, got {n_devices}")
    if len(devices) < n_devices:
        raise RuntimeError(
            f"asked for a {n_devices}-device mesh but only {len(devices)} "
            "devices are visible — on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_devices} before "
            "jax initialises")
    import numpy as np
    return Mesh(np.asarray(devices[:n_devices]), (BATCH_AXIS,))


def collective_cost_ms(svc, iters: int = 16) -> float:
    """Per-tick collective overhead of the mesh-sharded slab step, in ms.

    Times the service's own (sharded) no-event slab step against a
    freshly-jitted single-device copy of the same step on the same slab
    content, and returns the difference (floored at 0) — the price of
    the cross-shard collectives the sharded tick pays, which is the
    ``collective_ms_per_tick`` column of the sharded
    ``BENCH_sessions.json`` rows.  Run on an idle service (the slab is
    read, not donated)."""
    import jax
    import jax.numpy as jnp

    from repro.train.steps import make_gcn_slab_step, on_packed_constants

    S = svc.capacity
    zf = jnp.zeros((S, svc.vmax, svc.cfg.gcn_in_channels))
    zb = jnp.zeros((S,), bool)

    def timed(step, consts, slabs) -> float:
        out = step(consts, slabs, zf, zb, zb, zb)   # compile + warm
        jax.block_until_ready(out[1])
        t0 = time.perf_counter()
        for _ in range(iters):
            out = step(consts, slabs, zf, zb, zb, zb)
        jax.block_until_ready(out[1])
        return (time.perf_counter() - t0) / iters * 1e3

    consts = svc._consts[svc.primary]
    sharded_ms = timed(svc._step, consts, svc.slabs)
    dev = jax.devices()[0]
    single = jax.jit(on_packed_constants(make_gcn_slab_step(svc.cfg)))
    consts1, slabs1 = jax.device_put((consts, svc.slabs), dev)
    single_ms = timed(single, consts1, slabs1)
    return max(0.0, sharded_ms - single_ms)


def run_sharded_sessions(cfg, *, mesh: int, **kwargs) -> Dict:
    """Serve a session load with the slab sharded over a ``mesh``-device
    1-D batch mesh — :func:`repro.serving.run_sessions` with the mesh
    axis set; the returned row carries ``mesh`` and
    ``collective_ms_per_tick`` for the sharded ``BENCH_sessions.json``
    axis."""
    from repro.serving import run_sessions

    return run_sessions(cfg, mesh=int(mesh), **kwargs)
