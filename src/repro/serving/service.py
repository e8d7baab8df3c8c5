"""`GcnService`: the session-handle serving facade over the AGCN engine.

The paper's accelerator is a *serving* design — all layers resident,
runtime-compressed features, dynamic per-PE scheduling — and this module
is its service surface: one object owns the compiled ExecutionPlans, the
per-tier session slabs, the QoS scheduler and the elastic capacity
manager, and exposes the four-call session protocol:

    svc = GcnService(cfg, backend="pallas", qos="preempt",
                     capacity_tiers=(2, 4, 8, 16))
    h = svc.open_session(priority=1)
    svc.submit(h, frame)          # one (V, C) raw skeleton frame at a time
    svc.tick()                    # one scheduler tick serves every session
    svc.poll(h)                   # state + running logits
    svc.close(h)                  # end of stream -> flush drain -> record

Everything under the facade is the existing machinery recomposed: the
host-side :class:`~repro.serving.scheduler.SlabScheduler` builds each
tick's :class:`~repro.serving.scheduler.TickPlan`, one jitted
``make_gcn_slab_step`` call advances every slot (admission resets, flush
drains and starved-session holds are traced masks — no retrace within a
tier), and QoS preemption/elastic migration both ride the engine's
``snapshot_slots``/``restore_slots`` gather/scatter pair.

**Elastic capacity** (the ROADMAP item): slot capacity is a compiled
shape, so the service pre-builds one slab per ``capacity_tiers`` entry
(and warms the compiled step for each), watches queue depth + occupancy
through a hysteresis :class:`~repro.serving.capacity.CapacityManager`,
and on a grow/shrink decision migrates every active session across slabs:
snapshot the occupied rows, scatter them into the (pristine) target tier,
remap the scheduler's slot table.  The locked invariant
(tests/test_serving.py, both backends): a session migrated across tiers
produces the same logits as the uninterrupted fixed-capacity session.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.serving.capacity import CapacityConfig, CapacityManager
from repro.serving.saliency import SaliencyConfig, SaliencyGate
from repro.serving.scheduler import (QOS_POLICIES, AdmissionQueue,
                                     SessionRecord, SessionRequest,
                                     SlabScheduler, bursty_arrivals,
                                     max_events_for, pad_event_orders,
                                     poisson_arrivals)
from repro.serving.slo import CONTROL_POLICIES, SloConfig, SloController

SESSION_STATES = ("queued", "active", "draining", "done", "missed",
                  "rejected")

# the serving tick's named phases, in the order tick() runs them; readback
# is also reached from poll(wait=True) and metrics()
TICK_PHASES = ("feed", "stage", "dispatch", "readback", "drain")
HOST_PHASES = ("feed", "stage", "dispatch", "drain")


def _n_arrays(tree) -> int:
    """The number of arrays in a pytree."""
    import jax

    return len(jax.tree_util.tree_leaves(tree))


class _Phase:
    """One named phase of the serving tick: a profiler span ``svc.<name>``
    around the block (on the device trace's clock when a profiler runs)
    and the block's ``time.monotonic()`` duration added to
    ``phase_s[name]``.  Entering returns the phase's start time."""

    __slots__ = ("phase_s", "name", "span", "t0")

    def __init__(self, phase_s: Dict[str, float], name: str, span):
        self.phase_s, self.name, self.span = phase_s, name, span

    def __enter__(self) -> float:
        self.span.__enter__()
        self.t0 = time.monotonic()
        return self.t0

    def __exit__(self, *exc) -> None:
        self.phase_s[self.name] += time.monotonic() - self.t0
        self.span.__exit__(*exc)


@dataclasses.dataclass(frozen=True)
class SessionHandle:
    """Opaque ticket for one open session (returned by ``open_session``)."""

    sid: int


@dataclasses.dataclass
class SessionStatus:
    """One ``poll`` result: where the session is and what it predicts.

    ``state`` ∈ ``SESSION_STATES``: *queued* (awaiting a slot — including
    a preempted session awaiting re-admission), *active* (in a slot,
    consuming frames; a starved open session holds here), *draining*
    (stream closed, flush latency draining through the blocks), *done*
    (final record available), *missed* (dropped by the deadline
    policy) or *rejected* (turned away at open by the SLO controller's
    admission shed — it never entered the scheduler; ``submit``/``close``
    on it are no-ops).  ``logits`` is the slot's running prediction while
    active/draining, the final post-drain prediction when done, None
    otherwise."""

    sid: int
    state: str
    frames_submitted: int
    frames_consumed: int
    priority: int
    logits: Optional[np.ndarray] = None
    record: Optional[SessionRecord] = None


class GcnService:
    """Multi-session GCN serving facade: open/submit/poll/close + tick.

    One instance owns, per ensemble stream (joint + bone by default):
    a compiled ``ExecutionPlan``, frozen BN calibration, and one pristine
    session slab per capacity tier.  ``tick()`` advances every admitted
    session by one raw frame through a single jitted slab step; admission,
    preemption (``qos="preempt"``), deadline eviction (``qos="deadline"``)
    and elastic tier migration all happen between steps on the host.

    Parameters:
      cfg              — a gcn-family ``ModelConfig``; with ``use_ck``,
                         its ``ck_form`` must be ``"window"`` (the
                         published whole-clip C_k needs a whole clip,
                         which a live session does not have).
      backend          — engine backend (``reference`` | ``pallas``).
      qos              — scheduler policy (``fifo`` | ``preempt`` |
                         ``deadline``).
      capacity_tiers   — slot capacities; one entry = fixed capacity,
                         several = elastic (service starts at the smallest
                         tier and the capacity manager hops the ladder).
      capacity_config  — hysteresis knobs (tiers taken from
                         ``capacity_tiers``).
      policy           — capacity-control policy: ``"demand"`` (the
                         :class:`CapacityManager` — grow on raw
                         busy+queued demand) or ``"slo"`` (the
                         :class:`~repro.serving.slo.SloController` — grow
                         on measured p99 first-logit regression, shed via
                         admission control when even the top tier can't
                         hold the SLO).
      slo_config       — :class:`~repro.serving.slo.SloConfig` knobs for
                         ``policy="slo"`` (defaults when None; ignored
                         under ``"demand"``).
      record_outcomes  — keep a per-tick scheduler-outcome log under
                         ``self.outcomes`` (admissions, restores,
                         preemptions, finishes, misses, sheds, capacity)
                         — the pure-host, float-free record the golden
                         trace-replay tests lock.  Off by default: a
                         long-lived service must not grow an unbounded
                         log.
      quant            — Q8.8-quantize the plans (the paper's C5 target).
      seed             — parameter/init seed (ignored when ``plans`` is
                         given).
      plans            — prebuilt ExecutionPlan tuple: ``(joint,)`` or
                         ``(joint, bone)``; built from ``cfg`` when None.
      bn_stats         — frozen BN statistics per plan (tuple, or one dict
                         shared when a single plan is given); calibrated
                         from ``x_calib`` (or a synthetic pipeline batch)
                         when None.
      x_calib          — (N, T, V, C) calibration clip batch.
      warm             — pre-compile the slab step for every tier (and the
                         preempt gather/scatter) at construction so no
                         session ever pays compile latency.
      fused            — serve each tick as **one** device dispatch with
                         async logit readback.  Ticks carrying snapshot or
                         restore events run ``engine.fused_tick`` (gathers,
                         scatters, hold/reset masking and the slab step in
                         a single donated-slab jit, snapshots in an
                         on-device ring); event-free ticks run the plain
                         slab step — still one dispatch, no ring plumbing.
                         False restores the legacy multi-dispatch tick (one
                         jit per snapshot/restore event + a synchronous
                         readback) — kept for A/B parity tests and the
                         throughput benchmark baseline.
      snap_capacity    — snapshot-ring rows (fused path only): live
                         preempted sessions a tick can hold device state
                         for; defaults to ``2 * max(capacity_tiers)``.
      topologies       — skeleton graphs this service serves (registry
                         names, see ``repro.core.agcn.graph``).  The first
                         entry is the *primary* topology (what
                         ``open_session`` without ``topology=`` gets); the
                         slab is sized to the widest skeleton (``vmax``
                         joints) and every topology's ExecutionPlans are
                         padded to that width, so sessions with different
                         skeletons share one slab (narrow sessions ride
                         zero-padded, their plans mask the padded joints).
                         A mixed tick runs one dispatch per occupied
                         skeleton group — the primary group (plus all
                         snapshot/restore events and free slots) first,
                         then each other group with its own plans and BN
                         stats, everything outside the group held.
      sconv            — spatial-conv path selection forwarded to
                         ``engine.build_execution_plan`` (``auto`` |
                         ``dense`` | ``csr``); with the default
                         ``auto``/``csr_eps=0`` the learned dense B_k keeps
                         every graph dense — today's path.
      csr_eps          — |G| threshold below which entries are dropped
                         when measuring density / packing CSR.
      mesh             — optional 1-D ``jax.sharding.Mesh``: the live
                         slab, tier slabs and snapshot rings are placed
                         under it (slot axis sharded across the mesh,
                         BN stats and ring rows replicated) and every
                         jitted entry point is compiled with matching
                         output shardings, so one service tick runs
                         SPMD across the mesh devices.  Plans and BN
                         stats are committed to the mesh, replicated, so
                         a one-device mesh pins the whole service to that
                         device (the router's replica placement).  Every
                         capacity tier must divide the mesh size.  None
                         (default) = single-device service, unchanged.
      retain_records   — bound on per-session host bookkeeping: only the
                         most recent ``retain_records`` finished/missed
                         sessions keep their request/record entries
                         (lifetime totals live in running aggregates),
                         so a service that stays up for days holds
                         constant memory.
      saliency_thresh  — > 0 runs a
                         :class:`~repro.serving.saliency.SaliencyGate` at
                         that attention-ratio threshold: uninformative
                         frames are skipped per session (the scheduler
                         feeds only the kept subsequence; starved open
                         sessions ride the existing hold mask), so the
                         same slab serves more sessions at bounded
                         fidelity loss.  0 (default) = off — the feed
                         path and every metric row are byte-identical to
                         the pre-saliency service.
    """

    def __init__(self, cfg, *, backend: str = "reference", qos: str = "fifo",
                 capacity_tiers: Sequence[int] = (8,),
                 capacity_config: Optional[CapacityConfig] = None,
                 policy: str = "demand",
                 slo_config: Optional[SloConfig] = None,
                 record_outcomes: bool = False,
                 quant: bool = True, seed: int = 0,
                 plans: Optional[Tuple] = None,
                 bn_stats: Optional[Any] = None,
                 x_calib: Optional[np.ndarray] = None,
                 warm: bool = True, fused: bool = True,
                 snap_capacity: Optional[int] = None,
                 topologies: Sequence[str] = ("ntu25",),
                 sconv: str = "auto", csr_eps: float = 0.0,
                 mesh: Optional[Any] = None,
                 retain_records: int = 1024,
                 saliency_thresh: float = 0.0):
        import jax
        import jax.numpy as jnp

        from repro.core.agcn import engine
        from repro.core.agcn.graph import get_topology
        from repro.core.agcn.model import bone_stream_parents
        from repro.train.steps import (make_gcn_fused_tick,
                                       make_gcn_slab_step,
                                       on_packed_constants)

        if qos not in QOS_POLICIES:
            raise ValueError(f"unknown QoS policy {qos!r}")
        if cfg.use_ck and cfg.ck_form == "clip":
            raise ValueError(engine.STREAMING_CK_REFUSAL)
        if policy not in CONTROL_POLICIES:
            raise ValueError(f"unknown capacity policy {policy!r} "
                             f"(expected one of {CONTROL_POLICIES})")
        tiers = tuple(sorted(int(t) for t in capacity_tiers))
        if not tiers:
            raise ValueError("capacity_tiers must name at least one tier")
        if retain_records < 1:
            raise ValueError(
                f"retain_records must be >= 1, got {retain_records}")
        self.mesh = mesh
        if mesh is not None:
            if len(mesh.axis_names) != 1:
                raise ValueError(
                    f"GcnService expects a 1-D slot mesh, got axes "
                    f"{mesh.axis_names}")
            bad = [t for t in tiers if t % mesh.size]
            if bad:
                raise ValueError(
                    f"capacity tiers {bad} do not divide the mesh size "
                    f"{mesh.size} — the slot axis is sharded evenly "
                    "across the mesh devices")
        self.cfg = cfg
        self.backend = backend
        self.qos = qos
        self.tiers = tiers
        self.retain_records = int(retain_records)
        self._jax, self._jnp, self._engine = jax, jnp, engine

        # --- topology registry: one plan set per declared skeleton --------
        names = tuple(dict.fromkeys(topologies))
        if not names:
            raise ValueError("topologies must name at least one skeleton")
        self._topos = {t: get_topology(t, cfg.gcn_kv) for t in names}
        self.topologies = names
        self.primary = names[0]
        # the slab's joint width: every topology's plans are padded to it
        self.vmax = max(tp.num_joints for tp in self._topos.values())

        # --- plans (joint [+ bone]) and their input-stream transforms -----
        # one ExecutionPlan tuple per declared topology, each padded to the
        # service's vmax so all of them step the same slab; ``self.plans``
        # stays the primary tuple (slab init / router back-compat view)
        if plans is not None and len(names) > 1:
            raise ValueError(
                "prebuilt plans are single-topology — a multi-topology "
                "service builds its own per-skeleton plans from cfg")
        topo_plans: Dict[str, Tuple] = {}
        if plans is None:
            from repro.core.pruning.plan import plan_from_config
            from repro.models import registry
            # the same PRNG keys for every topology: joint-count-free
            # parameters (conv stacks, fc head) come out identical, so the
            # last dispatch of a mixed tick reports every held slot's
            # logits through the same head its own plan would use
            keys = jax.random.split(jax.random.PRNGKey(seed))
            for t in names:
                topo = self._topos[t]
                cfg_t = dataclasses.replace(cfg, gcn_joints=topo.num_joints)
                prune_plan = plan_from_config(cfg_t)
                topo_plans[t] = tuple(
                    engine.build_execution_plan(
                        registry.init_params(cfg_t, k), cfg_t, prune_plan,
                        quant=quant, backend=backend, topology=topo,
                        pad_joints=self.vmax, sconv=sconv, csr_eps=csr_eps)
                    for k in keys)
        else:
            topo_plans[self.primary] = tuple(plans)
        self.plans = topo_plans[self.primary]
        self.vmax = int(self.plans[0].static.joints)

        # --- frozen BN calibration (per topology, shared by every tier) ---
        # each skeleton calibrates at its own joint count (the padded plan
        # slices itself to the clip's width), then the stem stats are
        # padded to the slab width once, so every topology's stats pytree
        # carries identical leaf shapes into the per-group dispatches
        if len(names) > 1 and (bn_stats is not None or x_calib is not None):
            raise ValueError(
                "bn_stats/x_calib override a single topology's calibration "
                "— a multi-topology service calibrates each skeleton from "
                "its own synthetic batch")
        topo_stats: Dict[str, Tuple] = {}
        for t in names:
            plans_t = topo_plans[t]
            topo = self._topos[t]
            transforms = [
                lambda x: x,
                lambda x, p=topo.parents: bone_stream_parents(x, p),
            ][: len(plans_t)]
            if bn_stats is not None:
                st = ((bn_stats,) * len(plans_t)
                      if isinstance(bn_stats, dict) else tuple(bn_stats))
            else:
                xc = x_calib
                if xc is None:
                    from repro.data.pipeline import (DataConfig,
                                                     skeleton_batches)
                    cfg_t = dataclasses.replace(
                        cfg, gcn_joints=topo.num_joints)
                    dcfg = DataConfig(global_batch=4, seq_len=cfg.gcn_frames,
                                      seed=seed)
                    xc = jnp.asarray(next(skeleton_batches(cfg_t, dcfg))["x"])
                st = tuple(
                    engine.collect_bn_stats(p, tf(jnp.asarray(xc)))
                    for p, tf in zip(plans_t, transforms))
            topo_stats[t] = tuple(
                engine._pad_data_bn_stats(s, p.static)
                for s, p in zip(st, plans_t))
        self.bn_stats = topo_stats[self.primary]

        # --- the tick's constant operands, packed -------------------------
        # each topology's plans and frozen BN stats as one flat buffer per
        # dtype (engine.pack_constants): every jitted tick call takes these
        # few buffers in place of hundreds of plan and stats arrays, and
        # unpacks them inside its trace (steps.on_packed_constants)
        self._consts = {t: engine.pack_constants((topo_plans[t],
                                                  topo_stats[t]))
                        for t in names}

        # --- one pristine slab per capacity tier --------------------------
        # tier slabs are never mutated in place (every step/restore is a
        # functional update), so the pool entry a migration reads is always
        # the all-zero init: entering a tier needs no reset pass.  Slabs
        # are bare — per-slot state only, empty ``bn_stats`` — since the
        # statistics ride the packed constants
        self._tier_slabs = {
            S: tuple(engine.init_session_slab(p, S, bn_stats={})
                     for p in self.plans)
            for S in tiers}

        # --- mesh placement (distributed tier) ----------------------------
        # per-slot leaves (all of a bare slab's) shard their leading slot
        # axis across the 1-D mesh; snapshot-ring rows (ring axis, not
        # slot axis) and the packed constants replicate.  One sharding
        # tree per stream serves every tier — specs are shape-independent.
        self._slab_shardings = None   # per-stream StreamState of shardings
        self._ring_sharding = None    # per-stream ring pytree of shardings
        self._row_sharding = None     # (S, ...) leaves, e.g. tick logits
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            row = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
            rep = NamedSharding(mesh, PartitionSpec())

            self._slab_shardings = tuple(
                jax.tree_util.tree_map(lambda _: row, s)
                for s in self._tier_slabs[tiers[0]])
            # ring rows are slot-shaped snapshots (no slot axis) — same
            # pytree structure as ``engine.snapshot_slots``, replicated
            self._ring_sharding = tuple(
                jax.tree_util.tree_map(
                    lambda _: rep, engine.init_snapshot_ring(s, 1))
                for s in self._tier_slabs[tiers[0]])
            self._row_sharding = row
            self._tier_slabs = {
                S: tuple(jax.device_put(s, sh) for s, sh in
                         zip(slabs, self._slab_shardings))
                for S, slabs in self._tier_slabs.items()}
            # the packed constants ride every dispatch: commit them to the
            # mesh too (replicated), so no call re-uploads them, and with
            # them the plans and BN stats, so a one-device mesh pins the
            # whole service to its device
            self._consts = jax.device_put(self._consts, rep)
            self.plans, self.bn_stats = jax.device_put(
                (self.plans, self.bn_stats), rep)
        # the *live* slab is a deep copy, never an alias of a tier entry:
        # the fused tick donates its slab argument (XLA reuses the buffers
        # in place and deletes them Python-side), and a donated alias
        # would destroy the pristine tier slab
        self.slabs = tuple(jax.tree_util.tree_map(jnp.copy, s)
                           for s in self._tier_slabs[tiers[0]])

        # --- scheduler + capacity manager ---------------------------------
        self.fused = bool(fused)
        self.snap_capacity = int(snap_capacity if snap_capacity is not None
                                 else 2 * max(tiers))
        self.saliency: Optional[SaliencyGate] = None
        if saliency_thresh and saliency_thresh > 0.0:
            self.saliency = SaliencyGate(
                SaliencyConfig(threshold=float(saliency_thresh)))
        self.sched = SlabScheduler(
            tiers[0], self.vmax, cfg.gcn_in_channels,
            flush_frames=self.flush_frames,
            first_logit_delay=engine.stream_first_logit_delay(self.plans[0]),
            policy=qos,
            snap_ring=self.snap_capacity if self.fused else None,
            retain=self.retain_records,
            saliency=self.saliency)
        # deadline drops retire through the same bounded window as
        # completions, so service-side bookkeeping stays constant under a
        # miss-heavy load too
        self.sched.on_miss = self._on_miss
        self.policy = policy
        self.capman: Optional[CapacityManager] = None
        self.slo: Optional[SloController] = None
        if policy == "slo":
            # the SLO controller replaces the demand manager outright —
            # one `policy` knob swaps the whole control loop, and it is
            # useful even at a single tier (pure admission control)
            self.slo = SloController(
                slo_config or SloConfig(), tiers=tiers, start_tier=tiers[0],
                latency_floor=self.sched.first_logit_delay)
            self.sched.on_first_logit = self.slo.record_first_logit
        elif len(tiers) > 1:
            ccfg = capacity_config or CapacityConfig(tiers=tiers)
            if tuple(sorted(ccfg.tiers)) != tiers:
                ccfg = dataclasses.replace(ccfg, tiers=tiers)
            self.capman = CapacityManager(ccfg, start_tier=tiers[0])
        # per-tick scheduler-outcome log (golden-test shape; opt-in)
        self.record_outcomes = bool(record_outcomes)
        self.outcomes: List[Dict] = []
        self._shed_tick: List[Dict] = []    # sheds since the last tick
        self._missed_tick: List[int] = []   # misses within this tick
        self._rejected: set = set()         # rejected sids (poll-side)
        self.n_rejected = 0                 # lifetime rejected-open count

        # --- jitted device entry points ------------------------------------
        # under a mesh, every entry point pins its output shardings to the
        # slab/ring placement above: inputs (always the live sharded
        # buffers) and outputs then agree, so donation stays effective and
        # the compiled signature never flip-flops between placements
        step_out = fused_out = migrate_out = None
        if mesh is not None:
            step_out = (self._slab_shardings, self._row_sharding)
            fused_out = (self._slab_shardings, self._row_sharding,
                         self._ring_sharding)
            migrate_out = self._slab_shardings[0]
        self._step = jax.jit(on_packed_constants(make_gcn_slab_step(cfg)),
                             out_shardings=step_out)
        self._snap_fn = jax.jit(engine.snapshot_slots)
        self._rest_fn = jax.jit(engine.restore_slots)
        # the one-dispatch tick: slab and snapshot-ring pytrees are
        # DONATED (argnums 1 and 8) — XLA updates them in place and the
        # Python-side inputs die at the call; tick() must only ever pass
        # buffers it owns (self.slabs / self._rings) and immediately
        # rebind them to the outputs.  The packed constants (argnum 0)
        # are never donated.
        self._fused_tick = jax.jit(
            on_packed_constants(make_gcn_fused_tick(cfg)),
            donate_argnums=(1, 8), out_shardings=fused_out)
        # per-stream on-device snapshot rings (fused path): ring rows are
        # slot-shaped (S-independent), so one ring serves every capacity
        # tier and rides through elastic migrations untouched
        self._rings: Optional[Tuple] = None
        if self.fused:
            self._rings = tuple(
                engine.init_snapshot_ring(s, self.snap_capacity)
                for s in self._tier_slabs[tiers[0]])
            if mesh is not None:
                self._rings = tuple(
                    jax.device_put(r, sh)
                    for r, sh in zip(self._rings, self._ring_sharding))
        # the tier-migration pair fused into one jit: gather rows out of
        # the source slab, scatter into the (pristine) target slab
        self._migrate_fn = jax.jit(
            lambda src, dst, old_idx, new_idx: engine.restore_slots(
                dst, new_idx, engine.snapshot_slots(src, old_idx)),
            out_shardings=migrate_out)
        if mesh is not None:
            # every dispatch runs inside the mesh's axis-rule scope so the
            # engine's logical "batch" constraints resolve at trace time
            self._step = self._under_mesh(self._step)
            self._fused_tick = self._under_mesh(self._fused_tick)
            self._migrate_fn = self._under_mesh(self._migrate_fn)
            self._snap_fn = self._under_mesh(self._snap_fn)
            self._rest_fn = self._under_mesh(self._rest_fn)

        # --- session bookkeeping -------------------------------------------
        self._next_sid = 0
        self._sessions: Dict[int, SessionRequest] = {}
        self._records: Dict[int, SessionRecord] = {}
        self._snaps: Dict[int, Tuple] = {}    # sid -> per-stream snapshots
                                              # (legacy tick path only)
        # retirement window: finished/missed sids in order; once more than
        # retain_records sessions have retired after one, its request/
        # record entries are dropped (lifetime totals live in the
        # scheduler's running aggregates)
        self._retired: deque = deque()
        self._tick = 0
        self._last_logits: Optional[Any] = None   # device array until forced
        # seconds spent in each named phase (TICK_PHASES); wall_host_s and
        # wall_device_s are sums of these
        self.phase_s: Dict[str, float] = dict.fromkeys(TICK_PHASES, 0.0)
        self._span = jax.profiler.TraceAnnotation   # phase span factory
        self.device_dispatches = 0            # jitted calls issued by tick()
        # arrays passed to and returned by tick()'s jitted calls.  Per slab
        # step: the packed constants, both slabs, frames and three masks
        # in; both slabs and the logits out.  The fused tick adds its two
        # order buffers in and its snapshot rings both ways.
        self.call_arrays = 0
        n_slabs = _n_arrays(self.slabs)
        self._step_arrays = {t: len(c.buffers) + 2 * n_slabs + 5
                             for t, c in self._consts.items()}
        self._fused_arrays = (self._step_arrays[self.primary] + 2
                              + 2 * _n_arrays(self._rings))
        self.tier_ticks: Dict[int, int] = {S: 0 for S in tiers}

        if warm:
            self._warm()

    # -- construction helpers ------------------------------------------------

    def _under_mesh(self, fn):
        """Wrap a jitted entry point so every call (hence its trace) runs
        inside the mesh's logical-axis rule scope — the engine's
        ``constrain(x, "batch", ...)`` hints then resolve onto the service
        mesh and the step compiles SPMD.  Only applied when ``mesh`` is
        set; donation semantics pass straight through."""
        import functools

        from repro.distributed.sharding import axis_rules

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with axis_rules(self.mesh):
                return fn(*args, **kwargs)

        return wrapped

    def _retire(self, sid: int) -> None:
        """Enter ``sid`` into the bounded retirement window; the oldest
        retiree beyond ``retain_records`` loses its host-side bookkeeping
        (request, record, legacy snapshot, missed/rejected-sid mirrors) —
        its outcome already lives in the lifetime aggregates."""
        self._retired.append(sid)
        while len(self._retired) > self.retain_records:
            old = self._retired.popleft()
            self._sessions.pop(old, None)
            self._records.pop(old, None)
            self._snaps.pop(old, None)
            self.sched.missed_sids.discard(old)
            self._rejected.discard(old)

    def _on_miss(self, req: SessionRequest) -> None:
        """Scheduler ``on_miss`` hook: retire the dropped session's
        bookkeeping and note the miss in this tick's outcome log."""
        self._retire(req.sid)
        if self.record_outcomes:
            self._missed_tick.append(req.sid)

    def _warm(self) -> None:
        """Compile the active tick path for every tier (plus the preempt
        gather/scatter pair on the legacy path) before traffic arrives —
        post-warmup, no admission/hold/occupancy/event-count combination
        retraces within a tier."""
        jnp, jax = self._jnp, self._jax
        engine = self._engine
        V, C = self.vmax, self.cfg.gcn_in_channels
        for S, slabs in self._tier_slabs.items():
            zf = jnp.zeros((S, V, C))
            zb = jnp.zeros((S,), bool)
            # the no-event tick (fused and legacy paths alike) is the
            # plain slab step
            _, wl = self._step(self._consts[self.primary], slabs, zf, zb,
                               zb, zb)
            jax.block_until_ready(wl)
            # every non-primary skeleton group's dispatch (its own packed
            # plans and BN stats over the same slab shape)
            for t in self.topologies[1:]:
                _, wl = self._step(self._consts[t], slabs, zf, zb, zb, zb)
                jax.block_until_ready(wl)
            if self.fused:
                # the fused event tick donates its slab/ring arguments, so
                # warm it on throwaway copies — never on the pristine tier
                # slabs or the live ring.  One trace per tier covers any
                # event count: the order buffers are traced values of the
                # static (max_events_for(S), 2) shape.
                wslabs = tuple(jax.tree_util.tree_map(jnp.copy, s)
                               for s in slabs)
                wrings = tuple(engine.init_snapshot_ring(
                    s, self.snap_capacity) for s in slabs)
                if self.mesh is not None:
                    # match the live rings' placement so warmup compiles
                    # the same input signature traffic will use
                    wrings = tuple(
                        jax.device_put(r, sh)
                        for r, sh in zip(wrings, self._ring_sharding))
                zo = jnp.asarray(pad_event_orders([], max_events_for(S)))
                out = self._fused_tick(self._consts[self.primary], wslabs,
                                       zf, zb, zb, zb, zo, zo, wrings)
                jax.block_until_ready(out[1])
        if self.qos == "preempt" and not self.fused:
            # the legacy preempt gather/scatter traces per tier shape —
            # warm it at every tier so the first preemption after a grow
            # is free (the fused path carries its events in-dispatch)
            for slabs in self._tier_slabs.values():
                w = tuple(self._snap_fn(s, jnp.asarray(0)) for s in slabs)
                ws = tuple(self._rest_fn(s, jnp.asarray(0), x)
                           for s, x in zip(slabs, w))
                jax.block_until_ready(ws)
        # every ordered tier pair compiles its fixed-shape migration
        # (min(S_old, S_new) rows regardless of occupancy), so a traffic-
        # time grow/shrink never pays trace latency
        for a in self.tiers:
            for b in self.tiers:
                if a == b:
                    continue
                k = min(a, b)
                idx = np.arange(k, dtype=np.int32)   # as _migrate passes it
                out = tuple(self._migrate_fn(sa, sb, idx, idx)
                            for sa, sb in zip(self._tier_slabs[a],
                                              self._tier_slabs[b]))
                jax.block_until_ready(out)

    # -- plan-derived timing --------------------------------------------------

    def flush_frames(self, frames: int) -> int:
        """Flush-drain ticks after a ``frames``-long stream (the per-block
        'same'-padding latency, ``engine.stream_flush_frames``)."""
        return self._engine.stream_flush_frames(self.plans[0], frames)

    @property
    def first_logit_delay(self) -> int:
        """Raw frames from admission to the first valid logit."""
        return self._engine.stream_first_logit_delay(self.plans[0])

    # -- the session protocol -------------------------------------------------

    @property
    def now(self) -> int:
        """The service clock: index of the next tick to run."""
        return self._tick

    @property
    def wall_host_s(self) -> float:
        """Host time inside ``tick()`` less its readback waits: the sum of
        the host phases ``feed + stage + dispatch + drain`` of
        ``phase_s``."""
        return sum(self.phase_s[p] for p in HOST_PHASES)

    @property
    def wall_device_s(self) -> float:
        """Host time blocked on forced logit readback
        (``phase_s["readback"]``), from ``tick()``, ``poll(wait=True)``
        or ``metrics()``.  It is not device time: the device's own time
        is read from a profiler trace."""
        return self.phase_s["readback"]

    @property
    def wall_s(self) -> float:
        """Total serving time: ``wall_host_s`` plus ``wall_device_s``, so
        the sum of every named phase — kept as a property for back-compat
        with the old single counter."""
        return self.wall_host_s + self.wall_device_s

    def _phase(self, name: str) -> _Phase:
        """The context manager of one named phase (see ``TICK_PHASES``)."""
        return _Phase(self.phase_s, name, self._span("svc." + name))

    @property
    def capacity(self) -> int:
        """Current slot capacity (the active tier)."""
        return len(self.sched.slots)

    def open_session(self, *, priority: int = 0,
                     deadline: Optional[int] = None,
                     arrival: Optional[int] = None,
                     topology: Optional[str] = None) -> SessionHandle:
        """Open a new session and enter it into the admission queue.

        The session is *open*: frames arrive via :meth:`submit` and the
        stream ends with :meth:`close` (an admitted session with an empty
        buffer is held in place, never zero-padded).  ``priority`` orders
        admission and selects preemption victims; ``deadline`` is the
        absolute completion-deadline tick under ``qos="deadline"``;
        ``arrival`` backdates the queueing clock (defaults to now);
        ``topology`` declares the session's skeleton (one of the
        service's ``topologies``; default the primary) — its frames are
        (V_topo, C) and are served by that topology's plans.

        Under ``policy="slo"`` every open passes the controller's
        admission gate first: while shedding, an unprotected open is
        *rejected* (the handle polls as ``"rejected"``; it never enters
        the scheduler and its frames are dropped) or *degraded* (served
        at the configured frame-skip stride) per ``shed_mode``."""
        topo = topology or self.primary
        if topo not in self._topos:
            raise ValueError(
                f"unknown topology {topo!r} — this service serves "
                f"{self.topologies}; construct it with topologies=(...) "
                "to add a skeleton")
        sid = self._next_sid
        self._next_sid += 1
        req = SessionRequest(
            sid=sid, arrival=self._tick if arrival is None else int(arrival),
            clip=None, priority=priority, deadline=deadline, topology=topo)
        self._sessions[sid] = req
        if self.slo is not None:
            verdict = self.slo.admit(priority)
            if verdict == "reject":
                # turned away at the door: the queue-forever alternative
                # is exactly what the SLO policy exists to avoid
                self._rejected.add(sid)
                self.n_rejected += 1
                if self.record_outcomes:
                    self._shed_tick.append(
                        {"sid": sid, "mode": "reject"})
                self._retire(sid)
                return SessionHandle(sid=sid)
            if verdict == "degrade":
                req.degrade = self.slo.degrade_stride_now()
                if self.record_outcomes:
                    self._shed_tick.append(
                        {"sid": sid, "mode": "degrade",
                         "stride": req.degrade})
        self.sched.submit(req)
        return SessionHandle(sid=sid)

    def _req(self, h: SessionHandle) -> SessionRequest:
        try:
            return self._sessions[h.sid]
        except KeyError:
            raise KeyError(f"unknown session handle {h!r}") from None

    def submit(self, h: SessionHandle, frame: np.ndarray) -> None:
        """Append one raw (V, C) skeleton frame to the session's stream.
        A no-op on a rejected session (the frames would never be served;
        batch drivers need not special-case the shed path)."""
        if h.sid in self._rejected:
            return
        frame = np.asarray(frame, np.float32)
        req = self._req(h)
        t = req.topology or self.primary
        vt = self._topos[t].num_joints
        if frame.shape != (vt, self.cfg.gcn_in_channels):
            raise ValueError(
                f"expected one ({vt}, {self.cfg.gcn_in_channels}) frame "
                f"for topology {t!r}, got {frame.shape}")
        req.push_frame(frame)

    def submit_clip(self, h: SessionHandle, clip: np.ndarray) -> None:
        """Submit a whole (T, V, C) clip and close the stream — the batch
        convenience over per-frame :meth:`submit` + :meth:`close` (and,
        like them, a no-op on a rejected session)."""
        if h.sid in self._rejected:
            return
        for frame in np.asarray(clip, np.float32):
            self._req(h).push_frame(frame)
        self.close(h)

    def close(self, h: SessionHandle) -> None:
        """End the session's stream.  The scheduler drains the flush
        latency and the final record becomes available via :meth:`poll`.
        A no-op on a rejected session."""
        if h.sid in self._rejected:
            return
        self._req(h).close()

    def poll(self, h: SessionHandle, *, wait: bool = False) -> SessionStatus:
        """Non-blocking status: state, progress and the latest logits.

        For an active/draining session the default returns the logits of
        the most recent *forced* tick — possibly ``None`` right after a
        tick whose async readback is still pending — so a client polling
        every tick costs no device sync (the fused path's readback
        overlap survives the polling).  ``wait=True`` forces the pending
        readback first (the wait is the ``readback`` phase),
        guaranteeing the logits reflect the latest tick."""
        req = self._req(h)
        rec = self._records.get(h.sid)
        if rec is not None:
            return SessionStatus(
                sid=h.sid, state="done", frames_submitted=req.n_frames(),
                frames_consumed=rec.frames, priority=req.priority,
                logits=rec.logits, record=rec)
        if h.sid in self.sched.missed_sids:      # O(1) sid index
            return SessionStatus(
                sid=h.sid, state="missed", frames_submitted=req.n_frames(),
                frames_consumed=0, priority=req.priority)
        if h.sid in self._rejected:              # shed at open, never queued
            return SessionStatus(
                sid=h.sid, state="rejected",
                frames_submitted=req.n_frames(),
                frames_consumed=0, priority=req.priority)
        for s, slot in enumerate(self.sched.slots):
            if slot is not None and slot.req is req:
                # slot.rel counts *effective* (stride-decimated) frames;
                # report consumption in raw frames so clients see clip
                # progress regardless of the fidelity the SLO shed picked
                stride = max(1, int(req.degrade))
                state = ("active" if slot.rel < req.eff_frames()
                         or not req.is_closed() else "draining")
                if wait:
                    self._force_logits()
                logits = (np.asarray(self._last_logits[s])
                          if isinstance(self._last_logits, np.ndarray)
                          else None)
                return SessionStatus(
                    sid=h.sid, state=state, frames_submitted=req.n_frames(),
                    frames_consumed=min(slot.rel * stride, req.n_frames()),
                    priority=req.priority, logits=logits)
        # queued — either never admitted, or a preempted slot awaiting
        # re-admission (which keeps its consumed-frame progress); O(1)
        # sid lookup instead of a queue scan
        item = self.sched.queue.get(h.sid)
        consumed = (min(getattr(item, "rel", 0), req.n_frames())
                    if item is not None else 0)
        return SessionStatus(
            sid=h.sid, state="queued", frames_submitted=req.n_frames(),
            frames_consumed=consumed, priority=req.priority)

    def idle(self) -> bool:
        """True when no session is queued or occupying a slot."""
        return self.sched.idle()

    def advance_clock(self, tick: int) -> None:
        """Fast-forward an idle service to ``tick`` (Poisson lulls cost no
        compute; occupancy accounting weights them as empty).

        The skipped gap is fed to the elastic capacity manager as empty
        demand — enough observations to walk the tier ladder to the
        bottom, followed by **one** physical migration — so a long lull
        shrinks the slab and the first post-lull tick runs at bottom-tier
        cost (an idle elastic service used to stay pinned at whatever
        tier the last burst grew it to)."""
        if not self.idle():
            raise ValueError("cannot fast-forward a busy service")
        tick = int(tick)
        if self.capman is not None and tick > self._tick:
            cc = self.capman.config
            # worst case one full ladder walk: each rung needs its shrink
            # patience plus the post-resize cooldown before the next
            budget = len(self.tiers) * (cc.shrink_patience + cc.cooldown + 1)
            start = self.capman.capacity
            t = self._tick
            while (t < tick and budget > 0
                   and self.capman.capacity > self.tiers[0]):
                self.capman.observe(0, 0, t)
                t += 1
                budget -= 1
            if self.capman.capacity != start:
                self._migrate(self.capman.capacity)
        elif self.slo is not None and tick > self._tick:
            sc = self.slo.config
            # idle means every session drained: drop the stale latency
            # window (it describes a regime that no longer exists and
            # would pin the controller in breach forever), then feed
            # enough empty observations to walk the ladder down
            self.slo.idle_reset()
            budget = len(self.tiers) * (sc.recover_patience + sc.cooldown + 1)
            start = self.slo.capacity
            t = self._tick
            while (t < tick and budget > 0
                   and self.slo.capacity > self.tiers[0]):
                self.slo.observe(0, 0, t, queue_age=0)
                t += 1
                budget -= 1
            if self.slo.capacity != start:
                self._migrate(self.slo.capacity)
        self._tick = max(self._tick, tick)

    # -- the serving tick -----------------------------------------------------

    def _force_logits(self) -> Optional[np.ndarray]:
        """Force the pending tick's logits to host (no-op once forced).

        The fused tick keeps ``_last_logits`` as a device array — a
        future the host only waits on when someone actually reads it
        (``poll``, a finishing session, ``metrics``).  The block is the
        ``readback`` phase (``wall_device_s``): the host waiting on the
        device, kept apart from host scheduling time."""
        if (self._last_logits is not None
                and not isinstance(self._last_logits, np.ndarray)):
            with self._phase("readback"):
                self._last_logits = np.asarray(self._last_logits)
        return self._last_logits

    def _topology_groups(self) -> List[Tuple[str, np.ndarray]]:
        """Partition the slot table by session topology: ``[(name, (S,)
        bool mask), ...]`` with the primary group first (free slots ride
        the primary — their dead-weight step happens exactly once, where
        it always did).  Empty non-primary groups are dropped, so a
        mixed-capable service serving only primary traffic pays no extra
        dispatch."""
        S = len(self.sched.slots)
        masks = {t: np.zeros(S, bool) for t in self.topologies}
        for s, slot in enumerate(self.sched.slots):
            t = self.primary
            if slot is not None and slot.req.topology:
                t = slot.req.topology
            masks[t][s] = True
        out = [(self.primary, masks[self.primary])]
        out += [(t, masks[t]) for t in self.topologies[1:]
                if masks[t].any()]
        return out

    def _step_groups(self, frames, groups, logits):
        """Step each non-primary skeleton group: one plain dispatch per
        group with that topology's packed plans and BN stats over the
        shared slab, everything outside the group held (held slots keep their
        state bit-for-bit and report their running prediction).
        ``groups`` holds each group's staged ``(topology, valid, reset,
        hold)``.  Returns the last dispatch's logits — it covers the whole
        slab, because held rows are recomputed from the post-step pool and
        the fc head is identical across topology plans by construction."""
        for t, valid, reset, hold in groups:
            self.slabs, logits = self._step(
                self._consts[t], self.slabs, frames, valid, reset, hold)
            self.device_dispatches += 1
            self.call_arrays += self._step_arrays[t]
        return logits

    def tick(self) -> List[SessionRecord]:
        """Run one scheduler tick: capacity decision (elastic), QoS policy
        + admissions, snapshot/restore orders, one device dispatch for
        all slots (the donated fused megakernel on event ticks, the plain
        slab step on no-event ticks; or the legacy multi-dispatch
        sequence when ``fused=False``), drain accounting.  Returns the sessions that
        finished this tick (their records are also kept for
        :meth:`poll`).

        On the fused path the logits stay on device: the host queues the
        dispatch and immediately resumes scheduling — the transfer is
        only forced when a session finishes this tick, someone polls, or
        metrics are read, so tick *t*'s device work overlaps tick
        *t+1*'s host-side planning.

        The tick runs as contiguous named phases (``TICK_PHASES``), each
        a ``svc.<phase>`` profiler span timed into ``phase_s``: *feed*
        (controllers, ``tick_inputs``, outcome and group bookkeeping),
        *stage* (host→device inputs), *dispatch* (every jitted call),
        *readback* (a forced readback, when one is due) and *drain*
        (``tick_outputs``, record retirement)."""
        jnp = self._jnp
        with self._phase("feed") as t0:
            if self.capman is not None or self.slo is not None:
                # sweep deadline-expired sessions *before* the controller
                # looks: expired slots/queue entries are not demand,
                # and counting them used to trigger spurious grows
                self.sched.sweep_expired(self._tick)
            if self.slo is not None:
                # the leading-edge breach signal: the oldest queued
                # session's wait so far — a saturated queue never latches
                # first logits, so the p99 window alone would look healthy
                # while everyone starves
                queue_age = max(
                    (self._tick - AdmissionQueue._req(it).arrival
                     for it in self.sched.queue), default=0)
                # the in-flight twin: an admitted-but-unlatched session's
                # first logit cannot land before admission + pipeline
                # delay, so its committed latency is already known —
                # without it, a recovery streak could un-shed while the
                # slab is still full of sessions guaranteed to breach when
                # they latch
                inflight_age = max(
                    (slot.admitted + self.sched.first_logit_delay - 1
                     - slot.req.arrival
                     for slot in self.sched.slots
                     if slot is not None and slot.first_logit_tick < 0),
                    default=0)
                target = self.slo.observe(
                    self.sched.busy(), len(self.sched.queue), self._tick,
                    queue_age=queue_age, inflight_age=inflight_age)
                if target is not None and target != self.capacity:
                    self._migrate(target)
            elif self.capman is not None:
                target = self.capman.observe(
                    self.sched.busy(), len(self.sched.queue), self._tick)
                if target is not None:
                    self._migrate(target)
            tp = self.sched.tick_inputs(self._tick, t0)
            outcome = None
            if self.record_outcomes:
                # pure host ints, no wall times / logits: the per-tick
                # shape the golden replay tests lock byte-for-byte.
                # Captured right after tick_inputs (a tiny degraded
                # session can finish on its own admission tick, freeing
                # the slot before outputs).
                outcome = {
                    "tick": self._tick,
                    "capacity": self.capacity,
                    "busy": self.sched.busy(),
                    "queued": len(self.sched.queue),
                    "admitted": sorted(
                        self.sched.slots[s].req.sid
                        for s in np.flatnonzero(tp.reset)
                        if self.sched.slots[s] is not None),
                    "restored": sorted(sid for _, sid in tp.restore),
                    "preempted": sorted(sid for _, sid in tp.snapshot),
                    "held": int(tp.hold.sum()),
                    "shed": self._shed_tick,
                }
                self._shed_tick = []
            # mixed-skeleton slab: partition the slots by topology.  The
            # primary group carries the events and the free slots; every
            # other group is stepped by its own plans afterwards.  Group
            # masks: valid/reset only inside the group (reset must be
            # group-masked — step_frames resets *before* the hold
            # select), hold everything outside it.  None =
            # single-topology service, which takes exactly the legacy
            # dispatch.
            groups = (self._topology_groups()
                      if len(self.topologies) > 1 else None)
            valid, reset, hold = tp.valid, tp.reset, tp.hold
            if groups is not None:
                mp = groups[0][1]
                valid, reset, hold = valid & mp, reset & mp, hold | ~mp
            # a session finishing this tick needs its logits row before
            # drain accounting, so the readback is forced then; the legacy
            # tick is synchronous and always forces it.  Otherwise the
            # fused path leaves the future pending.
            force = not self.fused or any(
                slot is not None and not slot.held
                and slot.total is not None and slot.rel == slot.total - 1
                for slot in self.sched.slots)
        with self._phase("stage"):
            events = self.fused and bool(tp.snapshot or tp.restore)
            snap_at, rest_at = (), ()
            frames = jnp.asarray(tp.frames)
            masks = (jnp.asarray(valid), jnp.asarray(reset),
                     jnp.asarray(hold))
            if events:
                orders = (jnp.asarray(tp.snap_order),
                          jnp.asarray(tp.rest_order))
            elif not self.fused:
                snap_at = [(jnp.asarray(s), sid) for s, sid in tp.snapshot]
                rest_at = [(jnp.asarray(s), sid) for s, sid in tp.restore]
            group_args = [(t, jnp.asarray(tp.valid & m),
                           jnp.asarray(tp.reset & m),
                           jnp.asarray(tp.hold | ~m))
                          for t, m in (groups or [])[1:]]
        with self._phase("dispatch"):
            if events:
                # event tick — one donated dispatch: snapshot gathers ->
                # restore scatters -> reset/hold-masked slab step, all
                # inside _fused_tick.  self.slabs/self._rings die at this
                # call (donated) and are rebound to the outputs — never
                # re-read the old references.
                self.slabs, logits, self._rings = self._fused_tick(
                    self._consts[self.primary], self.slabs, frames, *masks,
                    *orders, self._rings)
                self.call_arrays += self._fused_arrays
            else:
                # legacy events: capture before restore/step
                for s, sid in snap_at:
                    self._snaps[sid] = tuple(self._snap_fn(slab, s)
                                             for slab in self.slabs)
                    self.device_dispatches += len(self.slabs)
                    self.call_arrays += len(self.slabs) + _n_arrays(
                        (self.slabs, self._snaps[sid]))
                for s, sid in rest_at:
                    snaps = self._snaps.pop(sid)
                    old = self.slabs
                    self.slabs = tuple(
                        self._rest_fn(slab, s, sn)
                        for slab, sn in zip(self.slabs, snaps))
                    self.device_dispatches += len(self.slabs)
                    self.call_arrays += len(old) + _n_arrays(
                        (old, snaps, self.slabs))
                # no-event fused tick (the common case): the plain slab
                # step is the same single dispatch minus the ring
                # plumbing — the fused win here is skipping the per-tick
                # readback, not the kernel shape
                self.slabs, logits = self._step(
                    self._consts[self.primary], self.slabs, frames, *masks)
                self.call_arrays += self._step_arrays[self.primary]
            self.device_dispatches += 1
            if group_args:
                logits = self._step_groups(frames, group_args, logits)
            self._last_logits = logits           # device array; forced lazily
        if force:
            self._force_logits()
        with self._phase("drain") as now:
            done = self.sched.tick_outputs(self._tick, self._last_logits,
                                           now)
            for rec in done:
                self._records[rec.sid] = rec
                # the record holds the outcome; drop the frame payload so
                # a long-lived service doesn't pin every served clip in
                # memory
                self._sessions[rec.sid].release_frames()
                self._retire(rec.sid)
            # (deadline misses release + retire through the scheduler's
            # on_miss hook the moment they are swept)
            if outcome is not None:
                outcome["finished"] = sorted(r.sid for r in done)
                outcome["missed"] = sorted(self._missed_tick)
                self._missed_tick = []
                self.outcomes.append(outcome)
            self.tier_ticks[self.capacity] += 1
            self._tick += 1
        return done

    def run_until_idle(self, max_ticks: int = 100_000) -> int:
        """Tick until every queued/active session has drained; returns the
        number of ticks run.  Raises if the budget is exhausted (an open
        session that is never closed holds its slot forever)."""
        n = 0
        while not self.idle():
            if n >= max_ticks:
                raise RuntimeError(
                    f"service did not drain within {max_ticks} ticks — "
                    "is an open session missing its close()?")
            self.tick()
            n += 1
        return n

    # -- elastic migration ----------------------------------------------------

    def _migrate(self, new_S: int) -> None:
        """Hop capacity tiers: compact the scheduler slot table, gather
        the occupied rows out of the old slabs and scatter them into the
        pristine target-tier slabs.  The gather/scatter is **fixed-shape**
        — always ``min(S_old, S_new)`` rows, occupied first, padded with
        free rows (their stale content lands in *free* target slots, which
        the admission reset zeroes before reuse) — so each ordered tier
        pair reuses one compiled migration regardless of occupancy, and
        :meth:`_warm` pre-compiles every pair.  Same primitives as QoS
        preemption, so the migrated-session parity invariant is the
        preemption invariant."""
        jax = self._jax
        t0 = time.monotonic()
        S_old = self.capacity
        occupied = [s for s, slot in enumerate(self.sched.slots)
                    if slot is not None]
        mapping = self.sched.resize(new_S)
        free = [s for s in range(S_old) if s not in mapping]
        k = min(S_old, new_S)
        # host int32 index arrays: an eager jnp conversion would compile
        # a one-off program at the first migration, after warm-up
        old_idx = np.asarray((occupied + free)[:k], np.int32)
        new_idx = np.arange(k, dtype=np.int32)     # == mapped targets
        new_slabs = tuple(
            self._migrate_fn(slab, nsl, old_idx, new_idx)
            for slab, nsl in zip(self.slabs, self._tier_slabs[new_S]))
        jax.block_until_ready(new_slabs)
        self.slabs = new_slabs
        # _last_logits is NOT remapped: _migrate only runs inside tick(),
        # which overwrites it with the step's fresh logits before any
        # poll() can observe the stale rows
        ctrl = self.capman if self.capman is not None else self.slo
        if ctrl is not None and ctrl.events:
            ctrl.events[-1].wall_ms = (time.monotonic() - t0) * 1e3

    # -- cross-replica migration ----------------------------------------------

    def export_session(self, h: SessionHandle) -> Dict:
        """Drain one live session out of this service so another replica
        can adopt it — the router's rebalance primitive.

        Returns a host-side package: the session's scheduler item (the
        request, or the in-flight slot bookkeeping) plus per-stream numpy
        snapshots of its device state (``engine.snapshot_slots`` shape;
        None when the session was never admitted and has no device
        state).  The session stops existing here: its slot/queue entry
        and per-sid bookkeeping are dropped, bystander slots untouched.
        Finished or missed sessions cannot be exported.  The locked
        parity invariant (tests/test_distributed.py): exporting at any
        tick and resuming via :meth:`import_session` on another replica
        reproduces the uninterrupted run's logits ≤1e-3, and bystanders
        on both replicas are bit-identical."""
        jax, jnp = self._jax, self._jnp
        req = self._req(h)
        sid = h.sid
        if sid in self._records or sid in self.sched.missed_sids:
            raise ValueError(
                f"session {sid} already finished — nothing to export")
        item: Any = None
        snaps: Optional[Tuple] = None
        for s, slot in enumerate(self.sched.slots):
            if slot is not None and slot.req is req:
                # active: its live state is slab row s — same gather as a
                # preemption capture, then the slot is freed (admission
                # reset zeroes the stale row before reuse)
                snaps = tuple(
                    jax.device_get(self._snap_fn(slab, jnp.asarray(s)))
                    for slab in self.slabs)
                self.sched.slots[s] = None
                item = slot
                break
        if item is None:
            item = self.sched.queue.remove(sid)
            if item is None:
                raise ValueError(f"session {sid} is in no exportable state")
            if item is not req:
                # a preempted slot awaiting re-admission: its device state
                # is a ring row (fused) or a host snapshot tuple (legacy)
                if self.fused:
                    row = self.sched.ring_release(sid)
                    snaps = tuple(
                        jax.device_get(jax.tree_util.tree_map(
                            lambda leaf: leaf[row], ring))
                        for ring in self._rings)
                else:
                    snaps = tuple(jax.device_get(sn)
                                  for sn in self._snaps.pop(sid))
        self._sessions.pop(sid, None)
        return {"item": item, "snaps": snaps}

    def import_session(self, package: Dict) -> SessionHandle:
        """Adopt a session exported from another replica.

        The package's scheduler item re-enters the admission queue under
        a fresh local sid (the handle returned here supersedes the
        origin replica's).  A package carrying device snapshots uploads
        them first — into a snapshot-ring row (fused) or the host
        snapshot table (legacy) — so the next admission restores the
        session exactly like a local preemption resume: same ring
        phases, same block clocks, same running pool."""
        jax, jnp = self._jax, self._jnp
        item = package["item"]
        snaps = package["snaps"]
        req = item if isinstance(item, SessionRequest) else item.req
        if req.topology and req.topology not in self._topos:
            raise ValueError(
                f"cannot adopt a {req.topology!r} session — this replica "
                f"serves {self.topologies}")
        sid = self._next_sid
        self._next_sid += 1
        req.sid = sid
        self._sessions[sid] = req
        if snaps is not None:
            if self.fused:
                row = self.sched.ring_adopt(sid)
                self._rings = tuple(
                    jax.tree_util.tree_map(
                        lambda r, sv: r.at[row].set(jnp.asarray(sv, r.dtype)),
                        ring, sn)
                    for ring, sn in zip(self._rings, snaps))
                if self.mesh is not None:
                    # keep the rings on their replicated mesh placement so
                    # the fused tick's compiled signature never changes
                    self._rings = tuple(
                        jax.device_put(r, sh)
                        for r, sh in zip(self._rings, self._ring_sharding))
            else:
                self._snaps[sid] = tuple(snaps)
        self.sched.queue.push(item)
        return SessionHandle(sid=sid)

    # -- metrics --------------------------------------------------------------

    def metrics(self, *, keep_records: Optional[int] = None) -> Dict:
        """Aggregate serving metrics over everything served so far — the
        row shape merged into ``BENCH_sessions.json`` (fps, per-priority
        latency p50/p99, occupancy both ways, first-logit delay, QoS and
        elastic-capacity accounting) plus recent completed
        :class:`SessionRecord`\\ s under ``"records"``.

        Totals (``sessions``, ``deadline_missed``, occupancy, mean queue
        wait) come from lifetime running aggregates; percentile fields are
        computed over the retention window (the most recent
        ``retain_records`` completions).  ``keep_records`` bounds the
        returned record list further (``0`` drops it entirely — the
        long-lived-service polling shape); None returns the whole window.

        Reading metrics forces any pending async logits first, so
        ``wall_device_s`` settles before the row is built."""
        self._force_logits()
        sched, wall = self.sched, self.wall_s
        recs = list(sched.completed)
        lat = np.asarray([r.wall_finished - r.wall_admitted for r in recs])
        first = np.asarray([r.wall_first_logit - r.wall_admitted
                            for r in recs if r.wall_first_logit >= 0])
        no_first = sum(r.wall_first_logit < 0 for r in recs)
        # per-class latency, both anchors: service time (admission→finish,
        # wall ms) and end-to-end (arrival→finish, scheduler ticks — queue
        # wait and preemption requeues included, which is where the QoS
        # policies differ; tick-denominated so the comparison is
        # deterministic, not wall noise)
        by_prio: Dict[str, Dict[str, float]] = {}
        for p in sorted({r.priority for r in recs}):
            pl = np.asarray([r.wall_finished - r.wall_admitted
                             for r in recs if r.priority == p])
            pt = np.asarray([r.finished - r.arrival
                             for r in recs if r.priority == p], np.float64)
            # first-logit latency in scheduler ticks (arrival -> latch):
            # the SLO's own denomination, per class — the number the
            # controller is judged on
            ft = np.asarray([r.first_logit_tick - r.arrival
                             for r in recs
                             if r.priority == p and r.first_logit_tick >= 0],
                            np.float64)
            by_prio[str(p)] = {
                "n": int(len(pl)),
                "p50_ms": float(np.percentile(pl, 50) * 1e3),
                "p99_ms": float(np.percentile(pl, 99) * 1e3),
                "e2e_p50_ticks": float(np.percentile(pt, 50)),
                "e2e_p99_ticks": float(np.percentile(pt, 99)),
                "first_logit_p50_ticks": (float(np.percentile(ft, 50))
                                          if len(ft) else -1.0),
                "first_logit_p99_ticks": (float(np.percentile(ft, 99))
                                          if len(ft) else -1.0),
                "degraded": int(sum(r.degrade > 1 for r in recs
                                    if r.priority == p)),
            }
        n_missed = sched.n_missed
        ticks = self._tick
        # occ_sum/occ_ticks are lifetime aggregates over *processed* ticks
        # only; the true time-weighted occupancy counts fast-forwarded
        # idle gaps as zero (ticks spans the whole serving window, gaps
        # included)
        occ_busy = float(sched.occ_sum / max(sched.occ_ticks, 1))
        occ_time = float(sched.occ_sum / max(ticks, 1))
        ctrl = self.capman if self.capman is not None else self.slo
        events = ctrl.events if ctrl is not None else []
        out = {
            "backend": self.backend,
            "slots": self.tiers[0],
            "mesh": self.mesh.size if self.mesh is not None else 1,
            "topologies": ",".join(self.topologies),
            "joints": self.vmax,
            "qos": self.qos,
            "policy": self.policy,
            "capacity": ("fixed" if len(self.tiers) == 1 else
                         "elastic:" + ",".join(str(t) for t in self.tiers)),
            "sessions": sched.n_completed,
            "ticks": ticks,
            "wall_s": wall,
            "wall_host_s": self.wall_host_s,
            "wall_device_s": self.wall_device_s,
            "tick_path": "fused" if self.fused else "legacy",
            "device_dispatches": self.device_dispatches,
            "call_arrays": self.call_arrays,
            "frames_per_s": sched.valid_frames / wall if wall > 0 else 0.0,
            "ticks_per_s": ticks / wall if wall > 0 else 0.0,
            "occupancy": occ_time,
            "occupancy_busy": occ_busy,
            "latency_ms_p50": (float(np.percentile(lat, 50) * 1e3)
                               if len(lat) else 0.0),
            "latency_ms_p99": (float(np.percentile(lat, 99) * 1e3)
                               if len(lat) else 0.0),
            "latency_ms_by_priority": by_prio,
            "first_logit_ms_p50": (float(np.percentile(first, 50) * 1e3)
                                   if len(first) else 0.0),
            "first_logit_frames": self.first_logit_delay,
            "sessions_no_first_logit": int(no_first),
            "queue_wait_ticks_mean": (sched.qwait_sum / sched.n_completed
                                      if sched.n_completed else 0.0),
            "preemptions": sched.preemptions,
            "restores": sched.restores,
            "deadline_missed": n_missed,
            "deadline_miss_rate": (
                n_missed / (n_missed + sched.n_completed)
                if (n_missed + sched.n_completed) else 0.0),
            "capacity_final": self.capacity,
            "migrations": len(events),
            "migrations_grow": sum(e.new > e.old for e in events),
            "migrations_shrink": sum(e.new < e.old for e in events),
            "migration_ms_mean": (float(np.mean([e.wall_ms for e in events]))
                                  if events else 0.0),
            # the tier walk itself (tick-denominated, wall-free) — what
            # the golden trace tests lock alongside the outcome log
            "resize_events": [[e.tick, e.old, e.new] for e in events],
            "tier_ticks": {str(S): n for S, n in self.tier_ticks.items()},
            "records": (recs if keep_records is None
                        else recs[len(recs) - min(keep_records, len(recs)):]),
        }
        # adaptive-streaming axes ride the row ONLY when enabled, so every
        # feature-off row (and the tracked legacy BENCH artifacts) stays
        # byte-identical; bench_key defaults the absent keys to off
        if getattr(self.cfg, "use_ck", False):
            out["ck"] = True
        if self.saliency is not None:
            gate = self.saliency
            out["saliency"] = gate.config.threshold
            out["frames_scored"] = gate.frames_scored
            out["frames_skipped"] = gate.frames_skipped
            out["frames_skipped_finished"] = sched.frames_skipped
            out["skip_rate"] = (gate.frames_skipped / gate.frames_scored
                                if gate.frames_scored else 0.0)
            # the headline: sessions one slab-slot-tick buys — a gated run
            # packs more sessions into the same slab * tick budget
            out["sessions_per_slot_tick"] = (
                sched.n_completed / (self.capacity * max(sched.occ_ticks, 1)))
        if self.slo is not None:
            out["slo_target_p99_ticks"] = self.slo.config.target_p99_ticks
            out["shed_mode"] = self.slo.config.shed_mode
            out["shed_rejected"] = self.slo.shed_rejected
            out["shed_degraded"] = self.slo.shed_degraded
            out["shed_windows"] = self.slo.shed_windows
            out["sessions_rejected"] = self.n_rejected
            out["sessions_degraded"] = int(
                sum(r.degrade > 1 for r in recs))
        return out


# ---------------------------------------------------------------------------
# the batch serving driver (serve sessions / BENCH rows)
# ---------------------------------------------------------------------------

def run_sessions(
    cfg,
    *,
    slots: int = 8,
    n_sessions: int = 16,
    mean_interarrival: float = 8.0,
    lengths: Optional[Sequence[int]] = None,
    backend: str = "reference",
    quant: bool = True,
    seed: int = 0,
    max_ticks: int = 100_000,
    qos: str = "fifo",
    preempt_ratio: float = 0.25,
    deadline_slack: int = 25,
    priorities: Optional[Sequence[int]] = None,
    capacity_tiers: Optional[Sequence[int]] = None,
    load: str = "poisson",
    fused: bool = True,
    mesh: int = 0,
    policy: str = "demand",
    slo_config: Optional[SloConfig] = None,
    topology: Optional[str] = None,
    rng: Optional[np.random.Generator] = None,
    use_ck: bool = False,
    saliency_thresh: float = 0.0,
) -> Dict:
    """Serve ``n_sessions`` generated skeleton sessions through a
    :class:`GcnService` with the two-stream (joint + bone) ensemble.

    The batch driver over the session-handle API: each arrival becomes
    ``open_session`` + ``submit_clip``; idle stretches fast-forward the
    service clock.  ``capacity_tiers`` switches the service elastic (one
    slab per tier, hysteresis grow/shrink + migration); ``slots`` alone is
    a fixed-capacity run.  ``load`` selects the arrival process:
    ``"poisson"`` (steady, ``mean_interarrival``) or ``"burst"`` (bursty
    peaks and lulls — the elastic stress shape).  ``preempt_ratio`` sets
    the load generator's high-priority mix (priority 1 vs 0) under every
    policy — same seed, same labels, so a fifo run baselines the preempt
    run directly; under ``qos="deadline"`` each session's completion
    deadline is its minimal service time (clip + flush) plus
    ``deadline_slack`` ticks past arrival.  ``mesh`` > 1 runs the slab
    sharded across that many devices (a 1-D batch mesh; the row gains a
    ``collective_ms_per_tick`` estimate).  ``policy`` selects the
    capacity controller (``"demand"`` | ``"slo"``, knobs via
    ``slo_config``); ``rng`` threads an explicit generator into the load
    generators (``default_rng(seed)`` otherwise — numpy's global state is
    never touched, so concurrent runs can't cross-contaminate);
    ``topology`` serves the whole run on a named registry skeleton
    (``ntu50``, ``hand21``, ...) — clips are generated at that skeleton's
    joint count (None = the default ``ntu25``).  ``use_ck`` switches the
    model to the windowed data-dependent C_k graph
    (``repro.core.agcn.adaptive``; a config whose ``ck_form`` is the
    published whole-clip ``"clip"`` is refused, as by
    :class:`GcnService`) and ``saliency_thresh`` > 0 gates
    uninformative frames (``repro.serving.saliency``) — the two
    adaptive-streaming knobs, tagged onto the row only when on.  Returns
    the :meth:`GcnService.metrics` dict (also the row merged into
    ``BENCH_sessions.json`` by ``serve sessions``)."""
    from repro.data.pipeline import DataConfig, skeleton_batches

    mesh_obj = None
    if mesh and mesh > 1:
        from repro.distributed.serving import make_batch_mesh
        mesh_obj = make_batch_mesh(mesh)
    tiers = tuple(capacity_tiers) if capacity_tiers else (slots,)
    if use_ck and not cfg.use_ck:
        cfg = dataclasses.replace(cfg, use_ck=True)
    svc = GcnService(cfg, backend=backend, qos=qos, capacity_tiers=tiers,
                     policy=policy, slo_config=slo_config,
                     topologies=(topology,) if topology else ("ntu25",),
                     quant=quant, seed=seed, fused=fused, mesh=mesh_obj,
                     saliency_thresh=saliency_thresh)

    if lengths is None:
        lengths = (cfg.gcn_frames, max(2, cfg.gcn_frames // 2))
    # clips are generated at the served skeleton's own joint count (the
    # scheduler zero-pads them to the slab width at tick time)
    vt = svc._topos[svc.primary].num_joints
    cfg_clips = (dataclasses.replace(cfg, gcn_joints=vt)
                 if vt != cfg.gcn_joints else cfg)
    pool = np.asarray(next(skeleton_batches(
        cfg_clips, DataConfig(global_batch=n_sessions,
                              seq_len=cfg.gcn_frames,
                              seed=seed + 1)))["x"])

    def clip_source(sid: int, T: int) -> np.ndarray:
        return pool[sid % len(pool), :T]

    # the priority mix applies under every policy (same seed -> identical
    # labels), so a fifo run is the directly comparable baseline for the
    # preempt run: priority admission without preemption
    if load == "burst":
        reqs = bursty_arrivals(
            n_sessions, lengths, vt, cfg.gcn_in_channels,
            burst_gap=max(1.0, mean_interarrival / 8.0),
            lull_gap=mean_interarrival * 8.0,
            seed=seed, clip_source=clip_source, priorities=priorities,
            high_priority_ratio=preempt_ratio, rng=rng)
    elif load == "poisson":
        reqs = poisson_arrivals(
            n_sessions, mean_interarrival, lengths,
            vt, cfg.gcn_in_channels, seed=seed,
            clip_source=clip_source, priorities=priorities,
            high_priority_ratio=preempt_ratio, rng=rng)
    else:
        raise ValueError(f"unknown load {load!r} (poisson | burst)")
    if qos == "deadline":
        for r in reqs:
            r.deadline = (r.arrival + len(r.clip)
                          + svc.flush_frames(len(r.clip)) + deadline_slack)

    pending = deque(reqs)
    while svc.now < max_ticks:
        while pending and pending[0].arrival <= svc.now:
            r = pending.popleft()
            h = svc.open_session(priority=r.priority, deadline=r.deadline,
                                 arrival=r.arrival)
            svc.submit_clip(h, r.clip)
        if svc.idle():
            if not pending:
                break
            svc.advance_clock(pending[0].arrival)   # fast-forward the lull
            continue
        svc.tick()

    out = svc.metrics()          # "slots" = the service's (sorted) base tier
    out["load"] = load
    if mesh_obj is not None:
        from repro.distributed.serving import collective_cost_ms
        out["collective_ms_per_tick"] = collective_cost_ms(svc)
    return out
