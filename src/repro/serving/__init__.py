"""`repro.serving` — the multi-session GCN serving package.

Public surface (snapshotted in ``docs/api_surface.txt`` and gated by
``tools/check_api.py``):

* :class:`GcnService` — the session-handle facade
  (``open_session``/``submit``/``poll``/``close`` + ``tick``), owning the
  compiled plans, the per-tier session slabs, QoS and elastic capacity.
* :func:`run_sessions` — the batch driver (Poisson/bursty load through a
  service; the ``serve sessions`` / BENCH row path).
* :class:`SlabScheduler`, :class:`AdmissionQueue`, :class:`TickPlan`,
  :class:`SessionRequest`, :class:`SessionRecord` — scheduling internals
  (host-side, jax-free), importable for tests and custom drivers.
* :class:`CapacityManager`, :class:`CapacityConfig` — the demand-driven
  elastic-tier decision logic (``policy="demand"``).
* :class:`SloController`, :class:`SloConfig` — the SLO-closed-loop
  controller (``policy="slo"``): grow on measured p99 first-logit
  regression, shed (reject/degrade) via admission control at the top
  tier.
* :func:`poisson_arrivals`, :func:`bursty_arrivals` — load generators;
  :class:`TrafficConfig`, :class:`TraceGenerator`, :func:`generate_trace`
  — the richer traffic model (diurnal cycle, flash crowds, heavy-tailed
  lengths) emitting serializable :class:`TraceEvent` records.
* :class:`Trace`, :func:`replay` — the deterministic trace-replay
  harness: feed a recorded trace byte-identically into any service
  configuration (the ``serve sessions --trace`` / golden-test path).
* :func:`write_bench`, :func:`bench_key` — BENCH_sessions.json row merge."""
from repro.serving.capacity import (CapacityConfig, CapacityManager,
                                    ResizeEvent)
from repro.serving.scheduler import (DEFAULT_BENCH_PATH, QOS_POLICIES,
                                     AdmissionQueue, SessionRecord,
                                     SessionRequest, SlabScheduler,
                                     TickPlan, bench_key, bursty_arrivals,
                                     poisson_arrivals, write_bench)
from repro.serving.service import (SESSION_STATES, GcnService,
                                   SessionHandle, SessionStatus,
                                   run_sessions)
from repro.serving.slo import (CONTROL_POLICIES, SHED_MODES, SloConfig,
                               SloController)
from repro.serving.traffic import (LENGTH_DISTS, TRACE_SCHEMA_VERSION,
                                   Trace, TraceEvent, TraceGenerator,
                                   TrafficConfig, event_clip,
                                   generate_trace, outcome_digest, replay,
                                   trace_requests)

__all__ = [
    "AdmissionQueue",
    "CONTROL_POLICIES",
    "CapacityConfig",
    "CapacityManager",
    "DEFAULT_BENCH_PATH",
    "GcnService",
    "LENGTH_DISTS",
    "QOS_POLICIES",
    "ResizeEvent",
    "SESSION_STATES",
    "SHED_MODES",
    "SessionHandle",
    "SessionRecord",
    "SessionRequest",
    "SessionStatus",
    "SlabScheduler",
    "SloConfig",
    "SloController",
    "TRACE_SCHEMA_VERSION",
    "TickPlan",
    "Trace",
    "TraceEvent",
    "TraceGenerator",
    "TrafficConfig",
    "bench_key",
    "bursty_arrivals",
    "event_clip",
    "generate_trace",
    "outcome_digest",
    "poisson_arrivals",
    "replay",
    "run_sessions",
    "trace_requests",
    "write_bench",
]
