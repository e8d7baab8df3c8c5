"""Open-loop session traffic against ``replicas`` one-chip replicas of
``GcnService`` behind a ``ReplicaRouter`` (``"kind":
"routed_open_loop"``).

The traffic and its accounting are :mod:`benchlib.kinds.open_loop`'s,
run unchanged: N one-body sessions at ``frame_hz`` after a lead-in, every
session read ``reads_per_session`` times and compared.  Only the service
it drives differs: ``ReplicaRouter.build`` places replica i on chip i,
each with one ``qos`` tier of N / ``replicas`` slots, sharing one set of
plans and one BN calibration, and the sessions are opened through the
router's feedback placement (least loaded replica first), so each
replica holds N / ``replicas`` of them.  There is no rebalance and no
replica loss in the window.

One host thread drives the router: a router tick runs every replica's
service tick in turn (``ReplicaRouter.tick``), and the loop's forced
readback waits for every replica's logits, so a frame's latency runs to
the end of the router tick that consumed it.
"""
from __future__ import annotations

import contextlib

from benchlib import program
from benchlib.kinds import open_loop


class Routed:
    """The service the open loop drives (``submit``, ``tick``, ``poll``,
    ``wall_host_s``, ...), backed by a ``ReplicaRouter``.  A forced poll
    (``wait=True``) forces the latest logits of every replica."""

    def __init__(self, router):
        self.router = router
        self._probe = {}        # replica -> a session handle on it

    def open_session(self):
        h = self.router.open_session()
        self._probe.setdefault(self.router.replica_of(h), h)
        return h

    def submit(self, h, frame) -> None:
        self.router.submit(h, frame)

    def tick(self) -> None:
        self.router.tick()

    def poll(self, h, *, wait: bool = False):
        if wait:
            for p in self._probe.values():
                self.router.poll(p, wait=True)
        return self.router.poll(h)

    @property
    def wall_host_s(self) -> float:
        """Host seconds inside every replica's ``tick()``, summed."""
        return sum(float(s.wall_host_s) for s in self.router.services)

    @property
    def first_logit_delay(self) -> int:
        return self.router.services[0].first_logit_delay


def build(cfg, plans, stats, sessions: int, qos: str,
          replicas: int) -> Routed:
    """``replicas`` services of ``sessions / replicas`` slots each behind
    one router, on the given plans and calibration; nothing is warmed."""
    from repro.distributed.router import ReplicaRouter

    if sessions % replicas:
        raise ValueError(f"{sessions} sessions do not split evenly over "
                         f"{replicas} replicas")
    return Routed(ReplicaRouter.build(
        cfg, replicas=replicas, backend=plans[0].static.backend, qos=qos,
        capacity_tiers=(sessions // replicas,), plans=plans,
        bn_stats=stats, warm=False))


@contextlib.contextmanager
def routed_service(replicas: int):
    """While open, the program's one-tier service is ``replicas``
    replicas behind a router, and the slab program compiled for its
    kernel counts is one replica's."""
    service, slab_program = program.service, program.slab_program

    def routed(cfg, plans, stats, slots, qos):
        return build(cfg, plans, stats, slots, qos, replicas)

    def one_replica(cfg, plans, stats, slots):
        return slab_program(cfg, plans, stats, slots // replicas)

    program.service, program.slab_program = routed, one_replica
    try:
        yield
    finally:
        program.service, program.slab_program = service, slab_program


def run(conf, tr, seed, seconds, traced, trace_dir, log, t_start):
    with routed_service(int(tr["replicas"])):
        return open_loop.run(conf, tr, seed, seconds, traced, trace_dir,
                             log, t_start)
