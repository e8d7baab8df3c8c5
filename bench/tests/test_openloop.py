"""Open-loop latency accounting under a fake clock: a frame's latency runs
from when it was due, so a stalled tick delays every frame due behind it."""
import types

import numpy as np
import pytest

from benchlib.openloop import OpenLoop


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += max(0.0, dt)


class FakeService:
    """Consumes one pending frame per session per tick; each tick costs the
    next duration of ``durations`` on the fake clock."""

    def __init__(self, clock, n, durations):
        self.clock, self.durations = clock, list(durations)
        self.buf = [0] * n
        self.done = [0] * n
        self.wall_host_s = 0.0
        self.ticks = 0

    def submit(self, h, frame):
        self.buf[h] += 1

    def tick(self):
        for i in range(len(self.buf)):
            if self.buf[i] > self.done[i]:
                self.done[i] += 1
        d = self.durations[min(self.ticks, len(self.durations) - 1)]
        self.ticks += 1
        self.clock.t += d
        self.wall_host_s += d / 2

    def poll(self, h, wait=False):
        return types.SimpleNamespace(frames_consumed=self.done[h],
                                     logits=np.zeros(3))


def _run(durations, period=0.1, phases=(0.0, 0.05), seconds=2.0):
    clock = FakeClock()
    svc = FakeService(clock, len(phases), durations)
    loop = OpenLoop(svc, range(len(phases)),
                    lambda ids, ks: np.zeros((len(ids), 2, 3), np.float32),
                    np.array(phases), period, clock, clock.sleep)
    ws, we = 0.5, 0.5 + seconds
    loop.run(0.0, ws, we, we + 5.0, True, {0: [1.0, 1.5]}, {})
    return loop, loop.summary(ws, we), svc


def test_steady_ticks_answer_within_one_tick():
    loop, s, svc = _run([0.01])
    assert s["due"] == s["answered"] == 40
    assert s["latency_s"].max() == pytest.approx(0.01, abs=1e-9)
    assert list(loop.consumed) == svc.done
    assert [r[0] for r in loop.samples] == [0, 0]
    assert 0 < loop.samples[0][1] < loop.samples[1][1]


def test_a_stalled_tick_delays_every_frame_due_behind_it():
    # ticks before the window are quick; the first tick inside the window
    # stalls for 0.5 s, so every frame that came due while it ran waits
    # for it and then for the tick that consumes it
    quick = [0.01] * 10
    phases = (0.003, 0.053)      # no frame comes due exactly at a tick
    loop, s, _ = _run(quick + [0.5] + [0.01] * 1000, phases=phases)
    t = np.array(loop.ticks)
    stall = t[(t[:, 1] - t[:, 0]) > 0.4][0]
    lat = s["latency_s"]
    assert s["due"] == s["answered"]
    # every frame due while the stall ran waits at least until it ends
    # (the queued ones longer: each later tick answers one per session);
    # without the stall no frame waits more than one quick tick
    due = np.sort(np.concatenate([p + np.arange(50) * 0.1 for p in phases]))
    behind = due[(due >= stall[0]) & (due < stall[1])]
    assert len(behind) >= 8
    floor = np.sort(stall[1] - behind)[::-1]
    got = np.sort(lat)[::-1][:len(behind)]
    assert np.all(got >= floor - 1e-9)
    _, calm, _ = _run([0.01], phases=phases)
    assert calm["latency_s"].max() <= 0.01 + 1e-9
    assert lat.max() == pytest.approx(0.5 + 0.01, abs=0.06)


def test_generator_lateness_is_recorded():
    _, s, _ = _run([0.03])
    assert s["lateness_s"].size == 40
    assert 0.0 <= s["lateness_s"].max() <= 0.03 + 1e-9
