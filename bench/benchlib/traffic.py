"""Inputs from the seed: skeleton frames for open-loop sessions (on the
host, since the service takes host frames) and clip batches (on the
device).  Every joint coordinate moves as a sinusoid about a rest value,
with the rest pose, amplitude, frequency and phase drawn per row from the
seed, so the same seed gives the same frames and every seed the same
shapes and arrival pattern."""
from __future__ import annotations

import numpy as np

MOTION_HZ = (0.2, 2.0)      # joint oscillation frequencies
AMPLITUDE = (0.05, 0.3)     # per-coordinate motion amplitude


class SessionFrames:
    """Frame ``k`` of session ``i``, for ``rows`` sessions of (V, C) frames
    sampled at ``frame_hz``."""

    def __init__(self, seed: int, rows: int, joints: int, channels: int,
                 frame_hz: float):
        rng = np.random.default_rng([seed, 0x5E55])
        shape = (rows, joints, channels)
        self.base = rng.normal(0.0, 0.5, shape)
        self.amp = rng.uniform(*AMPLITUDE, shape)
        self.omega = 2 * np.pi * rng.uniform(*MOTION_HZ, shape) / frame_hz
        self.phi = rng.uniform(0.0, 2 * np.pi, shape)

    def frames(self, ids, ks) -> np.ndarray:
        """(n, V, C) float32: frame ``ks[j]`` of session ``ids[j]``."""
        ids = np.asarray(ids)
        k = np.asarray(ks, np.float64)[:, None, None]
        return (self.base[ids] + self.amp[ids] * np.sin(
            self.omega[ids] * k + self.phi[ids])).astype(np.float32)

    def clips(self, ids, length: int) -> np.ndarray:
        """(len(ids), length, V, C) float32: the first ``length`` frames
        of each session."""
        ids = np.asarray(ids)
        k = np.arange(length, dtype=np.float64)[None, :, None, None]
        sel = (slice(None), None)
        return (self.base[ids][sel] + self.amp[ids][sel] * np.sin(
            self.omega[ids][sel] * k + self.phi[ids][sel])).astype(np.float32)


def clip_batch(key, rows: int, frames: int, joints: int, channels: int,
               frame_hz: float = 30.0):
    """(rows, frames, V, C) float32 clip batch made on the device from a
    PRNG key (jit it; rows 2i and 2i+1 are the two persons of clip i)."""
    import jax
    import jax.numpy as jnp

    kb, ka, kf, kp = jax.random.split(key, 4)
    shape = (rows, 1, joints, channels)
    base = 0.5 * jax.random.normal(kb, shape)
    amp = jax.random.uniform(ka, shape, minval=AMPLITUDE[0],
                             maxval=AMPLITUDE[1])
    omega = 2 * jnp.pi * jax.random.uniform(
        kf, shape, minval=MOTION_HZ[0], maxval=MOTION_HZ[1]) / frame_hz
    phi = jax.random.uniform(kp, shape, maxval=2 * jnp.pi)
    t = jnp.arange(frames, dtype=jnp.float32)[None, :, None, None]
    return base + amp * jnp.sin(omega * t + phi)


def phases(seed: int, sessions: int, period: float) -> np.ndarray:
    """Each session's frame phase, uniform in [0, period)."""
    return np.random.default_rng([seed, 0xF4A5E]).uniform(0.0, period,
                                                          sessions)
