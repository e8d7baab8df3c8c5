"""Production mesh construction.  A FUNCTION (not a module-level constant)
so importing this module never touches jax device state."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_smoke_mesh():
    """1-device mesh with the production axis names (CPU tests)."""
    return jax.make_mesh((1, 1), ("data", "model"))


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 394 TOP/s int8,
# 819 GB/s HBM, 1,600 Gbit/s of interconnect over four links).
PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,     # FLOP/s
        "ops_int8": 394e12,       # OP/s
        "hbm_bw": 819e9,          # B/s
        "ici_bw": 50e9,           # B/s per link
    },
}


def peak_rates(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``.  A kind missing
    from :data:`PEAKS` raises: no device borrows another's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})") from None
