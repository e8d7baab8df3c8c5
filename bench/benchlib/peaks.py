"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports
it.  Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/
docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.  A
kind missing here is an error, never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises on an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None
