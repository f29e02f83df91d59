"""Published peaks of each chip the benchmark may run on, keyed by
``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): per chip 197 TFLOP/s in bfloat16, 394 TOP/s in int8, 16 GB of HBM
at 819 GB/s.  A device that is not in the table is an error: a share of
a peak is never taken against a guess.
"""

from __future__ import annotations

__all__ = ["PEAKS", "peak"]

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
