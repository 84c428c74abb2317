"""Published peaks of one chip, keyed by JAX's ``device_kind``.

The benchmark's own copy, so that no change to the program can move the
yardstick. A device that is not listed is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud docs, 'TPU v5e': 197 TFLOP/s bf16, 819 GB/s HBM",
    },
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}; "
            f"listed: {sorted(PEAKS)}") from None

