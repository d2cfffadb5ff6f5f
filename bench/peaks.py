"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error, never a
default: a roofline share against a guessed peak is no measurement.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture table):
    # per chip, 16 GB of HBM at 819 GB/s and 197 TFLOP/s in bf16.
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table of ``device_kind``; raises ``KeyError`` for a chip
    the table does not know."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
