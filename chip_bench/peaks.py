"""Published peaks of each device kind the benchmark may run on.

Keyed by ``jax.Device.device_kind``.  A kind that is not here is an
error, never a default: a roofline share against a guessed peak is no
measurement.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 16 GB of HBM at 819 GB/s per chip
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peak for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[kind]
