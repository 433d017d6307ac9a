"""Peak rates of each accelerator the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A kind that is not listed is an error: a share
of a peak is never computed against a guess.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" system
architecture page: 197 TFLOP/s bf16 per chip (float32 matmuls at the default
precision run as bfloat16 passes), 819 GB/s of HBM bandwidth, 16 GB of HBM.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud TPU documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_time_s(flops: float, nbytes: float, peaks: dict,
                 chips: int = 1) -> float:
    """The least time ``chips`` chips at their peaks could take."""
    return max(flops / (peaks["flops_per_s"] * chips),
               nbytes / (peaks["hbm_bytes_per_s"] * chips))
