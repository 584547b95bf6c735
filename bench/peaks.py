"""Published peaks of each chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s in bfloat16, 16 GB of HBM at 819 GB/s per chip.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py"
                       ) from None


def min_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of operations over
    peak rate and bytes over peak bandwidth."""
    pk = peaks(device_kind)
    return max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
