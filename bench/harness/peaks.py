"""Published peaks per chip, keyed by JAX's ``device_kind``.

TPU v5e (JAX reports "TPU v5 lite"): 197 TFLOP/s in bfloat16 and 819 GB/s
of HBM bandwidth, per the Google Cloud documentation page "TPU v5e".  A
device that is not in the table is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9},
}


def of(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}")
    return PEAKS[kind]


def least_time(flops: float, nbytes: float, kind: str) -> tuple[float, str]:
    """(seconds, bound): the larger of compute and memory time at peak."""
    p = of(kind)
    tc, tm = flops / p["flops"], nbytes / p["hbm_bytes_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
