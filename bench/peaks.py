"""Peak figures of each chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).  A kind that is not here is
an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16e9},
}


def peak(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no peak figures for device kind {kind!r}")
    return PEAKS[kind]
