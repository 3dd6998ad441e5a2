"""Peaks of each chip, keyed by `device_kind` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
A device missing from the table is an error, never a default.
"""
from __future__ import annotations

_V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (197 TFLOP/s bf16, "
                  "819 GB/s, 16 GB)"}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to bench/peaks.py with its source") from None
