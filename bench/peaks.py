"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip. A device
kind missing from the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float     # FLOP/s of one chip's matrix units in bf16
    hbm_bytes_s: float    # bytes/s of one chip's HBM
    hbm_bytes: float      # bytes of HBM on one chip
    source: str


_V5E = Peak(bf16_flops=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9,
            source='Google Cloud documentation, "TPU v5e"')

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
