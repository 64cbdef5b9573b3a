"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A kind without a row is an error: a roofline against another chip's peaks
is silently wrong.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s
    hbm_bytes: float       # bytes/s
    hbm_capacity: int      # bytes
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes=819e9, hbm_capacity=16 * 10**9,
        source="Google Cloud documentation, 'TPU v5e' (per chip: 197 "
               "TFLOP/s bf16, 16 GB HBM at 819 GB/s)"),
}


def peaks_for(kind: str) -> Peaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no peak row for device_kind {kind!r}; known: "
                       f"{sorted(PEAKS)}") from None
