"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` string JAX reports.

A device that is not in the table is an error, never a default: a share
of a peak taken against the wrong chip's peak is a wrong number.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    flops: float        # dense bf16 FLOP/s of one chip
    hbm_bw: float       # HBM bytes/s of one chip
    hbm_bytes: int      # HBM capacity of one chip
    source: str


PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM2 at 819 GB/s per chip.
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16 << 30,
                         source="Google Cloud documentation, TPU v5e"),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; raises ``KeyError`` for a chip that
    is not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
