"""Per-chip peaks, keyed by ``device_kind`` as JAX reports it.

A copy of ``repro.roofline.analysis.PEAKS``, kept with the benchmark so
that a change to the program cannot move the yardstick.  A chip with no
entry is an error, never a default.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    """bf16 FLOP/s, HBM bytes/s and one ICI link's bytes/s of one chip."""

    flops_bf16: float
    hbm_bw: float
    ici_link_bw: float
    source: str


PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12, hbm_bw=819e9, ici_link_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "819 GB/s HBM, 1,600 Gbit/s ICI per chip (4 links)",
    ),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; ``ValueError`` for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device_kind {device_kind!r}; known kinds: "
            f"{', '.join(sorted(PEAKS))}"
        ) from None
