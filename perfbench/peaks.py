"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from ``CHIP_PEAKS`` in ``src/repro/analysis/roofline.py`` at commit
4a8f582. Source: Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s
bf16, 819 GB/s HBM, 1,600 Gbit/s of interchip interconnect per chip over
4 links (50 GB/s each). A kind not in the table is an error, never a
default.
"""
from __future__ import annotations

from typing import NamedTuple


class ChipPeaks(NamedTuple):
    flops_bf16: float          # FLOP/s
    hbm_bw: float              # bytes/s
    ici_bw_per_link: float     # bytes/s per interchip link


CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bw=819e9,
                             ici_bw_per_link=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(CHIP_PEAKS)}") from None
