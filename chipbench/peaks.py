"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The benchmark's own copy: rooflines and utilizations are shares of these
numbers, and the yardstick stays where a change to the program cannot
move it.  A device the table does not list is an error, never a default.
"""
from __future__ import annotations

from typing import NamedTuple


class ChipPeaks(NamedTuple):
    source: str
    flops_bf16: float      # FLOP/s
    hbm_bw: float          # B/s
    hbm_bytes: float       # B


PEAKS = {
    # TPU v5e: 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s
    "TPU v5 lite": ChipPeaks(source='Google Cloud documentation, "TPU v5e"',
                             flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None
