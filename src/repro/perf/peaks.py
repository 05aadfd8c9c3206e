"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

One table for every roofline in the repo.  A device that is not in the
table is an error, never a default: a CPU timing divided by a TPU peak is
not a utilization of anything.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Peaks of one chip, with where they were published."""
    source: str
    flops_bf16: float      # FLOP/s
    ops_int8: float        # OP/s
    hbm_bw: float          # B/s
    hbm_bytes: float       # B
    ici_link_bw: float     # B/s per inter-chip link


PEAKS = {
    # TPU v5e: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s,
    # 1,600 Gbit/s of inter-chip interconnect over 4 links
    "TPU v5 lite": ChipPeaks(
        source='Google Cloud documentation, "TPU v5e"',
        flops_bf16=197e12, ops_int8=393e12, hbm_bw=819e9, hbm_bytes=16e9,
        ici_link_bw=1600e9 / 8 / 4),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; raises ``ValueError`` for any device the
    table does not list."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); a roofline needs the chip it "
            f"ran on") from None
