"""Published per-chip peaks, keyed by ``device_kind`` — the ONE home for
peak-throughput numbers.

Every analytic model in the repo prices compute and wire time against
these peaks: fig3's Eq. 6 rows, the dry-run roofline
(launch/hlo_analysis.py) and the live per-phase attribution
(obs/timeline.py).  Callers name the chip they price; a kind that is not
in the table is an error, never a default.

The *measured* counterparts live elsewhere by design: link constants are
probe-calibrated per mesh by ``repro.tune`` (``CalibratedCostModel``)
and per-phase seconds come from ``obs/profile.py``'s trace parsing —
the peaks below are the uncalibrated fallback, never the answer.
"""
from __future__ import annotations

from typing import NamedTuple

# ``jax.devices()[0].device_kind`` of a TPU v5e chip.
V5E = "TPU v5 lite"


class ChipPeaks(NamedTuple):
    flops: float             # bf16 peak FLOP/s
    hbm_bytes_per_s: float   # HBM bandwidth, B/s
    ici_bytes_per_s: float   # one inter-chip link, B/s (fig3's b_inter)


# Google Cloud documentation, "TPU v5e" (system architecture table):
# 197 TFLOP/s bf16, 16 GB HBM2 at 819 GB/s, 1,600 Gbit/s of inter-chip
# interconnect per chip.  The chips form a 2D torus, four links each, so
# one link carries 1,600 / 4 = 400 Gbit/s = 50 GB/s.
_PEAKS = {
    V5E: ChipPeaks(flops=197e12, hbm_bytes_per_s=819e9,
                   ici_bytes_per_s=1600e9 / 8 / 4),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of one chip of ``device_kind``; raises for a
    kind the table does not hold."""
    try:
        return _PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r}; known: {sorted(_PEAKS)}") from None
