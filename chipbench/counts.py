"""Work counts kept with the benchmark: chip peaks and kernel bytes.

A model's FLOPs per trained token are its model module's
(``models/<name>.py``, ``train_flops_per_token``).

A kernel call's bytes are its operands' and results' (``array_bytes``;
``trace.Op.interface_bytes`` reads the same from a traced call's
shapes).
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float          # FLOP/s
    hbm_bytes_per_s: float     # B/s
    hbm_bytes: float           # B


# Google Cloud documentation, "TPU v5e" (system architecture table):
# 197 TFLOP/s bf16, 16 GB of HBM2 at 819 GB/s per chip.  Keyed by
# ``jax.Device.device_kind``; the program's ``src/repro/hw.py`` holds the
# same numbers.
PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9),
}


def peaks(device_kind: str) -> Peaks:
    """Published peaks of one chip; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def array_bytes(*avals) -> int:
    """Bytes of arrays (anything with ``shape`` and ``dtype``), each read
    or written once: the interface of an op, whatever implements it."""
    import numpy as np
    return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
               for a in avals)

