"""Work counts kept with the benchmark: chip peaks and model FLOPs.

A kernel call's bytes are its operands' and results' (``array_bytes``;
``trace.Op.interface_bytes`` reads the same from a traced call's
shapes).
"""
from __future__ import annotations

from typing import Dict, NamedTuple


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


def active_matmul_params(m: Dict) -> int:
    """Weights one token multiplies by in a forward pass: attention
    projections, router, its top-k experts' SwiGLU matrices and the LM
    head, over every layer.  The embedding is a lookup and is left out."""
    h, dh = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * dh, m["num_key_value_heads"] * dh
    attn = h * q + 2 * h * kv + q * h
    router = h * m["num_local_experts"]
    experts = m["num_experts_per_tok"] * 3 * h * m["intermediate_size"]
    return m["num_hidden_layers"] * (attn + router + experts) \
        + h * m["vocab_size"]


def train_flops_per_token(m: Dict, seq_len: int) -> float:
    """Model FLOPs of one trained token: 6 per active weight (forward and
    backward) plus causal attention, 6 * layers * heads * head_dim * S
    (QK^T and PV over half the sequence on average, times 3).  Every
    token counts its full top-k, with or without LSH; LSH's own work and
    recomputation do not count."""
    attn = 6 * m["num_hidden_layers"] * m["num_attention_heads"] \
        * m["head_dim"] * seq_len
    return 6.0 * active_matmul_params(m) + attn
