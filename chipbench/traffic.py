"""The one generator of training traffic: a mix file's parameters and a
seed give the same batches every time.

Tokens are drawn from a Zipf law over the vocabulary (rank r with
probability proportional to r^-a), and each row gets next-token
predictable runs (motifs): ``motif_len`` consecutive ids planted at
random starts, one run per 4 * motif_len positions.  After the
program's ``data/synthetic.py``.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def _rng(seed: int, step: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([seed % (1 << 64), step]))


class Traffic:
    """Batches of a closed-loop training mix, from ``--seed``."""

    def __init__(self, mix: Dict, vocab: int, seed: int):
        self.batch = int(mix["global_batch"])
        self.seq = int(mix["seq_len"])
        self.motif_len = int(mix["motif_len"])
        self.seed = seed
        self.vocab = vocab
        p = 1.0 / np.power(np.arange(1, vocab + 1, dtype=np.float64),
                           float(mix["zipf_a"]))
        self._cdf = np.cumsum(p / p.sum())

    @property
    def tokens_per_step(self) -> int:
        return self.batch * self.seq

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        """Batch ``step``: int32 tokens and next-token labels [B, S]."""
        rng = _rng(self.seed, step)
        B, S, k = self.batch, self.seq, self.motif_len
        u = rng.random((B, S + 1))
        toks = np.minimum(np.searchsorted(self._cdf, u, side="right"),
                          self.vocab - 1).astype(np.int32)
        for b in range(B):
            starts = rng.integers(0, S - k, size=max(1, S // (4 * k)))
            base = rng.integers(0, max(1, self.vocab - k))
            for s in starts:
                toks[b, s:s + k] = base + np.arange(k)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def batches(self, first: int, count: int) -> List[Dict[str, np.ndarray]]:
        return [self.batch_at(first + i) for i in range(count)]
