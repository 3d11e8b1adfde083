"""Seeded training batches: the program's `data.pipeline.SyntheticLM`
stream (a Zipf unigram base mixed with a sparse Markov successor table),
copied here so that the yardstick keeps it when the program changes.

The draws are made in bulk per batch (one inverse-CDF lookup for the
unigram picks instead of one `rng.choice` per position), and the Markov
walk runs over plain Python ints, so a batch of 4096 tokens costs about a
millisecond of host time and holds the interpreter lock no longer than
that; the stream differs from the program's in its draw order, not in
its distribution. `batch(step)` is a pure function of (seed, step).
"""

from __future__ import annotations

from typing import Dict

import numpy as np


class SyntheticLM:
    def __init__(self, vocab: int, seq_len: int, batch: int, seed: int,
                 zipf_a: float = 1.3, markov_weight: float = 0.7):
        self.vocab, self.seq_len, self.local_batch = vocab, seq_len, batch
        self.seed, self.markov_weight = int(seed), markov_weight
        base = np.random.default_rng([self.seed, 2])
        unigram = np.arange(1, vocab + 1, dtype=np.float64) ** (-zipf_a)
        self._cdf = np.cumsum(unigram / unigram.sum())
        self._succ = base.integers(0, vocab, size=(vocab, 4))
        self._succ_flat = self._succ.ravel().tolist()

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([self.seed, 3, int(step)])
        b, s = self.local_batch, self.seq_len
        uni = np.minimum(np.searchsorted(self._cdf, rng.random((b, s + 1))),
                         self.vocab - 1)
        use_markov = rng.random((b, s + 1)) < self.markov_weight
        pick = rng.integers(0, 4, size=(b, s + 1))
        succ = self._succ_flat
        toks = np.empty((b, s + 1), np.int32)
        for r in range(b):
            u, m, k = uni[r].tolist(), use_markov[r].tolist(), pick[r].tolist()
            prev = u[0]
            row = [prev]
            for t in range(1, s + 1):
                prev = succ[4 * prev + k[t]] if m[t] else u[t]
                row.append(prev)
            toks[r] = row
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:].copy()}
