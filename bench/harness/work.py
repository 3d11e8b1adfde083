"""Operations and bytes of the work a window did, from shapes alone.

`model` is a configuration's `model` dict (the program's ModelConfig
keys); `arch` is the module its configuration names (`bench/arch/`),
which counts what depends on the architecture: `flops_per_token`,
`head_flops`, `mixer_work` (the attention or mixer forward of pieces of
one sequence) and `train_mixer_work` (one training step's). Model FLOPs
count each multiply-add as 2 and no recomputed operation.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class Roofline:
    flops: float
    bytes: float
    compute_s: float  # time of the pieces bound by FLOPs
    memory_s: float  # time of the pieces bound by bytes

    @property
    def seconds(self) -> float:
        return self.compute_s + self.memory_s

    def bound(self) -> str:
        return "compute" if self.compute_s >= self.memory_s else "memory"


def roofline(flops: np.ndarray, nbytes: np.ndarray, peaks) -> Roofline:
    """Roofline time of pieces run one after another: for each piece the
    larger of FLOPs / peak and bytes / bandwidth, summed."""
    tf, tb = flops / peaks.flops, nbytes / peaks.hbm_bw
    return Roofline(float(flops.sum()), float(nbytes.sum()),
                    float(tf[tf >= tb].sum()), float(tb[tb > tf].sum()))


@dataclasses.dataclass(frozen=True)
class ServeWork:
    """Work of the requests a serving window ran: each prompt prefilled
    in `prefill_chunk` pieces, each output token but the last fed back."""

    arch: object
    model: dict
    prompt_lens: np.ndarray
    out_lens: np.ndarray
    prefill_chunk: int

    @classmethod
    def of(cls, arch, model: dict, prompts: Sequence, outputs: Sequence,
           prefill_chunk: int) -> "ServeWork":
        return cls(arch, model, np.array([len(p) for p in prompts]),
                   np.array([len(o) for o in outputs]), prefill_chunk)

    def fed_tokens(self) -> int:
        served = self.out_lens > 0
        return int(self.prompt_lens[served].sum()
                   + np.maximum(self.out_lens - 1, 0).sum())

    def model_flops(self) -> float:
        """Matmuls of every fed token, the LM head of every emitted
        token, and each served sequence's mixer forward."""
        a, m = self.arch, self.model
        lens = [int(p) + int(n) - 1 for p, n in zip(self.prompt_lens, self.out_lens)
                if n > 0]
        mixer = a.mixer_work(m, np.zeros(len(lens)), np.asarray(lens))[0].sum()
        return (self.fed_tokens() * a.flops_per_token(m)
                + int(self.out_lens.sum()) * a.head_flops(m) + float(mixer))

    def attn_pieces(self):
        """(FLOPs, bytes) per mixer piece: one per prefill chunk and one
        per decoded token, over all layers."""
        start, n = [], []
        for p, k in zip(self.prompt_lens, self.out_lens):
            if k == 0:
                continue
            p, k = int(p), int(k)
            chunks = np.arange(0, p, self.prefill_chunk)
            start.extend(chunks)
            n.extend(np.minimum(self.prefill_chunk, p - chunks))
            start.extend(range(p, p + k - 1))  # decode steps
            n.extend([1] * (k - 1))
        return self.arch.mixer_work(self.model, np.asarray(start, np.float64),
                                    np.asarray(n, np.float64))


@dataclasses.dataclass(frozen=True)
class TrainWork:
    arch: object
    model: dict
    batch: int
    seq_len: int

    def tokens(self) -> int:
        return self.batch * self.seq_len

    def mixer_fwd_flops(self) -> float:
        fl, _ = self.arch.mixer_work(self.model, np.zeros(1), np.full(1, self.seq_len))
        return self.batch * float(fl.sum())

    def model_flops(self) -> float:
        """Forward and backward (3x forward), no recomputation."""
        a, m = self.arch, self.model
        fwd = (self.tokens() * (a.flops_per_token(m) + a.head_flops(m))
               + self.mixer_fwd_flops())
        return 3.0 * fwd

    def attn_piece(self):
        """(FLOPs, bytes) of one step's mixer kernels, forward and
        backward."""
        return self.arch.train_mixer_work(self.model, self.batch, self.seq_len)
