"""Operations and bytes of the work a window did, from shapes alone.

`model` is a configuration's `model` dict (the program's ModelConfig
keys). Model FLOPs count each multiply-add as 2 and no recomputed
operation. Attention work is the causal minimum: a query at position p
reads keys 0..p; the bytes are what the algorithm must move at least
(each prefill chunk reads its context's K/V once, each decoded token
reads its whole cache once, training reads and writes each operand
once), in the served dtype (bf16, 2 bytes).
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np

KV_BYTES = 2


def matmul_params_per_layer(m: dict) -> int:
    d, hd, ff = m["d_model"], m["head_dim"], m["d_ff"]
    hq, hkv = m["n_heads"], m["n_kv_heads"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * ff


def dense_flops_per_token(m: dict) -> int:
    """Forward FLOPs of one token through every layer's matmuls."""
    return 2 * m["n_layers"] * matmul_params_per_layer(m)


def head_flops(m: dict) -> int:
    """Forward FLOPs of one row of logits."""
    return 2 * m["d_model"] * m["vocab_size"]


def attn_flops(m: dict, ctx_sum: float) -> float:
    """Forward attention FLOPs over every layer, for queries whose
    context lengths sum to ctx_sum (QK^T and PV)."""
    return 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * ctx_sum


def causal_ctx_sum(start: int, n: int) -> float:
    """Sum of (p + 1) for positions p in [start, start + n)."""
    return n * start + n * (n + 1) / 2.0


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

    model: dict
    prompt_lens: np.ndarray
    out_lens: np.ndarray
    prefill_chunk: int

    @classmethod
    def of(cls, model: dict, prompts: Sequence, outputs: Sequence,
           prefill_chunk: int) -> "ServeWork":
        return cls(model, np.array([len(p) for p in prompts]),
                   np.array([len(o) for o in outputs]), prefill_chunk)

    def fed_tokens(self) -> int:
        served = self.out_lens > 0
        return int(self.prompt_lens[served].sum()
                   + np.maximum(self.out_lens - 1, 0).sum())

    def model_flops(self) -> float:
        """Matmuls of every fed token, the LM head of every emitted
        token, and causal attention."""
        m = self.model
        ctx = 0.0
        for p, n in zip(self.prompt_lens, self.out_lens):
            if n > 0:
                ctx += causal_ctx_sum(0, int(p) + int(n) - 1)
        return (self.fed_tokens() * dense_flops_per_token(m)
                + int(self.out_lens.sum()) * head_flops(m) + attn_flops(m, ctx))

    def attn_pieces(self):
        """(FLOPs, bytes) per attention piece: one per prefill chunk and
        one per decoded token, over all layers."""
        m = self.model
        L, hq, hkv, hd = m["n_layers"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
        kv_row = 2 * hkv * hd * KV_BYTES  # K and V of one position
        q_row = 2 * hq * hd * KV_BYTES  # q read and o written
        fl, by = [], []
        for p, n in zip(self.prompt_lens, self.out_lens):
            if n == 0:
                continue
            p, n = int(p), int(n)
            for s in range(0, p, self.prefill_chunk):
                c = min(self.prefill_chunk, p - s)
                fl.append(attn_flops(m, causal_ctx_sum(s, c)))
                by.append(L * ((s + c) * kv_row + c * q_row))
            ctx = np.arange(p + 1, p + n, dtype=np.float64)  # decode steps
            fl.extend(attn_flops(m, 1.0) * ctx)
            by.extend(L * (ctx * kv_row + q_row))
        return np.asarray(fl, np.float64), np.asarray(by, np.float64)


@dataclasses.dataclass(frozen=True)
class TrainWork:
    model: dict
    batch: int
    seq_len: int

    def tokens(self) -> int:
        return self.batch * self.seq_len

    def attn_fwd_flops(self) -> float:
        return self.batch * attn_flops(self.model, causal_ctx_sum(0, self.seq_len))

    def model_flops(self) -> float:
        """Forward and backward (3x forward), no recomputation."""
        m = self.model
        fwd = (self.tokens() * (dense_flops_per_token(m) + head_flops(m))
               + self.attn_fwd_flops())
        return 3.0 * fwd

    def attn_piece(self):
        """(FLOPs, bytes) of one step's attention, forward and backward:
        3x the forward FLOPs; q, k, v and o read or written once forward,
        and q, k, v, o, do read and dq, dk, dv written backward."""
        m = self.model
        rows = self.tokens() * m["n_layers"] * m["head_dim"] * KV_BYTES
        hq, hkv = m["n_heads"], m["n_kv_heads"]
        nbytes = rows * ((2 * hq + 2 * hkv) + (3 * hq + 2 * hkv) + (hq + 2 * hkv))
        return (np.array([3.0 * self.attn_fwd_flops()]), np.array([float(nbytes)]))
