"""The dense GQA decoder (qwen2, qwen3): its plain float32 reference and
the counts of its work. A configuration names it with `"arch":
"dense_gqa"`; `harness/cells.py` loads it by path.

Reference. It imports nothing of the program. It reads the benchmark's
weight tree by name (`embed`, `final_norm`, optional `lm_head`, and per
layer, stacked on axis 0 under `blocks/pos0`: `norm1`, `norm2`,
`mixer/{wq,wk,wv,wo}`, optional `mixer/{bq,bk,bv}` and
`mixer/{q_norm,k_norm}`, `ffn/{wg,wu,wd}`), and follows the published
architecture: grouped-query attention with causal masking, qkv bias
(qwen2), RMSNorm of q and k per head (qwen3), rotary embeddings on the
two halves of each head, RMSNorm before attention and the feed-forward,
and SwiGLU. Norm scales are applied as (1 + w), the parametrisation the
weight tree stores them in.

Every product runs at `precision="highest"`. `lowp="fp8"` rounds both
operands of every product to float8 (e4m3, one scale per tensor): the
control, one precision below what the configurations state.

Memory stays bounded at the timed sizes: attention runs in blocks of
query rows, layers are rematerialised in the backward pass, and the loss
and the logits are taken in blocks of rows.

Counts (`bench/harness/work.py` asks for them). `model` is a
configuration's `model` dict. A multiply-add counts 2 FLOPs. Attention
work is the causal minimum: a query at position p reads keys 0..p; the
bytes are what the algorithm must move at least (each prefill chunk
reads its context's K/V once, each decoded token reads its whole cache
once, training reads and writes each operand once), in the served dtype
(bf16, 2 bytes).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
Q_BLOCK = 512
ROW_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class Arch:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab_size: int
    rope_theta: float
    norm_eps: float
    qkv_bias: bool
    qk_norm: bool
    tie_embeddings: bool
    lowp: Optional[str] = None  # None | "fp8"

    @classmethod
    def of(cls, model: dict, lowp: Optional[str] = None) -> "Arch":
        return cls(
            n_heads=model["n_heads"], n_kv_heads=model["n_kv_heads"],
            head_dim=model["head_dim"], vocab_size=model["vocab_size"],
            rope_theta=float(model["rope_theta"]),
            norm_eps=float(model["norm_eps"]),
            qkv_bias=bool(model.get("qkv_bias", False)),
            qk_norm=bool(model.get("qk_norm", False)),
            tie_embeddings=bool(model.get("tie_embeddings", False)),
            lowp=lowp,
        )

    def visible(self, rows, keys):
        """[Q, K] mask of the keys each query row attends to: causal."""
        return keys[None, :] <= rows[:, None]


def _round(x, dtype, top):
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _round_fp8(x):
    """x rounded to float8 e4m3 with one scale per tensor; its cotangent
    is rounded to e5m2 the same way, as float8 training does."""
    return _round(x, jnp.float8_e4m3fn, 448.0)


def _round_fp8_fwd(x):
    return _round_fp8(x), None


def _round_fp8_bwd(_, ct):
    return (_round(ct, jnp.float8_e5m2, 57344.0),)


_round_fp8.defvjp(_round_fp8_fwd, _round_fp8_bwd)


def _mm(arch: Arch, spec: str, a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if arch.lowp == "fp8":
        a, b = _round_fp8(a), _round_fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs  # [S, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(arch: Arch, q, k, v):
    """Causal GQA. q [B, S, Hq, d], k/v [B, S, Hkv, d] -> [B, S, Hq*d],
    one block of query rows at a time (rematerialised)."""
    b, s, hq, d = q.shape
    g = hq // arch.n_kv_heads
    nb = -(-s // Q_BLOCK)
    pad = nb * Q_BLOCK - s
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = qb.reshape(b, nb, Q_BLOCK, arch.n_kv_heads, g, d).transpose(1, 0, 2, 3, 4, 5)
    keys = jnp.arange(s)

    @jax.checkpoint
    def block(args):
        i, qi = args  # qi [B, Q, Hkv, g, d]
        sc = _mm(arch, "bqhgd,bkhd->bhgqk", qi, k) / jnp.sqrt(jnp.float32(d))
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(arch.visible(rows, keys), sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return _mm(arch, "bhgqk,bkhd->bqhgd", p, v)

    o = lax.map(block, (jnp.arange(nb), qb))  # [nb, B, Q, Hkv, g, d]
    o = o.transpose(1, 0, 2, 3, 4, 5).reshape(b, nb * Q_BLOCK, hq * d)
    return o[:, :s]


def _layer(arch: Arch, lp, h, pos):
    b, s, _ = h.shape
    d = arch.head_dim
    m, f = lp["mixer"], lp["ffn"]
    x = _rms(h, lp["norm1"], arch.norm_eps)
    q = _mm(arch, "bsd,dh->bsh", x, m["wq"])
    k = _mm(arch, "bsd,dh->bsh", x, m["wk"])
    v = _mm(arch, "bsd,dh->bsh", x, m["wv"])
    if arch.qkv_bias:
        q, k, v = q + m["bq"], k + m["bk"], v + m["bv"]
    q = q.reshape(b, s, arch.n_heads, d)
    k = k.reshape(b, s, arch.n_kv_heads, d)
    v = v.reshape(b, s, arch.n_kv_heads, d)
    if arch.qk_norm:
        q = _rms(q, m["q_norm"], arch.norm_eps)
        k = _rms(k, m["k_norm"], arch.norm_eps)
    q, k = _rope(q, pos, arch.rope_theta), _rope(k, pos, arch.rope_theta)
    h = h + _mm(arch, "bsh,hd->bsd", _attention(arch, q, k, v), m["wo"])
    x = _rms(h, lp["norm2"], arch.norm_eps)
    a = jax.nn.silu(_mm(arch, "bsd,df->bsf", x, f["wg"])) * _mm(arch, "bsd,df->bsf", x, f["wu"])
    return h + _mm(arch, "bsf,fd->bsd", a, f["wd"])


def _check_tree(params):
    extra = set(params) - {"embed", "final_norm", "lm_head", "blocks"}
    if extra or set(params["blocks"]) != {"pos0"}:
        raise ValueError(
            f"dense_gqa covers one repeated (attn, swiglu) layer; the tree has "
            f"{sorted(params)} / {sorted(params['blocks'])}: write bench/arch/"
            f"<name>.py for this architecture and name it in the "
            f"configuration's \"arch\"")


def hidden(arch: Arch, params, tokens):
    """Final-normed hidden states [B, S, d] for token ids [B, S]."""
    _check_tree(params)
    pos = jnp.arange(tokens.shape[1])
    h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    def body(h, lp):
        return jax.checkpoint(functools.partial(_layer, arch))(lp, h, pos), None

    h, _ = lax.scan(body, h, params["blocks"]["pos0"])
    return _rms(h, params["final_norm"], arch.norm_eps)


def _head(arch: Arch, params):
    if arch.tie_embeddings:
        return params["embed"][: arch.vocab_size].T
    return params["lm_head"][:, : arch.vocab_size]


def logits(arch: Arch, params, h):
    """Logits over the true vocabulary for hidden rows h [N, d]."""
    return _mm(arch, "nd,dv->nv", h, _head(arch, params))


def loss(arch: Arch, params, tokens, labels):
    """Mean next-token cross entropy over labels >= 0."""
    h = hidden(arch, params, tokens)
    b, s, dm = h.shape
    n = b * s
    nb = -(-n // ROW_BLOCK)
    pad = nb * ROW_BLOCK - n
    hr = jnp.pad(h.reshape(n, dm), ((0, pad), (0, 0))).reshape(nb, ROW_BLOCK, dm)
    lr = jnp.pad(labels.reshape(n), (0, pad), constant_values=-1).reshape(nb, ROW_BLOCK)
    head = _head(arch, params)

    @jax.checkpoint
    def block(args):
        hb, lb = args
        lg = _mm(arch, "nd,dv->nv", hb, head)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, jnp.maximum(lb, 0)[:, None], -1)[:, 0]
        keep = (lb >= 0).astype(jnp.float32)
        return jnp.sum((lse - gold) * keep), jnp.sum(keep)

    tot, cnt = lax.map(block, (hr, lr))
    return jnp.sum(tot) / jnp.maximum(jnp.sum(cnt), 1.0)


# ---- counts ------------------------------------------------------------------

KV_BYTES = 2


def matmul_params_per_layer(m: dict) -> int:
    d, hd, ff = m["d_model"], m["head_dim"], m["d_ff"]
    hq, hkv = m["n_heads"], m["n_kv_heads"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * ff


def flops_per_token(m: dict) -> int:
    """Forward FLOPs of one token through every layer's matmuls."""
    return 2 * m["n_layers"] * matmul_params_per_layer(m)


def head_flops(m: dict) -> int:
    """Forward FLOPs of one row of logits."""
    return 2 * m["d_model"] * m["vocab_size"]


def attn_flops(m: dict, ctx_sum):
    """Forward attention FLOPs over every layer, for queries whose
    context lengths sum to ctx_sum (QK^T and PV)."""
    return 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * ctx_sum


def causal_ctx_sum(start, n):
    """Sum of (p + 1) for positions p in [start, start + n)."""
    return n * start + n * (n + 1) / 2.0


def mixer_work(m: dict, start, n):
    """(FLOPs, bytes) over every layer of the attention forward of pieces
    of one sequence: piece i puts n[i] queries at positions
    [start[i], start[i] + n[i]) against the K/V of every position before
    them and its own (a prefill chunk, or a decoded token with n 1)."""
    start, n = np.asarray(start, np.float64), np.asarray(n, np.float64)
    L, hq, hkv, hd = m["n_layers"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    kv_row = 2 * hkv * hd * KV_BYTES  # K and V of one position
    q_row = 2 * hq * hd * KV_BYTES  # q read and o written
    return (attn_flops(m, causal_ctx_sum(start, n)),
            L * ((start + n) * kv_row + n * q_row))


def train_mixer_work(m: dict, batch: int, seq_len: int):
    """(FLOPs, bytes) of one step's attention, forward and backward: 3x
    the forward FLOPs; q, k, v and o read or written once forward, and
    q, k, v, o, do read and dq, dk, dv written backward."""
    fwd = batch * attn_flops(m, causal_ctx_sum(0, seq_len))
    rows = batch * seq_len * m["n_layers"] * m["head_dim"] * KV_BYTES
    hq, hkv = m["n_heads"], m["n_kv_heads"]
    nbytes = rows * ((2 * hq + 2 * hkv) + (3 * hq + 2 * hkv) + (hq + 2 * hkv))
    return np.array([3.0 * fwd]), np.array([float(nbytes)])
