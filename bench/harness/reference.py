"""Plain reference of the qwen2 / qwen3 decoder stack, in float32.

It imports nothing of the program. It reads the benchmark's weight tree
by name (`embed`, `final_norm`, optional `lm_head`, and per layer, stacked
on axis 0 under `blocks/pos0`: `norm1`, `norm2`, `mixer/{wq,wk,wv,wo}`,
optional `mixer/{bq,bk,bv}` and `mixer/{q_norm,k_norm}`,
`ffn/{wg,wu,wd}`), and follows the published architecture: grouped-query
attention with causal masking, qkv bias (qwen2), RMSNorm of q and k per
head (qwen3), rotary embeddings on the two halves of each head, RMSNorm
before attention and the feed-forward, and SwiGLU. Norm scales are
applied as (1 + w), the parametrisation the weight tree stores them in.

Every product runs at `precision="highest"`. `lowp="fp8"` rounds both
operands of every product to float8 (e4m3, one scale per tensor): the
control, one precision below what the configurations state.

Memory stays bounded at the timed sizes: attention runs in blocks of
query rows, layers are rematerialised in the backward pass, and the loss
and the logits are taken in blocks of rows.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
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
        sc = jnp.where(keys[None, :] <= rows[:, None], sc, -jnp.inf)
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
        raise ValueError(f"reference covers one repeated (attn, swiglu) layer; "
                         f"tree has {sorted(params)} / {sorted(params['blocks'])}")


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


# ---- the optimizer the training configurations state -----------------------

def lr_at(opt: dict, step):
    """Linear warm-up from 0, then cosine to `final_frac` of the peak."""
    step = jnp.asarray(step, jnp.float32)
    peak, warm, total = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    t = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = peak * (opt["final_frac"] + (1 - opt["final_frac"]) * 0.5 * (1 + jnp.cos(jnp.pi * t)))
    return jnp.where(step < warm, peak * step / max(warm, 1), cos)


def decays(opt: dict, name: str, ndim: int) -> bool:
    return ndim > 1 and not any(k in name for k in opt["no_decay_keywords"])


def adamw(opt: dict, params, grads, m, v, step: int):
    """One AdamW step after global-norm clipping; `step` counts from 0.
    Returns (params, m, v, clipped grads)."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    g = jax.tree.map(lambda x: x * scale, grads)
    b1, b2, t = opt["b1"], opt["b2"], step + 1
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    lr = lr_at(opt, step)

    def upd(path, p, a, b):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        wd = opt["weight_decay"] if decays(opt, name, p.ndim) else 0.0
        u = (a / (1 - b1 ** t)) / (jnp.sqrt(b / (1 - b2 ** t)) + opt["eps"]) + wd * p
        return p - lr * u

    params = jax.tree_util.tree_map_with_path(upd, params, m, v)
    return params, m, v, g
