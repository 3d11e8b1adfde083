"""jit'd dispatch registry over the Pallas kernels.

On TPU backends the real kernels run; on the CPU backend they execute in
Pallas interpret mode (kernel body evaluated op-by-op) so every code path
is exercised in CI. Any other backend is an error, not a fallback. The
models never import kernels directly — they go through
`repro.core.attention`, which lands here for the `*_pallas` impls.

The entry points form a REGISTRY: each is registered under a stable op
name (`attention_fwd`, `decode`, `decode_paged`, `varlen`) so new kernel
families plug in with `@register_op` instead of another hand-threaded
import chain, and callers that route dynamically (benchmarks, tuning
sweeps) resolve them with `get_op(name)`. The module-level functions stay
importable by name — the registry is the same objects, indexed.

Every op also carries a registered *jnp fallback* — a pure-jnp callable
with the SAME signature, resolved with `get_fallback(name)`. The serving
engine's graceful-degradation path (DESIGN.md §3.7) uses `fallback_impl`
to flip a faulting `*_pallas` attention impl to its jnp twin for the rest
of a serve; dynamic callers can swap a single op the same way.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from repro.core.blockwise import MaskSpec
from repro.kernels.fa2_fwd import fa2_fwd_pallas
from repro.kernels.flashd_decode import (
    flashd_decode_paged_pallas,
    flashd_decode_pallas,
)
from repro.kernels.flashd_fwd import flashd_fwd_pallas
from repro.kernels.flashd_varlen import flashd_varlen_pallas

__all__ = [
    "pallas_attention_fwd_batched",
    "pallas_attention_bwd_batched",
    "pallas_decode",
    "pallas_decode_paged",
    "pallas_varlen",
    "register_op",
    "get_op",
    "op_names",
    "register_fallback",
    "get_fallback",
    "fallback_impl",
    "interpret_mode",
]

_REGISTRY: Dict[str, Callable] = {}
_FALLBACKS: Dict[str, Callable] = {}


def register_op(name: str) -> Callable[[Callable], Callable]:
    """Register a kernel dispatch entry point under `name` (decorator)."""

    def deco(fn: Callable) -> Callable:
        if name in _REGISTRY:
            raise ValueError(f"op {name!r} already registered")
        _REGISTRY[name] = fn
        return fn

    return deco


def get_op(name: str) -> Callable:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown kernel op {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def op_names() -> tuple:
    return tuple(sorted(_REGISTRY))


def register_fallback(name: str) -> Callable[[Callable], Callable]:
    """Register the pure-jnp fallback for op `name` (same signature)."""

    def deco(fn: Callable) -> Callable:
        if name in _FALLBACKS:
            raise ValueError(f"fallback for {name!r} already registered")
        _FALLBACKS[name] = fn
        return fn

    return deco


def get_fallback(name: str) -> Callable:
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown kernel op {name!r}; registered: {sorted(_REGISTRY)}"
        )
    try:
        return _FALLBACKS[name]
    except KeyError:
        raise KeyError(f"op {name!r} has no registered jnp fallback") from None


def fallback_impl(attn_impl: str) -> str:
    """The jnp twin of a Pallas attention impl name ('flashd_pallas' →
    'flashd'); non-Pallas impls map to themselves (nothing to downgrade)."""
    suffix = "_pallas"
    return attn_impl[: -len(suffix)] if attn_impl.endswith(suffix) else attn_impl


def interpret_mode() -> bool:
    """Pallas interpret mode exactly when the backend is the CPU (how the
    tests run); any other backend compiles the kernels for real."""
    return jax.default_backend() == "cpu"


@register_op("attention_fwd")
def pallas_attention_fwd_batched(
    q: jax.Array,  # [B, Sq, Hq, d]   (model layout)
    k: jax.Array,  # [B, Skv, Hkv, d]
    v: jax.Array,  # [B, Skv, Hkv, dv]
    *,
    mask: MaskSpec,
    scale: float,
    impl: str,
    block_q: int,
    block_k: int,
    skip: bool,
):
    """Returns (o [B,Sq,Hq,dv], Λ [B,Hq,Sq]) — kernel layout handled here."""
    if impl == "flashd":
        kernel = functools.partial(flashd_fwd_pallas, skip=skip)
    elif impl == "fa2":
        kernel = fa2_fwd_pallas
    else:
        raise ValueError(f"unknown pallas impl {impl!r}")
    o, lam = kernel(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), mask=mask, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret_mode(),
    )
    return o.transpose(0, 2, 1, 3), lam


@register_op("attention_bwd")
def pallas_attention_bwd_batched(
    q: jax.Array,  # [B, Sq, Hq, d]   (model layout)
    k: jax.Array,  # [B, Skv, Hkv, d]
    v: jax.Array,  # [B, Skv, Hkv, dv]
    o: jax.Array,  # [B, Sq, Hq, dv]  — saved forward output
    lam: jax.Array,  # [B, Hq, Sq] f32  — saved Λ (log-normalizer)
    do: jax.Array,  # [B, Sq, Hq, dv]
    *,
    mask: MaskSpec,
    scale: float,
    impl: str,
    block_q: int,
    block_k: int,
):
    """Fused attention backward from saved (O, Λ) — the training twin of
    `attention_fwd` (DESIGN.md §6). Recomputes score tiles inside the
    kernel (activation checkpointing: nothing [Sq, Skv]-sized is ever
    materialized in HBM) and reconstructs P = exp(s − Λ), which with
    FLASH-D's Λ is overflow-free with no max subtraction — the same
    max-free property as the forward. Both `flashd` and `fa2` forwards
    save the same Λ, so one backward kernel serves both impls.
    Returns (dq, dk, dv) in model layout."""
    del impl  # one bwd kernel serves every fwd impl that saves Λ
    from repro.kernels.flashd_bwd import flashd_bwd_pallas  # lazy: keep import cheap
    t = lambda x: x.transpose(0, 2, 1, 3)
    dq, dk, dv = flashd_bwd_pallas(
        t(q), t(k), t(v), t(o), lam, t(do),
        mask=mask, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret_mode(),
    )
    return t(dq), t(dk), t(dv)


@register_op("decode")
def pallas_decode(
    q: jax.Array,  # [B, 1, Hq, d]
    k_cache: jax.Array,  # [B, S, Hkv, d]
    v_cache: jax.Array,  # [B, S, Hkv, dv]
    cache_len: jax.Array,
    *,
    scale=None,
    n_splits: int | None = None,  # None → tuned (repro.kernels.tuning)
    window: int = 0,
    chunk: int = 0,
    fused: bool = True,
):
    o = flashd_decode_pallas(
        q[:, 0] if q.ndim == 4 else q,  # accept [B,1,Hq,d] or [B,Hq,d]
        k_cache.transpose(0, 2, 1, 3),
        v_cache.transpose(0, 2, 1, 3),
        jnp.asarray(cache_len, jnp.int32).reshape(-1),
        scale=scale,
        n_splits=n_splits,
        window=window,
        chunk=chunk,
        fused=fused,
        interpret=interpret_mode(),
    )
    return o[:, None]  # [B, 1, Hq, dv]


@register_op("decode_paged")
def pallas_decode_paged(
    q: jax.Array,  # [B, 1, Hq, d] or [B, Hq, d]
    k_pages: jax.Array,  # [P, page, Hkv, d] — model page layout == kernel layout
    v_pages: jax.Array,  # [P, page, Hkv, dv]
    block_tbl: jax.Array,  # [B, N] i32
    cache_len: jax.Array,  # [B]
    *,
    scale=None,
    window: int = 0,
    chunk: int = 0,
    k_scale: jax.Array | None = None,  # [P, Hkv] f32 — quantized pool
    v_scale: jax.Array | None = None,
    layer: jax.Array | None = None,  # pool stacked [L, P, ...]: read this layer
):
    """Paged fused decode — the block table rides in as a scalar-prefetch
    operand, so K/V pages are gathered by the DMA engine (DESIGN.md §3.4).
    Page arrays are stored page-major ([P, page, Hkv, d]), which is already
    the kernel layout — no transpose on the hot path. When the pool is
    quantized (DESIGN.md §3.8) the per-(page, head) scales ride the same
    indirection and tiles are dequantized in-kernel. With `layer` the pool
    is the model's stacked [L, P, ...] cache leaf and the kernel's index
    maps read that layer of it: no per-layer copy of the pool."""
    o = flashd_decode_paged_pallas(
        q[:, 0] if q.ndim == 4 else q,
        k_pages,
        v_pages,
        jnp.asarray(block_tbl, jnp.int32),
        jnp.asarray(cache_len, jnp.int32).reshape(-1),
        scale=scale,
        window=window,
        chunk=chunk,
        k_scale=k_scale,
        v_scale=v_scale,
        layer=layer,
        interpret=interpret_mode(),
    )
    return o[:, None]  # [B, 1, Hq, dv]


@register_op("varlen")
def pallas_varlen(
    q: jax.Array,  # [T, Hq, d] — packed, block_q-aligned segments
    k_pages: jax.Array,  # [P, page, Hkv, d]
    v_pages: jax.Array,  # [P, page, Hkv, dv]
    block_tbl: jax.Array,  # [B, N] i32
    seq_ids: jax.Array,  # [T] i32 (−1 padding)
    q_pos: jax.Array,  # [T] i32 (−1 padding)
    kv_len: jax.Array,  # [B] i32
    *,
    scale=None,
    window: int = 0,
    chunk: int = 0,
    block_q: int,
    k_scale: jax.Array | None = None,  # [P, Hkv] f32 — quantized pool
    v_scale: jax.Array | None = None,
    layer: jax.Array | None = None,  # pool stacked [L, P, ...]: read this layer
):
    """Unified packed varlen step (DESIGN.md §3.5): prefill chunks and
    decode rows in ONE kernel dispatch, K/V gathered through the block
    table in the DMA descriptors. Subsumes `attention_fwd` + `decode` +
    `decode_paged` on the serving path — decode is the q_len == 1 case."""
    return flashd_varlen_pallas(
        q, k_pages, v_pages,
        jnp.asarray(block_tbl, jnp.int32),
        jnp.asarray(seq_ids, jnp.int32),
        jnp.asarray(q_pos, jnp.int32),
        jnp.asarray(kv_len, jnp.int32).reshape(-1),
        scale=scale, window=window, chunk=chunk, block_q=block_q,
        k_scale=k_scale, v_scale=v_scale, layer=layer,
        interpret=interpret_mode(),
    )


# ---------------------------------------------------------------------------
# jnp fallbacks — same signatures, pure-jnp bodies (graceful degradation)
# ---------------------------------------------------------------------------

@register_fallback("attention_fwd")
def jnp_attention_fwd_batched(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mask: MaskSpec,
    scale: float,
    impl: str,
    block_q: int,
    block_k: int,
    skip: bool,
):
    from repro.core.attention import _attention_core_fwd  # lazy: avoid cycle

    b, sq, hq, _ = q.shape
    o, (_, _, _, _, lam) = _attention_core_fwd(
        q, k, v, mask, scale, impl, block_q, block_k, skip
    )
    return o, lam.reshape(b, hq, sq)


@register_fallback("attention_bwd")
def jnp_attention_bwd_batched(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    o: jax.Array,
    lam: jax.Array,
    do: jax.Array,
    *,
    mask: MaskSpec,
    scale: float,
    impl: str,
    block_q: int,
    block_k: int,
):
    """jnp mirror of the fused backward — `blockwise_backward` vmapped over
    (B, Hkv, G). The differential oracle the Pallas bwd kernel is tested
    against, and the graceful-degradation target for training."""
    import functools as _ft

    from repro.core.blockwise import blockwise_backward  # lazy: avoid cycle

    del impl, block_q
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    dv_ = v.shape[-1]
    qg = q.transpose(0, 2, 1, 3).reshape(b, hkv, g, sq, d)
    og = o.transpose(0, 2, 1, 3).reshape(b, hkv, g, sq, dv_)
    dog = do.transpose(0, 2, 1, 3).reshape(b, hkv, g, sq, dv_)
    lamg = lam.reshape(b, hkv, g, sq)
    kg = k.transpose(0, 2, 1, 3)  # [B, Hkv, Skv, d]
    vg = v.transpose(0, 2, 1, 3)

    fn = _ft.partial(blockwise_backward, mask=mask, scale=scale, block_k=block_k)
    fn = jax.vmap(fn, in_axes=(0, None, None, 0, 0, 0))  # over G
    fn = jax.vmap(fn)  # over Hkv
    fn = jax.vmap(fn)  # over B
    dq, dk, dv = fn(qg, kg, vg, og, lamg, dog)
    dq = dq.reshape(b, hq, sq, d).transpose(0, 2, 1, 3).astype(q.dtype)
    dk = jnp.sum(dk, axis=2).transpose(0, 2, 1, 3).astype(k.dtype)  # sum over G
    dv = jnp.sum(dv, axis=2).transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dv


@register_fallback("decode")
def jnp_decode(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array,
    *,
    scale=None,
    n_splits: int | None = None,
    window: int = 0,
    chunk: int = 0,
    fused: bool = True,
):
    from repro.core.attention import decode_attention  # lazy: avoid cycle

    return decode_attention(
        q if q.ndim == 4 else q[:, None],
        k_cache, v_cache,
        jnp.asarray(cache_len, jnp.int32).reshape(-1),
        scale=scale, window=window, chunk=chunk, n_splits=n_splits,
    )


@register_fallback("decode_paged")
def jnp_decode_paged(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tbl: jax.Array,
    cache_len: jax.Array,
    *,
    scale=None,
    window: int = 0,
    chunk: int = 0,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    layer: jax.Array | None = None,
):
    from repro.core.attention import decode_attention_paged  # lazy: avoid cycle

    return decode_attention_paged(
        q if q.ndim == 4 else q[:, None],
        k_pages, v_pages,
        jnp.asarray(block_tbl, jnp.int32),
        jnp.asarray(cache_len, jnp.int32).reshape(-1),
        scale=scale, window=window, chunk=chunk,
        k_scale=k_scale, v_scale=v_scale, layer=layer,
    )


@register_fallback("varlen")
def jnp_varlen(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tbl: jax.Array,
    seq_ids: jax.Array,
    q_pos: jax.Array,
    kv_len: jax.Array,
    *,
    scale=None,
    window: int = 0,
    chunk: int = 0,
    block_q: int,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    layer: jax.Array | None = None,
):
    from repro.core.attention import varlen_attention  # lazy: avoid cycle

    return varlen_attention(
        q, k_pages, v_pages,
        jnp.asarray(block_tbl, jnp.int32),
        jnp.asarray(seq_ids, jnp.int32),
        jnp.asarray(q_pos, jnp.int32),
        jnp.asarray(kv_len, jnp.int32).reshape(-1),
        scale=scale, window=window, chunk=chunk, impl="flashd",
        block_q=block_q, k_scale=k_scale, v_scale=v_scale, layer=layer,
    )
