"""Context-parallel attention: cross-device FLASH-D sigmoid merge.

Long-context prefill and decode on a sequence-sharded KV cache, built from
two primitives (DESIGN.md §4.1):

`ring_prefill` — shard_map over a seq-sharded Q/K/V with a `ppermute` ring
  schedule. Each device keeps its q shard (and its (O, Λ) carry) resident;
  KV shards rotate one neighbor per hop, each hop running the per-shard
  forward kernel and folding the hop's (O, Λ) into the carry with the §2.2
  sigmoid blend. No running-max exchange, no rescale pass, no final
  division — the wire carries exactly one KV shard per hop and nothing
  else. The canonical +1 rotation puts every device's KV shard exactly
  `h` shards behind its q shard at hop h, so the hop's mask offset is the
  *static* value h·shard and structured masks prune hops at trace time
  (a sliding window only needs ⌈window/shard⌉ + 1 hops of the full ring);
  wrapped shards (device i < h) are strictly future under causal-family
  masks and skip the kernel launch behind a `lax.cond`.

`cp_decode` — each device computes its shard's decode partial (o_p, λ_p)
  with the split-K kernel (`return_lam=True` exposes the merged Λ; the
  `start` bound clips globally-windowed live regions to the shard), then a
  log-depth cross-device butterfly of `ppermute`s merges partials with the
  same blend — the blend is associative AND commutative in (O, Λ), so the
  XOR-partner reduction is exact. log₂(n) hops of (O, Λ)-sized messages
  ([B, Hq, dv] + [B, Hq]) replace any gather of cache- or score-sized
  tensors. Non-power-of-two device counts fall back to one all_gather of
  the partials + the log-depth tree merge.

Both run on a simulated host-device mesh (CPU, Pallas interpret mode) and
unmodified on a real TPU ring. `repro.core.attention` routes here when the
active `ShardingCtx` seq-shards the cache (see `sharding.cp_axis_for_cache`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.blockwise import (
    MaskSpec,
    NEG_INF,
    blockwise_fa2,
    blockwise_flashd,
    merge_pair,
    merge_partials,
)

__all__ = [
    "ring_prefill",
    "cp_decode",
    "maybe_ring_prefill",
    "maybe_cp_decode",
    "ring_applicable",
    "cp_decode_applicable",
]

_CAUSAL_FAMILY = ("causal", "local", "chunked")


def _shard_map(f, mesh, in_specs, out_specs):
    """shard_map with replication checks off: the pallas calls and
    collectives inside have no registered replication rules."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def _resolve_mesh(mesh: Optional[Mesh]) -> Mesh:
    if mesh is not None:
        return mesh
    from repro.distributed.sharding import active_ctx  # lazy: no cycle

    ctx = active_ctx()
    if ctx is None or ctx.mesh is None:
        raise ValueError("context-parallel attention needs a mesh "
                         "(argument or active ShardingCtx)")
    return ctx.mesh


def _axis_size(mesh: Mesh, axis: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[axis]


def ring_applicable(q_shape, k_shape, mask: MaskSpec, n_shards: int) -> bool:
    """Can ring_prefill handle these operands? (Static check — used by
    `core.attention.flash_attention` before routing.)"""
    sq, skv = q_shape[1], k_shape[1]
    if n_shards <= 1 or sq % n_shards or skv % n_shards:
        return False
    if mask.kind in _CAUSAL_FAMILY:
        # shard-offset algebra needs aligned q/kv shards (self-attention)
        if sq != skv:
            return False
        if mask.kind == "chunked" and (skv // n_shards) % max(mask.chunk, 1):
            return False  # hop offsets shift chunk boundaries
    return True


def cp_decode_applicable(cache_shape, n_shards: int) -> bool:
    return n_shards > 1 and cache_shape[1] % n_shards == 0


# ---------------------------------------------------------------------------
# per-shard forward (one ring hop's local attention)
# ---------------------------------------------------------------------------

def _shard_fwd(q, k, v, *, mask, scale, impl, block_q, block_k, skip, interpret):
    """Kernel-layout forward on one KV shard → (o [B,Hq,S,dv] f32, Λ f32)."""
    if impl in ("flashd_pallas", "fa2_pallas"):
        from repro.kernels.fa2_fwd import fa2_fwd_pallas  # lazy: no cycle
        from repro.kernels.flashd_fwd import flashd_fwd_pallas

        fn = flashd_fwd_pallas if impl == "flashd_pallas" else fa2_fwd_pallas
        kw = dict(mask=mask, scale=scale, block_q=block_q, block_k=block_k,
                  interpret=interpret)
        if impl == "flashd_pallas":
            kw["skip"] = skip
        o, lam = fn(q, k, v, **kw)
        return o.astype(jnp.float32), lam
    if impl == "naive":
        from repro.kernels.ref import attention_ref  # lazy: no cycle

        o, lam = attention_ref(q, k, v, mask=mask, scale=scale)
        return o.astype(jnp.float32), lam
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    base = blockwise_flashd if impl == "flashd" else blockwise_fa2
    fn = functools.partial(base, mask=mask, scale=scale,
                           block_q=block_q, block_k=block_k)
    if impl == "flashd":
        fn = functools.partial(fn, skip=skip)
    fn = jax.vmap(fn, in_axes=(0, None, None))  # over G
    fn = jax.vmap(fn, in_axes=(0, 0, 0))  # over Hkv
    fn = jax.vmap(fn, in_axes=(0, 0, 0))  # over B
    o, lam = fn(q.reshape(b, hkv, g, sq, d), k, v)
    dv_ = o.shape[-1]
    return o.reshape(b, hq, sq, dv_), lam.reshape(b, hq, sq)


# ---------------------------------------------------------------------------
# ring prefill
# ---------------------------------------------------------------------------

def ring_prefill(
    q: jax.Array,  # [B, Sq, Hq, d]   (model layout, like flash_attention)
    k: jax.Array,  # [B, Skv, Hkv, d]
    v: jax.Array,  # [B, Skv, Hkv, dv]
    *,
    axis: str,
    mesh: Optional[Mesh] = None,
    mask: MaskSpec = MaskSpec("causal"),
    scale: Optional[float] = None,
    impl: str = "flashd_pallas",
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    skip: bool = False,
    batch_axes: Optional[Tuple[str, ...]] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Context-parallel prefill: per-shard kernels + cross-device Λ-merge.

    Returns o [B, Sq, Hq, dv], sequence-sharded over `axis` like q (and
    batch-sharded over `batch_axes` when given — a batch+seq-sharded
    operand set must keep its batch sharding inside the shard_map, or the
    unmentioned dims would be gathered). Wire per hop = one KV shard
    (ppermute); the (O, Λ) carry never moves — it stays with its q shard.
    Forward-only (serving/prefill path): the ring schedule has no
    registered VJP.
    """
    mesh = _resolve_mesh(mesh)
    n = _axis_size(mesh, axis)
    if not ring_applicable(q.shape, k.shape, mask, n):
        raise ValueError(
            f"ring_prefill: {q.shape}/{k.shape} with {mask.kind!r} mask not "
            f"context-parallelizable over {n} shards"
        )
    if scale is None:
        scale = float(1.0 / (q.shape[-1] ** 0.5))
    if interpret is None:
        from repro.kernels.ops import interpret_mode  # lazy: no cycle

        interpret = interpret_mode()
    sq_sh, skv_sh = q.shape[1] // n, k.shape[1] // n
    if block_q is None or block_k is None:
        from repro.kernels.tuning import choose_ring_schedule  # lazy: no cycle

        sched = choose_ring_schedule(
            sq_sh, skv_sh, q.shape[-1], v.shape[-1], n_devices=n, mask=mask
        )
        block_q = sched.block_q if block_q is None else block_q
        block_k = sched.block_k if block_k is None else block_k
        n_hops = sched.n_hops
    else:
        from repro.kernels.tuning import choose_ring_schedule

        n_hops = choose_ring_schedule(
            sq_sh, skv_sh, q.shape[-1], v.shape[-1], n_devices=n, mask=mask
        ).n_hops
    block_q = min(block_q, sq_sh)
    block_k = min(block_k, skv_sh)
    causal_family = mask.kind in _CAUSAL_FAMILY
    perm = [(j, (j + 1) % n) for j in range(n)]

    def local_fn(q_l, k_l, v_l):
        # kernel layout for the per-shard attention
        qk = q_l.transpose(0, 2, 1, 3)  # [B, Hq, sq_sh, d]
        kk = k_l.transpose(0, 2, 1, 3)
        vk = v_l.transpose(0, 2, 1, 3)
        idx = jax.lax.axis_index(axis)
        b, hq = qk.shape[0], qk.shape[1]

        o = lam = None  # hop 0 seeds the carry (always live everywhere)
        for h in range(n_hops):
            # hop h: resident KV shard is h shards behind the q shard, so
            # every position offset is the static h·skv_sh (wrapped shards
            # are strictly future under causal-family masks — dead below)
            hop_mask = dataclasses.replace(
                mask,
                kind=("full" if _hop_fully_visible(mask, h, sq_sh, skv_sh)
                      else mask.kind),
                q_offset=mask.q_offset + h * skv_sh,
            )
            run = functools.partial(
                _shard_fwd, mask=hop_mask, scale=scale, impl=impl,
                block_q=block_q, block_k=block_k, skip=skip,
                interpret=interpret,
            )
            if causal_family and h > 0:
                # devices i < h hold a wrapped (future) shard: skip the
                # kernel launch entirely, contribute a dead partial
                def _dead(kv, _b=b, _hq=hq, _dv=vk.shape[-1]):
                    return (
                        jnp.zeros((_b, _hq, sq_sh, _dv), jnp.float32),
                        jnp.full((_b, _hq, sq_sh), NEG_INF, jnp.float32),
                    )

                o_p, lam_p = jax.lax.cond(
                    idx >= h, lambda kv: run(qk, kv[0], kv[1]), _dead, (kk, vk)
                )
            else:
                o_p, lam_p = run(qk, kk, vk)
            o, lam = (o_p, lam_p) if o is None else merge_pair((o, lam), (o_p, lam_p))
            if h < n_hops - 1:  # rotate the KV shard one neighbor over
                kk = jax.lax.ppermute(kk, axis, perm)
                vk = jax.lax.ppermute(vk, axis, perm)
        return o.transpose(0, 2, 1, 3).astype(q_l.dtype)

    seq_spec = P(batch_axes, axis, None, None)
    return _shard_map(
        local_fn, mesh,
        in_specs=(seq_spec, seq_spec, seq_spec),
        out_specs=seq_spec,
    )(q, k, v)


def _hop_fully_visible(mask: MaskSpec, h: int, sq_sh: int, skv_sh: int) -> bool:
    """Static: is hop h's whole shard-vs-shard block inside the mask (for
    non-wrapped devices)? Then the kernel runs mask-free ('full')."""
    if h == 0 or mask.kind not in _CAUSAL_FAMILY:
        return False
    hop = dataclasses.replace(mask, q_offset=mask.q_offset + h * skv_sh)
    return hop.block_fully_visible(0, sq_sh, 0, skv_sh)


# ---------------------------------------------------------------------------
# context-parallel decode
# ---------------------------------------------------------------------------

def maybe_cp_decode(q, k_cache, v_cache, cache_len, *, scale=None, window=0,
                    chunk=0, n_splits=None, use_kernel=True):
    """The one selection point for context-parallel decode: returns
    `cp_decode(...)` iff the active ShardingCtx's kv_cache rule seq-shards
    this cache (`sharding.cp_axis_for_cache`), else None — callers fall
    through to their single-device path. Keeps the routing decision out of
    `core.attention` / `models.transformer`, which would otherwise each
    re-implement it. The cache's batch sharding (if any) is preserved
    inside the shard_map."""
    from repro.distributed.sharding import (
        active_ctx, cp_axis_for_cache, cp_batch_axes_for_cache,
    )

    ctx = active_ctx()
    if ctx is None:
        return None
    axis = cp_axis_for_cache(k_cache.shape)
    if axis is None:
        return None
    return cp_decode(
        q, k_cache, v_cache, cache_len, axis=axis, mesh=ctx.mesh, scale=scale,
        window=window, chunk=chunk, n_splits=n_splits, use_kernel=use_kernel,
        batch_axes=cp_batch_axes_for_cache(k_cache.shape),
    )


def maybe_ring_prefill(q, k, v, *, mask, scale=None, impl="flashd",
                       block_q=None, block_k=None, skip=False):
    """Selection point for context-parallel prefill, the `maybe_cp_decode`
    counterpart: returns `ring_prefill(...)` iff the active ShardingCtx
    opts in (`cp_prefill=True`), its kv_cache rule seq-shards these
    operands, and the ring schedule applies (divisible shards, aligned
    causal-family masks) — else None."""
    from repro.distributed.sharding import (
        active_ctx, cp_axis_for_cache, cp_batch_axes_for_cache,
    )

    ctx = active_ctx()
    if ctx is None or not getattr(ctx, "cp_prefill", False):
        return None
    axis = cp_axis_for_cache(k.shape)
    if axis is None or not ring_applicable(q.shape, k.shape, mask, ctx.axis_size(axis)):
        return None
    return ring_prefill(
        q, k, v, axis=axis, mesh=ctx.mesh, mask=mask, scale=scale, impl=impl,
        block_q=block_q, block_k=block_k, skip=skip,
        batch_axes=cp_batch_axes_for_cache(k.shape),
    )


def _jnp_shard_partial(q, k_sh, v_sh, hi, start, scale):
    """Pure-jnp per-shard decode partial (o_p [B,Hq,dv] f32, λ_p [B,Hq]) —
    the kernel-free analogue of `flashd_decode._split_partial` for the
    einsum decode path."""
    b, hq, d = q.shape
    hkv, s_sh = k_sh.shape[2], k_sh.shape[1]
    g = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, g, d)
    s = jnp.einsum("bhgd,bshd->bhgs", qf, k_sh.astype(jnp.float32)) * scale
    pos = jnp.arange(s_sh)
    keep = (pos[None, :] >= start[:, None]) & (pos[None, :] < hi[:, None])
    s = jnp.where(keep[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(s - m_safe[..., None])
    l = jnp.sum(p, axis=-1)
    lam = jnp.where(
        l > 0, m_safe + jnp.log(jnp.maximum(l, jnp.finfo(jnp.float32).tiny)),
        NEG_INF,
    )
    o = jnp.einsum("bhgs,bshd->bhgd", p, v_sh.astype(jnp.float32))
    o = o * jnp.where(l > 0, jnp.exp(m_safe - lam), 0.0)[..., None]
    return o.reshape(b, hq, -1), lam.reshape(b, hq)


def cp_decode(
    q: jax.Array,  # [B, 1, Hq, d] or [B, Hq, d]
    k_cache: jax.Array,  # [B, S, Hkv, d]  — sequence-sharded over `axis`
    v_cache: jax.Array,  # [B, S, Hkv, dv]
    cache_len: jax.Array,  # [B] or scalar — GLOBAL valid length
    *,
    axis: str,
    mesh: Optional[Mesh] = None,
    scale: Optional[float] = None,
    window: int = 0,
    chunk: int = 0,
    n_splits: Optional[int] = None,
    use_kernel: bool = True,
    fused: bool = True,
    batch_axes: Optional[Tuple[str, ...]] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Single-token decode against a seq-sharded cache; partials merged
    with a log-depth cross-device butterfly of the FLASH-D blend.

    Each shard clips the global live region [lo_bound, cache_len) (window/
    chunk masks shrink lo_bound) to its own range — shard-empty shards
    produce dead partials (Λ = NEG_INF) that merge as identities, so
    ragged `cache_len` needs no special casing. Returns o shaped like q.

    `batch_axes` carries the cache's batch sharding (heads-not-divisible
    CP shards batch over data AND seq over model) through the shard_map —
    leaving those dims unspecified would gather the cache's batch dim,
    exactly the wire cost this path exists to avoid. The butterfly only
    reduces over `axis`; the output stays batch-sharded.
    """
    squeezed = q.ndim == 3
    if squeezed:
        q = q[:, None]
    b, _, hq, d = q.shape
    s_max = k_cache.shape[1]
    mesh = _resolve_mesh(mesh)
    n = _axis_size(mesh, axis)
    if not cp_decode_applicable(k_cache.shape, n):
        raise ValueError(f"cp_decode: cache seq {s_max} not shardable over {n}")
    if scale is None:
        scale = float(1.0 / (d ** 0.5))
    if interpret is None:
        from repro.kernels.ops import interpret_mode  # lazy: no cycle

        interpret = interpret_mode()
    s_sh = s_max // n
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))

    from repro.kernels.flashd_decode import _lo_bound  # lazy: no cycle

    def local_fn(q_g, k_sh, v_sh, cl):
        idx = jax.lax.axis_index(axis)
        shard_lo = idx * s_sh
        lo_g = jnp.broadcast_to(
            _lo_bound(cl, jnp.int32(0), window=window, chunk=chunk), cl.shape
        )
        start_l = jnp.clip(lo_g - shard_lo, 0, s_sh)
        hi_l = jnp.clip(cl - shard_lo, 0, s_sh)
        qk = q_g[:, 0]  # [B, Hq, d]
        if use_kernel:
            from repro.kernels.flashd_decode import flashd_decode_pallas

            o_p, lam_p = flashd_decode_pallas(
                qk, k_sh.transpose(0, 2, 1, 3), v_sh.transpose(0, 2, 1, 3),
                hi_l, start=start_l, scale=scale, n_splits=n_splits,
                fused=fused, return_lam=True, interpret=interpret,
            )
            o_p = o_p.astype(jnp.float32)
        else:
            o_p, lam_p = _jnp_shard_partial(qk, k_sh, v_sh, hi_l, start_l, scale)

        # log-depth cross-device tree: the blend is associative and
        # commutative, so XOR-partner butterflies all-reduce it exactly
        if n & (n - 1) == 0:
            step = 1
            while step < n:
                bp = [(j, j ^ step) for j in range(n)]
                o_r = jax.lax.ppermute(o_p, axis, bp)
                lam_r = jax.lax.ppermute(lam_p, axis, bp)
                o_p, lam_p = merge_pair((o_p, lam_p), (o_r, lam_r))
                step *= 2
        else:  # non-power-of-two ring: gather partials, tree-merge locally
            o_all = jax.lax.all_gather(o_p, axis)
            lam_all = jax.lax.all_gather(lam_p, axis)
            o_p, lam_p = merge_partials(o_all, lam_all)
        return o_p.astype(q_g.dtype)

    q_spec = P(batch_axes, None, None, None)
    kv_spec = P(batch_axes, axis, None, None)
    o = _shard_map(
        local_fn, mesh,
        in_specs=(q_spec, kv_spec, kv_spec, P(batch_axes)),
        out_specs=P(batch_axes, None, None),
    )(q, k_cache, v_cache, cache_len)
    o = o[:, None]  # [B, 1, Hq, dv]
    return o[:, 0] if squeezed else o
