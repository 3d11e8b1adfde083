"""Public attention ops: batched, GQA-aware, differentiable FLASH-D.

`flash_attention`  — training / prefill: [B, S, H, d] tensors, tiled scan.
`decode_attention` — single-token decode against a KV cache with dynamic
                     length; optional split-K with FLASH-D sigmoid merging.

impl ∈ {'flashd', 'fa2', 'naive', 'xla', 'flashd_pallas', 'fa2_pallas'}:
  flashd / fa2  — pure-jnp tiled recurrences (run on any backend; these are
                  what the CPU-hosted dry-run lowers).
  *_pallas      — Pallas TPU kernels from repro.kernels (interpret mode on
                  CPU; real kernels on TPU).
  naive         — O(S²) softmax oracle (custom_vjp with the tiled backward,
                  like every impl above).
  xla           — O(S²) softmax DIFFERENTIATED BY XLA: no custom_vjp, the
                  [S, S] probability matrix is saved for the backward. The
                  seed-era training baseline BENCH_train.json compares the
                  fused fwd+bwd pair against (DESIGN.md §6).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.blockwise import (
    MaskSpec,
    NEG_INF,
    blockwise_fa2,
    blockwise_flashd,
    merge_partials,
)

__all__ = [
    "flash_attention",
    "decode_attention",
    "decode_attention_paged",
    "gather_pages",
    "varlen_attention",
    "MaskSpec",
]


def _single_head_fwd(q, k, v, mask, scale, impl, block_q, block_k, skip):
    if impl == "flashd":
        return blockwise_flashd(
            q, k, v, mask=mask, scale=scale, block_q=block_q, block_k=block_k, skip=skip
        )
    if impl == "fa2":
        return blockwise_fa2(q, k, v, mask=mask, scale=scale, block_q=block_q, block_k=block_k)
    if impl == "naive":
        s = (q.astype(jnp.float32) @ k.astype(jnp.float32).T) * scale
        bias = mask.block_bias(jnp.arange(q.shape[0]), jnp.arange(k.shape[0]))
        if bias is not None:
            s = s + bias
        lam = jax.nn.logsumexp(s, axis=-1)
        dead = lam <= NEG_INF / 2  # no visible key → zero row, Λ sentinel
        lam = jnp.where(dead, NEG_INF, lam)
        p = jnp.where(dead[:, None], 0.0, jnp.exp(s - lam[:, None]))
        return p @ v.astype(jnp.float32), lam
    raise ValueError(f"unknown attention impl {impl!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _attention_core(q, k, v, mask, scale, impl, block_q, block_k, skip):
    o, _ = _attention_core_fwd(q, k, v, mask, scale, impl, block_q, block_k, skip)
    return o


def _attention_core_fwd(q, k, v, mask, scale, impl, block_q, block_k, skip):
    """q [B,Sq,Hq,d], k/v [B,Skv,Hkv,d|dv] → o [B,Sq,Hq,dv]; saves Λ."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    if impl.endswith("_pallas"):
        from repro.distributed.sharding import batch_parallel  # lazy: no cycle
        from repro.kernels import ops as kernel_ops  # lazy: avoid import cycle

        kernel = functools.partial(
            kernel_ops.pallas_attention_fwd_batched, mask=mask, scale=scale,
            impl=impl.replace("_pallas", ""), block_q=block_q,
            block_k=block_k, skip=skip,
        )
        # GSPMD cannot partition a Mosaic call: per batch shard under a mesh
        o, lam = batch_parallel(kernel, q, k, v)
        return o, (q, k, v, o, lam.reshape(b, hkv, g, sq))
    # group queries on their shared KV head: [B, Hkv, G, Sq, d]
    qg = q.transpose(0, 2, 1, 3).reshape(b, hkv, g, sq, d)
    kg = k.transpose(0, 2, 1, 3)  # [B, Hkv, Skv, d]
    vg = v.transpose(0, 2, 1, 3)

    fn = functools.partial(
        _single_head_fwd, mask=mask, scale=scale, impl=impl,
        block_q=block_q, block_k=block_k, skip=skip,
    )
    fn = jax.vmap(fn, in_axes=(0, None, None))  # over G
    fn = jax.vmap(fn, in_axes=(0, 0, 0))  # over Hkv
    fn = jax.vmap(fn, in_axes=(0, 0, 0))  # over B
    o, lam = fn(qg, kg, vg)  # o [B,Hkv,G,Sq,dv], lam [B,Hkv,G,Sq]
    dv_ = o.shape[-1]
    o = o.reshape(b, hq, sq, dv_).transpose(0, 2, 1, 3).astype(q.dtype)
    return o, (q, k, v, o, lam)


def _attention_core_bwd(mask, scale, impl, block_q, block_k, skip, res, do):
    """Backward from saved (q, k, v, O, Λ) through the `attention_bwd`
    registry op (kernels/ops.py): `*_pallas` impls run the fused Pallas
    kernel, everything else its jnp fallback twin — which keeps the jnp
    mirror the differential oracle for the training path (DESIGN.md §6).
    Both recompute score tiles from (q, k, Λ); no [Sq, Skv] intermediate
    is ever saved by the forward."""
    q, k, v, o, lam = res
    b, sq, hq, _ = q.shape
    from repro.distributed.sharding import batch_parallel  # lazy: no cycle
    from repro.kernels import ops as kernel_ops  # lazy: avoid import cycle

    args = (q, k, v, o, lam.reshape(b, hq, sq), do)
    kw = dict(mask=mask, scale=scale, impl=impl, block_q=block_q, block_k=block_k)
    if impl.endswith("_pallas"):  # a Mosaic call: per batch shard under a mesh
        op = functools.partial(kernel_ops.get_op("attention_bwd"), **kw)
        return batch_parallel(op, *args)
    return kernel_ops.get_fallback("attention_bwd")(*args, **kw)


_attention_core.defvjp(
    lambda q, k, v, mask, scale, impl, bq, bk, skip: _attention_core_fwd(
        q, k, v, mask, scale, impl, bq, bk, skip
    ),
    _attention_core_bwd,
)


def _xla_attention(q, k, v, mask: MaskSpec, scale: float):
    """Plain softmax attention with NO custom_vjp — XLA's autodiff saves
    the [B, H, Sq, Skv] probabilities for the backward. This is the
    seed-era training datapath and the baseline the fused FLASH-D fwd+bwd
    pair is benchmarked against (BENCH_train.json, DESIGN.md §6)."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    if g > 1:  # GQA: materialize the repeated KV heads (baseline semantics)
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale
    bias = mask.block_bias(jnp.arange(sq), jnp.arange(skv))
    if bias is not None:
        s = s + bias
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mask: MaskSpec = MaskSpec("causal"),
    scale: Optional[float] = None,
    impl: str = "flashd",
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    skip: bool = False,
) -> jax.Array:
    """Multi-head GQA attention. q [B,Sq,Hq,d]; k,v [B,Skv,Hkv,·].

    block_q / block_k = None resolves the tiling from the VMEM-budget
    heuristics in repro.kernels.tuning (shape-static, so jit-stable).

    Context parallelism: when the active ShardingCtx opts into prefill CP
    (`cp_prefill=True`) and the kv_cache rule seq-shards these operands,
    the call routes to the ring schedule in repro.distributed.context —
    per-shard kernels + cross-device FLASH-D Λ-merge, no score gather.
    That path is forward-only (serving/prefill)."""
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError("expected [batch, seq, heads, dim] operands")
    if q.shape[2] % k.shape[2] != 0:
        raise ValueError(f"Hq={q.shape[2]} not a multiple of Hkv={k.shape[2]}")
    if scale is None:
        scale = float(1.0 / (q.shape[-1] ** 0.5))

    if impl == "xla":  # XLA-autodiff baseline: no custom_vjp, no tiling
        return _xla_attention(q, k, v, mask, scale)

    from repro.distributed.context import maybe_ring_prefill  # lazy: no cycle

    o_cp = maybe_ring_prefill(
        q, k, v, mask=mask, scale=scale, impl=impl,
        block_q=block_q, block_k=block_k, skip=skip,
    )
    if o_cp is not None:
        return o_cp

    if block_q is None or block_k is None:
        from repro.kernels.tuning import choose_prefill_blocks  # lazy: no cycle

        tiling = choose_prefill_blocks(
            q.shape[1], k.shape[1], q.shape[-1], v.shape[-1]
        )
        block_q = tiling.block_q if block_q is None else block_q
        block_k = tiling.block_k if block_k is None else block_k
    block_q = min(block_q, max(q.shape[1], 1))
    block_k = min(block_k, max(k.shape[1], 1))
    return _attention_core(q, k, v, mask, scale, impl, block_q, block_k, skip)


def decode_attention(
    q: jax.Array,  # [B, 1, Hq, d] — one new token per sequence
    k_cache: jax.Array,  # [B, S_max, Hkv, d]
    v_cache: jax.Array,  # [B, S_max, Hkv, dv]
    cache_len: jax.Array,  # [B] or scalar — number of valid cache entries
    *,
    scale: Optional[float] = None,
    window: int = 0,  # >0: sliding-window (local) attention
    chunk: int = 0,  # >0: llama4-style chunked attention
    n_splits: Optional[int] = None,  # split-K partitions; None → tuned
) -> jax.Array:
    """Single-step decode against a (possibly sharded) KV cache.

    Uses the einsum formulation (one query row ⇒ attention is linear in S and
    memory-bound: the roofline term is the KV-cache read). With n_splits > 1
    the cache is partitioned along S, each partition yields (o_p, Λ_p), and
    partials are merged with the FLASH-D sigmoid blend (DESIGN.md §2.2) —
    one FMA per merge instead of FA2's rescale/divide. The same merge
    combines *cross-device* partials under context-parallel sharding.
    n_splits=None asks repro.kernels.tuning for a split count; the cache
    is zero-padded up to a multiple of it (padded slots are masked), the
    same convention as the pallas kernel.

    When the active ShardingCtx seq-shards this cache (context parallel —
    see `sharding.cp_axis_for_cache`), the call routes to
    `repro.distributed.context.cp_decode`: per-shard partials + a log-depth
    cross-device butterfly of the same blend, so the wire carries (O, Λ)
    messages instead of a gathered cache.
    """
    b, _, hq, d = q.shape
    s_max = k_cache.shape[1]
    hkv = k_cache.shape[2]
    g = hq // hkv
    if scale is None:
        scale = float(1.0 / (d ** 0.5))

    from repro.distributed.context import maybe_cp_decode  # lazy: no cycle

    o_cp = maybe_cp_decode(
        q, k_cache, v_cache, cache_len, scale=scale, window=window,
        chunk=chunk, n_splits=n_splits,
        # kernel-free per-shard partials, like the rest of this function
        # (dry-runs, any backend)
        use_kernel=False,
    )
    if o_cp is not None:
        return o_cp
    if n_splits is None:
        from repro.kernels.tuning import choose_decode_split  # lazy: no cycle

        n_splits = choose_decode_split(
            s_max, d, v_cache.shape[-1], group=g, window=window, chunk=chunk
        ).n_splits
    n_splits = max(1, min(n_splits, s_max))
    cache_len = jnp.asarray(cache_len)
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, (b,))

    qf = q.astype(jnp.float32).reshape(b, hkv, g, d)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)

    pos = jnp.arange(s_max)
    valid = pos[None, :] < cache_len[:, None]  # [B, S]
    if window > 0:
        valid &= pos[None, :] >= (cache_len[:, None] - window)
    if chunk > 0:
        cur_chunk = (cache_len[:, None] - 1) // chunk
        valid &= (pos[None, :] // chunk) == cur_chunk

    # scores: [B, Hkv, G, S]
    s = jnp.einsum("bhgd,bshd->bhgs", qf, kf, preferred_element_type=jnp.float32)
    s = s * scale
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)

    if n_splits <= 1:
        lam = jax.nn.logsumexp(s, axis=-1)
        p = jnp.exp(s - lam[..., None])
        # rows with no visible key are ZERO (the kernels' dead-partial
        # convention), not the uniform-softmax artifact exp(NEG_INF−NEG_INF)
        p = jnp.where(valid[:, None, None, :], p, 0.0)
        o = jnp.einsum("bhgs,bshd->bhgd", p, vf)
    else:
        dv = v_cache.shape[-1]
        pad = (-s_max) % n_splits  # padded slots score NEG_INF ⇒ dead
        if pad:
            s = jnp.pad(s, ((0, 0), (0, 0), (0, 0), (0, pad)), constant_values=NEG_INF)
            vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0), (0, 0)))
        split = (s_max + pad) // n_splits
        sp = s.reshape(b, hkv, g, n_splits, split).transpose(3, 0, 1, 2, 4)
        vp = vf.reshape(b, n_splits, split, hkv, dv).transpose(1, 0, 2, 3, 4)
        m_p = jnp.max(sp, axis=-1)
        m_safe = jnp.maximum(m_p, NEG_INF / 2)
        p = jnp.exp(sp - m_safe[..., None])
        l_p = jnp.sum(p, axis=-1)
        lam_p = jnp.where(
            l_p > 0, m_safe + jnp.log(jnp.maximum(l_p, jnp.finfo(jnp.float32).tiny)), NEG_INF
        )
        o_p = jnp.einsum("pbhgs,pbshd->pbhgd", p, vp)
        o_p = o_p / jnp.maximum(l_p, jnp.finfo(jnp.float32).tiny)[..., None]
        o, lam = merge_partials(o_p, lam_p)  # FLASH-D split-K merge

    return o.reshape(b, 1, hq, -1).astype(q.dtype)


def varlen_attention(
    q: jax.Array,  # [T, Hq, d] — packed query rows from many sequences
    k_pages: jax.Array,  # [P, page, Hkv, d] — global page pool
    v_pages: jax.Array,  # [P, page, Hkv, dv]
    block_tbl: jax.Array,  # [B, N] i32 per-sequence block tables
    seq_ids: jax.Array,  # [T] i32 owning sequence per row (−1 = padding)
    q_pos: jax.Array,  # [T] i32 absolute KV position per row (−1 = padding)
    kv_len: jax.Array,  # [B] i32 visible KV length per sequence
    *,
    scale: Optional[float] = None,
    window: int = 0,
    chunk: int = 0,
    impl: str = "flashd",
    block_q: Optional[int] = None,
    k_scale: Optional[jax.Array] = None,  # [P, Hkv] f32 — quantized pool
    v_scale: Optional[jax.Array] = None,  # [P, Hkv] f32
    layer: Optional[jax.Array] = None,  # pool stacked [L, P, ...]: read this layer
) -> jax.Array:
    """Packed varlen attention over a paged KV cache → o [T, Hq, dv].

    THE unified serving entry (DESIGN.md §3.5): prefill chunks, whole
    prompts and single decode tokens ride in one flat batch — a decode
    token is just a 1-row segment. Every row attends its own sequence's
    pages under a causal (× window/chunk) mask at its absolute position;
    padding rows (seq_ids < 0) return zeros.

    `impl` ∈ {*_pallas → the fused Pallas kernel (block-table gather in
    the DMA descriptors, in-VMEM sigmoid carry); anything else → this jnp
    mirror}. The mirror gathers each row's pages to a contiguous view, so
    its working set is O(T · N·page) — fine for serving packs, not meant
    for training-sized T. The Pallas path requires the packing contract
    (block_q-aligned segments, see kernels/flashd_varlen.py); rows are
    padded to a block multiple here, but segment ALIGNMENT is the
    caller's job (the scheduler's packer provides it).

    `k_scale`/`v_scale` ([P, Hkv] f32) mark a quantized page pool
    (DESIGN.md §3.8): the kernel dequantizes tiles in VMEM after the DMA
    gather; this mirror dequantizes during its page gather — identical
    arithmetic, so it stays the differential oracle.

    `layer` marks the model's stacked pool (pages [L, P, page, Hkv, ·],
    scales [L, P, Hkv]): the kernel reads that layer through its index
    maps, the mirror gathers from it (DESIGN.md §3.4).
    """
    t, hq, d = q.shape
    page, hkv, dv = v_pages.shape[-3:]
    g = hq // hkv
    if scale is None:
        scale = float(1.0 / (d ** 0.5))
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    seq_ids = jnp.asarray(seq_ids, jnp.int32)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(-1)

    if impl.endswith("_pallas"):
        from repro.kernels import ops as kernel_ops  # lazy: avoid import cycle

        if block_q is None:
            from repro.kernels.tuning import choose_varlen_blocks

            block_q = choose_varlen_blocks(
                t, d, dv, group=g, kv_heads=hkv, page=page,
                kv_itemsize=jnp.dtype(k_pages.dtype).itemsize
                if k_scale is not None else 4,
            ).block_q
        pad = (-t) % block_q
        if pad:
            q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
            seq_ids = jnp.pad(seq_ids, (0, pad), constant_values=-1)
            q_pos = jnp.pad(q_pos, (0, pad), constant_values=-1)
        o = kernel_ops.pallas_varlen(
            q, k_pages, v_pages, block_tbl, seq_ids, q_pos, kv_len,
            scale=scale, window=window, chunk=chunk, block_q=block_q,
            k_scale=k_scale, v_scale=v_scale, layer=layer,
        )
        return o[:t]

    # jnp mirror: gather each row's sequence cache, one einsum per pack.
    sid = jnp.maximum(seq_ids, 0)
    k_cache = gather_pages(k_pages, block_tbl, scales=k_scale, layer=layer)
    v_cache = gather_pages(v_pages, block_tbl, scales=v_scale, layer=layer)
    s_tot = k_cache.shape[1]
    kt = k_cache[sid].astype(jnp.float32)  # [T, S, Hkv, d]
    vt = v_cache[sid].astype(jnp.float32)
    qf = q.astype(jnp.float32).reshape(t, hkv, g, d)

    pos = jnp.arange(s_tot)
    keep = pos[None, :] < kv_len[sid][:, None]  # sequence boundary
    keep &= pos[None, :] <= q_pos[:, None]  # causal at the row's position
    if window > 0:
        keep &= q_pos[:, None] - pos[None, :] < window
    if chunk > 0:
        keep &= q_pos[:, None] // chunk == pos[None, :] // chunk

    s = jnp.einsum("thgd,tshd->thgs", qf, kt, preferred_element_type=jnp.float32)
    s = s * scale
    s = jnp.where(keep[:, None, None, :], s, NEG_INF)
    lam = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lam[..., None])
    # rows with no visible key (padding, empty segments) are ZERO — the
    # kernels' dead-partial convention, not the uniform-softmax artifact
    p = jnp.where(keep[:, None, None, :], p, 0.0)
    o = jnp.einsum("thgs,tshd->thgd", p, vt)
    return o.reshape(t, hq, dv).astype(q.dtype)


def gather_pages(
    pages: jax.Array,
    block_tbl: jax.Array,
    scales: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
) -> jax.Array:
    """[P, page, Hkv, ·] pool + [B, N] table → contiguous [B, N·page, Hkv, ·].

    The jnp materialization of the block-table indirection the paged Pallas
    kernel performs in its DMA descriptors — the oracle for that kernel,
    and the bridge that lets every contiguous-cache consumer (the split-K
    jnp path, cross-device cp_decode) run against a paged cache.

    With `scales` ([P, Hkv] f32, a quantized pool's per-(page, head)
    side-band) the gathered view is dequantized to f32 — the mirror of the
    kernels' in-tile dequant (DESIGN.md §3.8).

    With `layer` the pool is stacked ([L, P, page, Hkv, ·], scales
    [L, P, Hkv]) and the gather reads that layer's pages straight out of
    the stack."""
    b, n = block_tbl.shape
    page, hkv = pages.shape[-3:-1]
    if layer is not None:
        pages, scales = pages[layer], None if scales is None else scales[layer]
    out = pages[block_tbl]  # [B, N, page, Hkv, ·]
    if scales is not None:
        out = out.astype(jnp.float32) * scales[block_tbl][:, :, None, :, None]
    return out.reshape(b, n * page, hkv, pages.shape[-1])


def decode_attention_paged(
    q: jax.Array,  # [B, 1, Hq, d]
    k_pages: jax.Array,  # [P, page, Hkv, d] — global page pool
    v_pages: jax.Array,  # [P, page, Hkv, dv]
    block_tbl: jax.Array,  # [B, N] i32 per-sequence block tables
    cache_len: jax.Array,  # [B]
    *,
    scale: Optional[float] = None,
    window: int = 0,
    chunk: int = 0,
    n_splits: Optional[int] = None,
    k_scale: Optional[jax.Array] = None,  # [P, Hkv] f32 — quantized pool
    v_scale: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,  # pool stacked [L, P, ...]: read this layer
) -> jax.Array:
    """Single-step decode against a paged KV cache (DESIGN.md §3.4).

    This is the backend-agnostic path: gather the sequence's pages into
    contiguous [B, S, Hkv, ·] form and run `decode_attention`, which keeps
    all of its routing — context-parallel `cp_decode` when the active
    ShardingCtx seq-shards the (gathered) cache, tuned split-K with the
    FLASH-D sigmoid merge otherwise. The Pallas hot path
    (`kernels.ops.pallas_decode_paged`) skips the gather entirely: the
    block table becomes a scalar-prefetch operand and the DMA engine
    fetches physical pages directly. Quantized pools (k_scale/v_scale,
    DESIGN.md §3.8) are dequantized during the gather.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    k_cache = gather_pages(k_pages, block_tbl, scales=k_scale, layer=layer)
    v_cache = gather_pages(v_pages, block_tbl, scales=v_scale, layer=layer)
    return decode_attention(
        q, k_cache, v_cache, cache_len, scale=scale, window=window,
        chunk=chunk, n_splits=n_splits,
    )
