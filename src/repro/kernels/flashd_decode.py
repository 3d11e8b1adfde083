"""Pallas TPU kernel: FLASH-D split-K decode (flash-decoding adapted).

One new token per sequence attends a long KV cache. The cache is split along
the sequence axis; each split yields a partial (o_p, λ_p) pair and partials
are merged with the FLASH-D sigmoid blend

    o ← o_a + (o_b − o_a)·σ(λ_b − λ_a)

— one sigmoid + one vector FMA per merge, where the FA2 merge needs two
exp-rescales and a division (beyond-paper contribution, DESIGN.md §2.2).

Two execution modes:

fused=True (default) — the split axis is the innermost sequential
  ("arbitrary") grid dimension and the merge carry (acc, Λ) lives in VMEM
  scratch, exactly the `flashd_fwd_pallas` carry pattern. The kernel emits
  the final [B, Hq, dv] output directly: zero per-split HBM partials, no
  host-side moveaxis / merge scan. This is the decode hot path.

paged (`flashd_decode_paged_pallas`) — the fused carry structure, but K/V
  live in a global page pool ([P, page, Hkv, d]) addressed through a
  per-sequence block table. The table, cache_len and the layer of a
  stacked [L, P, ...] pool enter as scalar-prefetch operands: the K/V
  BlockSpec index maps read `(layer, tbl[b, ip])` so each sequential grid
  step DMAs the *physical* page of
  logical page ip — the gather happens in the DMA engine, the kernel body
  and the in-VMEM merge are identical to the fused path. This is what the
  paged serving cache (runtime/kvcache.py, DESIGN.md §3.4) decodes with.

fused=False — the historical multi-output form: every split writes its
  (o_p, λ_p) to HBM and the merge runs on the host graph via
  `merge_partials` (a log-depth pairwise tree of the same blend — the op
  sequence differs from the fused carry's sequential order, but the blend
  is associative, so the two paths agree to a few f32 ulps). Kept as the
  oracle for the fused kernel and as the cross-device merge building block
  for context-parallel caches (repro.distributed.context).

Dynamic cache bounds enter as scalar operands (i32 arrays indexed per batch
row): `cache_len` is the exclusive upper bound and the optional `start` a
per-row inclusive lower bound — context-parallel callers use it to clip a
globally-windowed live region [start, cache_len) to their shard. Sliding-
window / chunked masks for recurrentgemma / llama4 decode are applied
in-kernel, so only live splits do work (`pl.when` on split bounds).
`return_lam=True` additionally emits the merged Λ [B, Hq], which is what a
cross-device merge needs to keep blending.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.blockwise import NEG_INF, merge_partials

__all__ = ["flashd_decode_pallas", "flashd_decode_paged_pallas"]

# Mosaic tiling: every block's last two dims are (8k, 128k) or the full
# array dims, and every in-kernel vector is at least 2-D. Row statistics
# (m, λ, Λ) are therefore [rows, 1] columns, and per-row scalars of the
# grid (cache_len, start) ride in SMEM as scalar-prefetch operands.


def _split_partial(cache_len, start, q, k, v, *, lo, split, window, chunk, scale):
    """Per-split normalized partial (o_p [G, dv], λ_p [G, 1]) — shared by
    the fused, unfused and paged kernels so their per-split arithmetic is
    identical. q [G, d], k [split, d], v [split, dv] (already f32)."""
    lo_bound = _lo_bound(cache_len, start, window=window, chunk=chunk)
    pos = lo + jax.lax.broadcasted_iota(jnp.int32, (1, split), 1)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [G, split]
    keep = jnp.logical_and(pos >= lo_bound, pos < cache_len)
    s = jnp.where(keep, s, NEG_INF)
    return _normalized_partial(s, v)


def _normalized_partial(s, v):
    """(softmax(s)·V, λ = logsumexp(s)) of one masked score tile s [R, n]
    against v [n, dv]; fully-masked rows come back dead (λ = NEG_INF,
    o = 0), the identity of the sigmoid merge."""
    m = jnp.max(s, axis=-1, keepdims=True)
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(s - m_safe)
    l = jnp.sum(p, axis=-1, keepdims=True)
    lam = jnp.where(
        l > 0,
        m_safe + jnp.log(jnp.maximum(l, jnp.finfo(jnp.float32).tiny)),
        NEG_INF,
    )
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    c = jnp.where(l > 0, jnp.exp(m_safe - lam), 0.0)  # ⇒ pv·c = softmax·V
    return pv * c, lam


def _lo_bound(cache_len, start, *, window: int, chunk: int):
    """Inclusive lower bound of the live region: window/chunk structure ∨
    the caller's explicit per-row `start` (context-parallel shard clip)."""
    lo_bound = jnp.maximum(jnp.int32(0), start)
    if window > 0:
        lo_bound = jnp.maximum(lo_bound, cache_len - window)
    if chunk > 0:
        lo_bound = jnp.maximum(lo_bound, ((cache_len - 1) // chunk) * chunk)
    return lo_bound


def _split_live(cache_len, start, lo, split, *, window: int, chunk: int):
    """A split is live iff it overlaps [lo_bound, cache_len)."""
    lo_bound = _lo_bound(cache_len, start, window=window, chunk=chunk)
    return jnp.logical_and(lo < cache_len, lo + split > lo_bound)


def _merge_into_carry(o_p, lam_p, acc_ref, lam_scratch):
    """FLASH-D sigmoid merge of one partial into the VMEM carry — the same
    blend op as blockwise.merge_pair, applied sequentially along the
    innermost grid axis. Shared by the fused, paged and varlen kernels;
    λ_p and the Λ carry are [rows, 1] columns."""
    lam_run = lam_scratch[...]
    w = jax.nn.sigmoid(lam_p - lam_run)
    dead_b = lam_p <= NEG_INF / 2
    dead_a = lam_run <= NEG_INF / 2
    w = jnp.where(dead_b, 0.0, jnp.where(dead_a, 1.0, w))
    acc = acc_ref[...]
    acc_ref[...] = acc + (o_p - acc) * w
    ln_w1 = jax.nn.log_sigmoid(lam_run - lam_p)  # ln(1−w)
    lam_scratch[...] = jnp.where(
        dead_b, lam_run, jnp.where(dead_a, lam_p, lam_run - ln_w1)
    )


def _decode_fused_kernel(
    cache_len_ref, start_ref,  # scalar prefetch (SMEM)
    q_ref, k_ref, v_ref,
    *refs,  # outputs (o [, λ]) then VMEM scratch (acc, Λ carry)
    split: int,
    n_splits: int,
    window: int,
    chunk: int,
    scale: float,
    emit_lam: bool,
):
    if emit_lam:
        o_ref, lam_ref, acc_ref, lam_scratch = refs
    else:
        (o_ref, acc_ref, lam_scratch), lam_ref = refs, None
    ib = pl.program_id(0)
    ip = pl.program_id(2)  # innermost, sequential
    cache_len = cache_len_ref[ib]
    start = start_ref[ib]
    lo = ip * split

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        lam_scratch[...] = jnp.full_like(lam_scratch, NEG_INF)

    @pl.when(_split_live(cache_len, start, lo, split, window=window, chunk=chunk))
    def _body():
        o_p, lam_p = _split_partial(
            cache_len, start,
            q_ref[0, 0].astype(jnp.float32),
            k_ref[0, 0].astype(jnp.float32),
            v_ref[0, 0].astype(jnp.float32),
            lo=lo, split=split, window=window, chunk=chunk, scale=scale,
        )
        _merge_into_carry(o_p, lam_p, acc_ref, lam_scratch)

    @pl.when(ip == n_splits - 1)
    def _finalize():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)
        if emit_lam:
            lam_ref[0, 0] = lam_scratch[...]


def _decode_unfused_kernel(
    cache_len_ref, start_ref,  # scalar prefetch (SMEM)
    q_ref, k_ref, v_ref,
    o_ref, lam_ref,
    *,
    split: int,
    window: int,
    chunk: int,
    scale: float,
):
    ib = pl.program_id(0)
    ip = pl.program_id(2)
    cache_len = cache_len_ref[ib]
    start = start_ref[ib]
    lo = ip * split
    live = _split_live(cache_len, start, lo, split, window=window, chunk=chunk)

    @pl.when(live)
    def _body():
        o_p, lam = _split_partial(
            cache_len, start,
            q_ref[0, 0].astype(jnp.float32),
            k_ref[0, 0].astype(jnp.float32),
            v_ref[0, 0].astype(jnp.float32),
            lo=lo, split=split, window=window, chunk=chunk, scale=scale,
        )
        o_ref[0, 0, 0] = o_p.astype(o_ref.dtype)
        lam_ref[0, 0, 0] = lam

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)
        lam_ref[...] = jnp.full_like(lam_ref, NEG_INF)


def flashd_decode_pallas(
    q: jax.Array,  # [B, Hq, d] — one token per sequence
    k_cache: jax.Array,  # [B, Hkv, S_max, d]
    v_cache: jax.Array,  # [B, Hkv, S_max, dv]
    cache_len: jax.Array,  # [B] i32
    *,
    scale: Optional[float] = None,
    n_splits: Optional[int] = None,
    window: int = 0,
    chunk: int = 0,
    start: Optional[jax.Array] = None,  # [B] i32 inclusive lower bound
    fused: bool = True,
    return_lam: bool = False,
    interpret: bool = False,
):
    """Returns o [B, Hq, dv] (or (o, Λ [B, Hq] f32) with return_lam=True).
    Split partials merged with the FLASH-D blend.

    n_splits=None picks the split count from the tuning heuristics
    (repro.kernels.tuning). fused=True merges in VMEM (single HBM output);
    fused=False emits per-split HBM partials and merges on the host graph
    (the oracle path). `start` clips the live region to [start, cache_len)
    per batch row — context-parallel callers pass their shard's slice of a
    globally-windowed region; `return_lam` exposes the merged Λ so those
    callers can keep blending partials across devices.
    """
    b, hq, d = q.shape
    _, hkv, s_max, dv = v_cache.shape
    g = hq // hkv
    if scale is None:
        scale = float(1.0 / (d ** 0.5))
    if n_splits is None:
        from repro.kernels.tuning import choose_decode_split  # lazy: no cycle

        n_splits = choose_decode_split(
            s_max, d, dv, group=g, window=window, chunk=chunk
        ).n_splits
    n_splits = max(1, min(n_splits, s_max))
    pad = (-s_max) % n_splits
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
    split = (s_max + pad) // n_splits

    qg = q.reshape(b, hkv, g, d)
    cache_len = jnp.asarray(cache_len, jnp.int32).reshape(b)
    if start is None:
        start = jnp.zeros((b,), jnp.int32)
    else:
        start = jnp.asarray(start, jnp.int32).reshape(b)

    in_specs = [
        pl.BlockSpec((1, 1, g, d), lambda b_, h, ip, cl, st: (b_, h, 0, 0)),
        pl.BlockSpec((1, 1, split, d), lambda b_, h, ip, cl, st: (b_, h, ip, 0)),
        pl.BlockSpec((1, 1, split, dv), lambda b_, h, ip, cl, st: (b_, h, ip, 0)),
    ]
    grid = (b, hkv, n_splits)

    if fused:
        kernel = functools.partial(
            _decode_fused_kernel, split=split, n_splits=n_splits,
            window=window, chunk=chunk, scale=scale, emit_lam=return_lam,
        )
        # one output block revisited across splits — written once, at the
        # last split, from the VMEM carry: no per-split HBM partials
        out_specs = [
            pl.BlockSpec((1, 1, g, dv), lambda b_, h, ip, cl, st: (b_, h, 0, 0))
        ]
        out_shape = [jax.ShapeDtypeStruct((b, hkv, g, dv), q.dtype)]
        if return_lam:
            out_specs.append(
                pl.BlockSpec((1, 1, g, 1), lambda b_, h, ip, cl, st: (b_, h, 0, 0))
            )
            out_shape.append(jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32))
        call = pl.pallas_call(
            kernel,
            name="flashd_decode_fused",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=grid,
                in_specs=in_specs,
                out_specs=out_specs if return_lam else out_specs[0],
                scratch_shapes=[
                    pltpu.VMEM((g, dv), jnp.float32),
                    pltpu.VMEM((g, 1), jnp.float32),
                ],
            ),
            out_shape=out_shape if return_lam else out_shape[0],
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")
            ),
        )
        out = call(cache_len, start, qg, k_cache, v_cache)
        if return_lam:
            o, lam = out
            return o.reshape(b, hq, dv), lam.reshape(b, hq)
        return out.reshape(b, hq, dv)

    kernel = functools.partial(
        _decode_unfused_kernel, split=split, window=window, chunk=chunk, scale=scale
    )
    call = pl.pallas_call(
        kernel,
        name="flashd_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec(
                    (1, 1, 1, g, dv), lambda b_, h, ip, cl, st: (b_, h, ip, 0, 0)
                ),
                pl.BlockSpec(
                    (1, 1, 1, g, 1), lambda b_, h, ip, cl, st: (b_, h, ip, 0, 0)
                ),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, n_splits, g, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, n_splits, g, 1), jnp.float32),
        ],
        interpret=interpret,
    )
    o_p, lam_p = call(cache_len, start, qg, k_cache, v_cache)
    # FLASH-D sigmoid merge over splits (axis moved to front for the tree)
    o_p = jnp.moveaxis(o_p, 2, 0)  # [P, B, Hkv, G, dv]
    lam_p = jnp.moveaxis(lam_p[..., 0], 2, 0)
    o, lam = merge_partials(o_p, lam_p)
    o = o.reshape(b, hq, dv).astype(q.dtype)
    if return_lam:
        return o, lam.reshape(b, hq)
    return o


# ---------------------------------------------------------------------------
# paged variant: K/V gathered through a block table (scalar prefetch)
# ---------------------------------------------------------------------------

def _decode_paged_kernel(
    tbl_ref, cache_len_ref, layer_ref,  # scalar prefetch (SMEM); layer: index maps only
    q_ref, k_ref, v_ref,  # VMEM blocks (k/v: the ip-th *physical* page)
    *refs,  # quantized: (ks, vs) scale blocks; then o, then VMEM carry
    page: int,
    n_tbl: int,
    window: int,
    chunk: int,
    scale: float,
    quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, lam_scratch = refs
    else:
        (o_ref, acc_ref, lam_scratch), ks_ref, vs_ref = refs, None, None
    ib = pl.program_id(0)
    ip = pl.program_id(1)  # logical page index — innermost, sequential
    cache_len = cache_len_ref[ib]
    start = jnp.int32(0)
    lo = ip * page

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        lam_scratch[...] = jnp.full_like(lam_scratch, NEG_INF)

    @pl.when(_split_live(cache_len, start, lo, page, window=window, chunk=chunk))
    def _body():
        for h in range(k_ref.shape[2]):  # static: Mosaic slices heads statically
            k = k_ref[0, :, h, :].astype(jnp.float32)  # [page, d]
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if quantized:  # dequant in-tile: one per-(page, head) f32 scale each
                k = k * ks_ref[0, :, h:h + 1]
                v = v * vs_ref[0, :, h:h + 1]
            o_p, lam_p = _split_partial(
                cache_len, start, q_ref[0, h].astype(jnp.float32), k, v,
                lo=lo, split=page, window=window, chunk=chunk, scale=scale,
            )
            _merge_into_carry(o_p, lam_p, acc_ref.at[h], lam_scratch.at[h])

    @pl.when(ip == n_tbl - 1)
    def _finalize():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def stacked_pool(k_pages, v_pages, k_scale, v_scale, layer):
    """Kernel views of a page pool → (layer [1] i32, k, v, scale blocks).

    The paged kernels read one layer of a STACKED pool — pages
    [L, P, page, Hkv, d], scales [L, P, Hkv] — whose layer rides in as a
    scalar-prefetch operand of the K/V index maps. The model's layer loop
    therefore hands every layer the whole pool and never copies one layer
    out and back (DESIGN.md §3.4). layer=None takes a single-layer
    [P, page, Hkv, d] pool as a stack of one (a leading-axis reshape).

    A grid step fetches one whole page, all kv heads: the pool is read
    unreshaped as (1, page, Hkv, d) blocks, whose trailing dims equal the
    array's for every head width (a one-head (page, d) block tiles only
    when d is a multiple of 128, and a reshape of the pool would copy it
    on every call). The layer's scales [P, Hkv] become [P, 1, Hkv] so the
    page's (1, Hkv) row is such a block too; that reshape changes the
    tiling and so copies, which is why it takes the one layer's scales
    (P · Hkv floats) and not the stack's. () for a native pool."""
    if layer is None:
        layer = 0
        k_pages, v_pages = k_pages[None], v_pages[None]
    elif k_scale is not None:
        k_scale, v_scale = k_scale[layer], v_scale[layer]
    scales = () if k_scale is None else tuple(
        jnp.asarray(sc, jnp.float32)[:, None, :] for sc in (k_scale, v_scale)
    )
    return jnp.asarray(layer, jnp.int32).reshape(1), k_pages, v_pages, scales


def flashd_decode_paged_pallas(
    q: jax.Array,  # [B, Hq, d] — one token per sequence
    k_pages: jax.Array,  # [P, page, Hkv, d] — global page pool ([L, P, ...]: layer)
    v_pages: jax.Array,  # [P, page, Hkv, dv]
    block_tbl: jax.Array,  # [B, N] i32 — physical page id of logical page j
    cache_len: jax.Array,  # [B] i32
    *,
    scale: Optional[float] = None,
    window: int = 0,
    chunk: int = 0,
    k_scale: Optional[jax.Array] = None,  # [P, Hkv] f32 — quantized pool
    v_scale: Optional[jax.Array] = None,  # [P, Hkv] f32
    layer: Optional[jax.Array] = None,  # i32: pool stacked [L, P, ...], read layer
    interpret: bool = False,
):
    """Fused FLASH-D decode over a paged KV cache → o [B, Hq, dv].

    Grid (B, N) with the logical-page axis innermost and sequential;
    `block_tbl` and `cache_len` are scalar-prefetch operands, so the K/V
    BlockSpec index maps resolve `tbl[b, ip]` *before* the step's DMA is
    issued — the kernel never sees the indirection, each step's K/V block
    is one physical page (all kv heads), and every head's (acc, Λ) carry
    merges pages with the same one-sigmoid-one-FMA blend as the contiguous
    fused kernel. Table slots past the live region may hold anything
    (engine convention: garbage page 0) — their pages are DMA'd but
    `pl.when`-skipped, like padded splits.

    With `k_scale`/`v_scale` the pool is quantized (runtime/quant.py,
    DESIGN.md §3.8): the same index maps fetch the page's per-head f32
    scales and each head's tile is dequantized right after its upcast,
    before the scores — nothing downstream of the multiply changes.

    With `layer` the pool (pages and scales) carries a leading layer axis
    and the index maps read layer `layer` of it (`stacked_pool`).
    """
    layer, k_pages, v_pages, scales = stacked_pool(
        k_pages, v_pages, k_scale, v_scale, layer
    )
    b, hq, d = q.shape
    _, _, page, hkv, dv = v_pages.shape
    n_tbl = block_tbl.shape[1]
    g = hq // hkv
    if scale is None:
        scale = float(1.0 / (d ** 0.5))
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    quantized = k_scale is not None
    block_tbl = jnp.asarray(block_tbl, jnp.int32)
    cache_len = jnp.asarray(cache_len, jnp.int32).reshape(b)

    qg = q.reshape(b, hkv, g, d)
    kernel = functools.partial(
        _decode_paged_kernel, page=page, n_tbl=n_tbl, window=window,
        chunk=chunk, scale=scale, quantized=quantized,
    )
    def page_of(b_, ip, tbl, cl, ly):
        # the physical page: logical page ip of row b_ through the table
        return ly[0], tbl[b_, ip]

    in_specs = [
        pl.BlockSpec((1, hkv, g, d), lambda b_, *_: (b_, 0, 0, 0)),
        pl.BlockSpec((None, 1, page, hkv, d), lambda *a: (*page_of(*a), 0, 0, 0)),
        pl.BlockSpec((None, 1, page, hkv, dv), lambda *a: (*page_of(*a), 0, 0, 0)),
    ]
    if quantized:  # per-(page, head) scales ride the same table indirection
        in_specs += [
            pl.BlockSpec((1, 1, hkv), lambda *a: (page_of(*a)[1], 0, 0))
        ] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_tbl),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, hkv, g, dv), lambda b_, *_: (b_, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, dv), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        kernel,
        name="flashd_decode_paged",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dv), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )
    o = call(block_tbl, cache_len, layer, qg, k_pages, v_pages, *scales)
    return o.reshape(b, hq, dv)
