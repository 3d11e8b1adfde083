"""Pallas TPU kernel: packed varlen FLASH-D over a paged KV cache.

ONE kernel for the whole serving hot path (DESIGN.md §3.5). Queries from
many sequences arrive as a flat packed batch [T, Hq, d] — prefill chunks,
whole prompts and single decode tokens side by side — and K/V live in the
global page pool of the paged cache (runtime/kvcache.py). FlashAttention's
tiling and the FLASH-D sigmoid carry are both indifferent to *whose* rows a
tile holds, so prefill-vs-decode disappears from the dispatch layer:

  * a prefill chunk is a segment of q_len rows attending [0, kv_len);
  * a decode token is the degenerate q_len == 1 segment of the same grid —
    no separate decode kernel on this path.

Packing contract (the scheduler's packer enforces it):

  * each sequence's rows occupy one contiguous *segment*, and segments are
    aligned to `block_q` rows, so every q tile belongs to exactly ONE
    sequence (flash-attn varlen's per-sequence blocking, expressed in the
    packed layout instead of the launch grid);
  * `seq_ids[t]` is the owning sequence (batch row of `block_tbl`/`kv_len`)
    or −1 for alignment padding; `q_pos[t]` is the row's ABSOLUTE position
    in its sequence's KV space, −1 for padding. Padding rows mask every key
    (q_pos −1 defeats the causal test) and come back as zero rows.

Grid (q_block, logical_page) — the page axis innermost and sequential;
each step's K/V block is one whole physical page, all kv heads, and the
body walks the heads in a static loop. Per-block metadata (`blk_seq` =
seq_ids[::block_q], which is exact under the alignment contract, and the
block's largest and smallest q_pos for tile pruning) plus `kv_len` and
the block table are
scalar-prefetch operands: the K/V BlockSpec index maps resolve
`tbl[blk_seq[ib], ip]` before each step's DMA is issued, so the page
gather lives in the DMA descriptors exactly like the paged decode kernel.
The body is the flashd_fwd tile body: tile-local (m, λ), normalized
partial, and the in-VMEM (acc, Λ) sigmoid carry — merged with
`_merge_into_carry`, unchanged. Masks are per-element (sequence boundary ×
causal × window/chunk), so tile pruning is purely a FLOP optimization.

Kernel layout: q is viewed head-major, [Hkv, G, T, d], so one grid step's
q block is the (Hkv, G, block_q, d) slab and each kv head's G·block_q
rows collapse into one score matmul without a relayout; q_pos enters as
a [T, 1] column, one position per row.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.core.blockwise import NEG_INF
from repro.kernels.flashd_decode import (
    _merge_into_carry,
    _normalized_partial,
    stacked_pool,
)

__all__ = ["flashd_varlen_pallas"]


def _varlen_partial(q, k, q_pos, kv_len, lo, *, page, window, chunk, scale, v):
    """Normalized partial (o_p [R, dv], λ_p [R, 1]) of R packed query rows
    against one gathered page. Per-row masks: key visible iff it is inside
    the row's sequence (< kv_len), causally visible (≤ q_pos), and inside
    the window/chunk structure. Rows with q_pos < 0 (padding) see nothing
    and come back dead (λ = NEG_INF ⇒ identity under the sigmoid merge).
    q_pos is the [R, 1] column of row positions."""
    pos = lo + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [R, page]
    keep = jnp.logical_and(pos < kv_len, pos <= q_pos)
    if window > 0:
        keep = jnp.logical_and(keep, q_pos - pos < window)
    if chunk > 0:
        keep = jnp.logical_and(keep, q_pos // chunk == pos // chunk)
    return _normalized_partial(jnp.where(keep, s, NEG_INF), v)


def _varlen_kernel(
    blk_seq_ref, blk_qmax_ref, blk_qmin_ref, kv_len_ref, tbl_ref,  # SMEM
    layer_ref,  # SMEM, read only by the K/V index maps
    q_ref, qpos_ref, k_ref, v_ref,  # VMEM (k/v: the gathered physical page)
    *refs,  # quantized: (ks, vs) scale blocks; then o, then VMEM carry
    block_q: int,
    group: int,
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
    ip = pl.program_id(1)  # logical page — innermost, sequential
    seq_raw = blk_seq_ref[ib]
    seq = jnp.maximum(seq_raw, 0)
    kv_len = jnp.where(seq_raw >= 0, kv_len_ref[seq], 0)
    lo = ip * page

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        lam_scratch[...] = jnp.full_like(lam_scratch, NEG_INF)

    # conservative tile pruning: per-element masks above are exact, this
    # only skips pages no row of the block can see (future pages under the
    # causal test, pages past the sequence end). Padding rows carry
    # q_pos = −1, which can only shrink the block's max — never un-prune a
    # live page.
    live = jnp.logical_and(
        seq_raw >= 0, jnp.logical_and(lo < kv_len, lo <= blk_qmax_ref[ib])
    )
    if window > 0:
        live = jnp.logical_and(live, lo + page > blk_qmin_ref[ib] - window + 1)

    @pl.when(live)
    def _body():
        d = q_ref.shape[-1]
        q_pos = jnp.concatenate([qpos_ref[...]] * group, axis=0)  # [G·bq, 1]
        for h in range(k_ref.shape[2]):  # static: Mosaic slices heads statically
            q = q_ref[h].astype(jnp.float32).reshape(group * block_q, d)
            k = k_ref[0, :, h, :].astype(jnp.float32)  # [page, d]
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if quantized:  # dequant in-tile: one per-(page, head) f32 scale each
                k = k * ks_ref[0, :, h:h + 1]
                v = v * vs_ref[0, :, h:h + 1]
            o_p, lam_p = _varlen_partial(
                q, k, q_pos, kv_len, lo, page=page, window=window,
                chunk=chunk, scale=scale, v=v,
            )
            _merge_into_carry(o_p, lam_p, acc_ref.at[h], lam_scratch.at[h])

    @pl.when(ip == n_tbl - 1)
    def _finalize():
        hkv, _, dv = acc_ref.shape
        o_ref[...] = acc_ref[...].reshape(hkv, group, block_q, dv).astype(
            o_ref.dtype
        )


def flashd_varlen_pallas(
    q: jax.Array,  # [T, Hq, d] — packed, block_q-aligned segments
    k_pages: jax.Array,  # [P, page, Hkv, d] — global page pool ([L, P, ...]: layer)
    v_pages: jax.Array,  # [P, page, Hkv, dv]
    block_tbl: jax.Array,  # [B, N] i32
    seq_ids: jax.Array,  # [T] i32 (−1 = padding row)
    q_pos: jax.Array,  # [T] i32 absolute position in KV space (−1 = padding)
    kv_len: jax.Array,  # [B] i32 per-sequence visible KV length
    *,
    scale: Optional[float] = None,
    window: int = 0,
    chunk: int = 0,
    block_q: int,
    k_scale: Optional[jax.Array] = None,  # [P, Hkv] f32 — quantized pool
    v_scale: Optional[jax.Array] = None,  # [P, Hkv] f32
    layer: Optional[jax.Array] = None,  # i32: pool stacked [L, P, ...], read layer
    interpret: bool = False,
) -> jax.Array:
    """Packed varlen FLASH-D forward over a paged cache → o [T, Hq, dv].

    T must be a multiple of `block_q` and each block must belong to one
    sequence (the packing contract above) — callers go through
    `repro.core.attention.varlen_attention`, which pads and documents it.

    With `k_scale`/`v_scale` the page pool is quantized (runtime/quant.py,
    DESIGN.md §3.8): each per-(page, head) f32 scale rides the same
    block-table indirection as its page and the tile is dequantized right
    after its upcast, before the scores — the merge is untouched.

    With `layer` the pool (pages and scales) carries a leading layer axis
    and the index maps read layer `layer` of it (`stacked_pool`).
    """
    layer, k_pages, v_pages, scales = stacked_pool(
        k_pages, v_pages, k_scale, v_scale, layer
    )
    t, hq, d = q.shape
    _, _, page, hkv, dv = v_pages.shape
    n_tbl = block_tbl.shape[1]
    g = hq // hkv
    if scale is None:
        scale = float(1.0 / (d ** 0.5))
    if t % block_q:
        raise ValueError(f"packed length {t} not a multiple of block_q={block_q}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    quantized = k_scale is not None
    nb = t // block_q

    seq_ids = jnp.asarray(seq_ids, jnp.int32)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(-1)
    blk_seq = seq_ids[::block_q]  # exact under the alignment contract
    blk_pos = q_pos.reshape(nb, block_q)

    qg = q.reshape(t, hkv, g, d).transpose(1, 2, 0, 3)  # [Hkv, G, T, d]

    kernel = functools.partial(
        _varlen_kernel, block_q=block_q, group=g, page=page, n_tbl=n_tbl,
        window=window, chunk=chunk, scale=scale, quantized=quantized,
    )

    def page_of(ib, ip, bs, qmax, qmin, kl, tbl, ly):
        # the physical page: logical page ip of the block's sequence,
        # resolved through the table in the DMA descriptor
        return ly[0], tbl[jnp.maximum(bs[ib], 0), ip]

    in_specs = [
        pl.BlockSpec((hkv, g, block_q, d), lambda ib, *_: (0, 0, ib, 0)),
        pl.BlockSpec((block_q, 1), lambda ib, *_: (ib, 0)),
        pl.BlockSpec((None, 1, page, hkv, d), lambda *a: (*page_of(*a), 0, 0, 0)),
        pl.BlockSpec((None, 1, page, hkv, dv), lambda *a: (*page_of(*a), 0, 0, 0)),
    ]
    if quantized:  # per-(page, head) scales ride the same table indirection
        in_specs += [
            pl.BlockSpec((1, 1, hkv), lambda *a: (page_of(*a)[1], 0, 0))
        ] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(nb, n_tbl),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (hkv, g, block_q, dv), lambda ib, *_: (0, 0, ib, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((hkv, g * block_q, dv), jnp.float32),
            pltpu.VMEM((hkv, g * block_q, 1), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        kernel,
        name="flashd_varlen",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hkv, g, t, dv), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )
    o = call(
        blk_seq, blk_pos.max(axis=1), blk_pos.min(axis=1), kv_len,
        jnp.asarray(block_tbl, jnp.int32), layer,
        qg, q_pos.reshape(t, 1), k_pages, v_pages, *scales,
    )
    return o.transpose(2, 0, 1, 3).reshape(t, hq, dv)
