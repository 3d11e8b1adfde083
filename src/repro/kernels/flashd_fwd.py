"""Pallas TPU kernel: blockwise FLASH-D forward (prefill / training fwd).

Grid (batch, q_head, q_block, kv_block) — kv_block is the innermost,
sequential ("arbitrary") dimension; the (O, Λ) recurrence is carried in VMEM
scratch across kv steps, exactly the canonical TPU flash-attention structure,
but with the FLASH-D carry: **one f32 scratch row-vector (Λ) instead of two
(m, ℓ), and no division / epilogue normalization pass anywhere**:

    W_b = σ(λ_b − Λ)          c_b = e^{m_b − Λ'}        Λ' = λ_b − ln W_b
    acc ← acc·(1−W_b) + (P_b V_b)·c_b

Tile-level skipping (paper §III-C generalized, DESIGN.md §2.1): when every
row of the tile satisfies m_b − Λ < −(θ + ln B_k) the exp, the P·V MXU
matmul and the blend are all predicated off with `pl.when` — the tile's
total weight is < σ(−θ) ≈ 2.5e-3 of the output. Partial-row skips fall back
to VPU selects, which are exact.

GQA is handled in the index maps: q head h reads kv head h // group_size.
Causal / local / chunked masks: tiles that are statically outside the mask
never compute (pl.when on block indices); boundary tiles apply an in-kernel
position mask.

VMEM budget per grid step (f32): q (B_q·d) + k,v (2·B_k·d) + acc (B_q·d)
+ Λ (B_q) + scores (B_q·B_k). Defaults B_q = B_k = 512, d = 128 →
~2.6 MB, comfortably inside the ~16 MB/core VMEM of TPU v5e.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.core.blockwise import MaskSpec, NEG_INF, DEFAULT_SKIP_THETA, tile_live

__all__ = ["flashd_fwd_pallas"]


def _tile_pos(iq, ik, block_q: int, block_k: int):
    """Absolute (q_pos [B_q, 1], k_pos [1, B_k]) of tile (iq, ik) — 2-D, as
    Mosaic wants every in-kernel vector."""
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    return q_pos, k_pos


def _mask_bias(mask: MaskSpec, q_pos, k_pos, kv_len: int):
    """In-kernel keep-mask for a (B_q, B_k) tile from q_pos [B_q, 1] and
    k_pos [1, B_k]."""
    keep = k_pos < kv_len  # mask padded keys
    if mask.kind != "full":
        qp = q_pos + mask.q_offset
        if mask.kind == "causal":
            keep = keep & (k_pos <= qp)
        elif mask.kind == "local":
            keep = keep & (k_pos <= qp) & (qp - k_pos < mask.window)
        elif mask.kind == "chunked":
            keep = keep & (k_pos <= qp) & (qp // mask.chunk == k_pos // mask.chunk)
        else:
            raise ValueError(mask.kind)
    return keep


def _flashd_kernel(
    q_ref, k_ref, v_ref,  # inputs
    o_ref, lam_ref,  # outputs
    acc_ref, lam_scratch,  # VMEM scratch
    *,
    mask: MaskSpec,
    scale: float,
    block_q: int,
    block_k: int,
    q_len: int,
    kv_len: int,
    n_kv_blocks: int,
    skip: bool,
    skip_theta: float,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        lam_scratch[...] = jnp.full_like(lam_scratch, NEG_INF)

    q_pos, k_pos = _tile_pos(iq, ik, block_q, block_k)

    # static tile pruning: tiles fully outside the mask never compute;
    # fully-padded q tiles (from pad_q) have no live rows: skip their whole
    # kv loop rather than running it into masked-out scores
    compute = jnp.logical_and(
        tile_live(mask, iq, ik, block_q, block_k, kv_len),
        iq * block_q < q_len,
    )

    @pl.when(compute)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # [B_q, d]
        k = k_ref[0, 0].astype(jnp.float32)  # [B_k, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [B_q, B_k] on the MXU
        keep = _mask_bias(mask, q_pos, k_pos, kv_len)
        s = jnp.where(keep, s, NEG_INF)

        m_b = jnp.max(s, axis=-1, keepdims=True)  # tile-LOCAL max; no chain
        lam_run = lam_scratch[...]  # [B_q, 1]
        skip_bound = -(skip_theta + float(np.log(block_k)))

        def _update():
            m_safe = jnp.maximum(m_b, NEG_INF / 2)
            p = jnp.exp(s - m_safe)
            l_b = jnp.sum(p, axis=-1, keepdims=True)
            lam_b = jnp.where(
                l_b > 0,
                m_safe + jnp.log(jnp.maximum(l_b, jnp.finfo(jnp.float32).tiny)),
                NEG_INF,
            )
            delta = lam_b - lam_run
            w = jax.nn.sigmoid(delta)  # division hidden here
            ln_w = jax.nn.log_sigmoid(delta)
            lam_new = lam_b - ln_w  # = logaddexp, division-free
            tile_dead = lam_b <= NEG_INF / 2
            first = lam_run <= NEG_INF / 2
            w = jnp.where(tile_dead, 0.0, jnp.where(first, 1.0, w))
            lam_new = jnp.where(tile_dead, lam_run, jnp.where(first, lam_b, lam_new))
            c = jnp.where(tile_dead, 0.0, jnp.exp(m_safe - lam_new))  # ≤ 1

            v = v_ref[0, 0].astype(jnp.float32)
            pv = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            if skip:  # per-row predication (exact at any θ by construction)
                row_skip = jnp.logical_and(m_b - lam_run < skip_bound, ~first)
                w = jnp.where(row_skip, 0.0, w)
                c = jnp.where(row_skip, 0.0, c)
                lam_new = jnp.where(row_skip, lam_run, lam_new)
            acc_ref[...] = acc_ref[...] * (1.0 - w) + pv * c
            lam_scratch[...] = lam_new

        if skip:
            # whole-tile skip: every row below threshold ⇒ no exp, no MXU
            # matmul, no blend. This is the FLOP-level win on TPU.
            n_live = jnp.sum(jnp.logical_or(
                m_b - lam_run >= skip_bound, lam_run <= NEG_INF / 2
            ).astype(jnp.float32))
            pl.when(n_live > 0)(_update)
        else:
            _update()

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        # No division, no rescale: acc already holds softmax(S)·V exactly.
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)
        lam_ref[0, 0] = lam_scratch[...]


def flashd_fwd_pallas(
    q: jax.Array,  # [B, Hq, Sq, d]
    k: jax.Array,  # [B, Hkv, Skv, d]
    v: jax.Array,  # [B, Hkv, Skv, dv]
    *,
    mask: MaskSpec = MaskSpec("causal"),
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    skip: bool = False,
    skip_theta: float = DEFAULT_SKIP_THETA,
    interpret: bool = False,
):
    """Returns (o [B, Hq, Sq, dv] in q.dtype, Λ [B, Hq, Sq] f32).

    block_q / block_k = None picks the tiling from the VMEM-budget
    heuristics in repro.kernels.tuning."""
    b, hq, sq, d = q.shape
    _, hkv, skv, dv = v.shape
    if scale is None:
        scale = float(1.0 / (d ** 0.5))
    group = hq // hkv

    if block_q is None or block_k is None:
        from repro.kernels.tuning import choose_prefill_blocks  # lazy: no cycle

        tiling = choose_prefill_blocks(sq, skv, d, dv)
        block_q = tiling.block_q if block_q is None else block_q
        block_k = tiling.block_k if block_k is None else block_k
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    n_q = (sq + pad_q) // block_q
    n_k = (skv + pad_k) // block_k

    grid = (b, hq, n_q, n_k)
    kernel = functools.partial(
        _flashd_kernel,
        mask=mask,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        q_len=sq,
        kv_len=skv,
        n_kv_blocks=n_k,
        skip=skip,
        skip_theta=skip_theta,
    )

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
        pl.BlockSpec((1, 1, block_k, d), lambda b_, h, iq, ik, g=group: (b_, h // g, ik, 0)),
        pl.BlockSpec((1, 1, block_k, dv), lambda b_, h, iq, ik, g=group: (b_, h // g, ik, 0)),
    ]
    # Λ leaves as a [.., Sq, 1] column: one f32 per row, legal block dims
    out_specs = [
        pl.BlockSpec((1, 1, block_q, dv), lambda b_, h, iq, ik: (b_, h, iq, 0)),
        pl.BlockSpec((1, 1, block_q, 1), lambda b_, h, iq, ik: (b_, h, iq, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, hq, sq + pad_q, dv), q.dtype),
        jax.ShapeDtypeStruct((b, hq, sq + pad_q, 1), jnp.float32),
    ]
    call = pl.pallas_call(
        kernel,
        name="flashd_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
    )
    o, lam = call(q, k, v)
    return o[:, :, :sq], lam[:, :, :sq, 0]
