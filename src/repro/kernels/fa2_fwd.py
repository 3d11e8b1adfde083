"""Pallas TPU kernel: FlashAttention2 forward — the paper's comparison
baseline (Alg. 2 / Fig. 1), tiled for the MXU.

Identical grid / BlockSpec structure to `flashd_fwd.py` so the two kernels
differ ONLY in the datapath, mirroring the paper's controlled comparison:

  FA2 carry:      m (B_q) + ℓ (B_q) + acc (B_q·dv)   — two row-vectors
  FA2 per tile:   α = e^{m−m'} rescale of acc + ℓ, unnormalized accumulate
  FA2 epilogue:   acc / ℓ division pass at the last kv block

vs. FLASH-D's single Λ row-vector, no rescale chain through a running max,
and no division/epilogue. The op-count benchmark reads both kernels' HLO.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.core.blockwise import MaskSpec, NEG_INF, tile_live
from repro.kernels.flashd_fwd import _mask_bias, _tile_pos

__all__ = ["fa2_fwd_pallas"]


def _fa2_kernel(
    q_ref, k_ref, v_ref,
    o_ref, lam_ref,
    acc_ref, m_scratch, l_scratch,
    *,
    mask: MaskSpec,
    scale: float,
    block_q: int,
    block_k: int,
    kv_len: int,
    n_kv_blocks: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)

    q_pos, k_pos = _tile_pos(iq, ik, block_q, block_k)

    # shared static tile pruning (one predicate for fwd/bwd/fa2 kernels)
    compute = tile_live(mask, iq, ik, block_q, block_k, kv_len)

    @pl.when(compute)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        keep = _mask_bias(mask, q_pos, k_pos, kv_len)
        s = jnp.where(keep, s, NEG_INF)

        m_prev = m_scratch[...]  # [B_q, 1]
        m_b = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_b)  # serial cross-tile max chain
        m_safe = jnp.maximum(m_new, NEG_INF / 2)
        alpha = jnp.exp(m_prev - m_safe)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)
        p = jnp.exp(s - m_safe)
        l_new = l_scratch[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * alpha + pv  # rescale + accum
        m_scratch[...] = m_new
        l_scratch[...] = l_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = l_scratch[...]
        l_safe = jnp.maximum(l, jnp.finfo(jnp.float32).tiny)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)  # epilogue div
        lam_ref[0, 0] = jnp.where(l > 0, m_scratch[...] + jnp.log(l_safe), NEG_INF)


def fa2_fwd_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mask: MaskSpec = MaskSpec("causal"),
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
):
    """Returns (o [B, Hq, Sq, dv], Λ [B, Hq, Sq] f32). Same contract as
    `flashd_fwd_pallas` (GQA via index maps, padding handled here)."""
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

    kernel = functools.partial(
        _fa2_kernel,
        mask=mask,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        kv_len=skv,
        n_kv_blocks=n_k,
    )
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
        pl.BlockSpec((1, 1, block_k, d), lambda b_, h, iq, ik, g=group: (b_, h // g, ik, 0)),
        pl.BlockSpec((1, 1, block_k, dv), lambda b_, h, iq, ik, g=group: (b_, h // g, ik, 0)),
    ]
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
        name="fa2_fwd",
        grid=(b, hq, n_q, n_k),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
    )
    o, lam = call(q, k, v)
    return o[:, :, :sq], lam[:, :, :sq, 0]
