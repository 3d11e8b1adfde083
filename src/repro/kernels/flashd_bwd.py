"""Pallas TPU kernels: FLASH-D backward (dQ, dK, dV) from saved (O, Λ).

Probabilities are reconstructed as P = exp(s − Λ); with FLASH-D's Λ the
exponent is always ≤ 0, so the backward — like the forward — needs no
max-subtraction pass and cannot overflow (DESIGN.md §2.1). Two kernels,
the canonical TPU split:

  dq kernel : grid (B, H_q, q_block, kv_block), kv innermost; carries
              dQ_acc in VMEM, writes at the last kv step.
  dkv kernel: grid (B, H_kv, kv_block, g·q_block), the q-head group is
              folded into the innermost loop so GQA's dK/dV accumulate over
              their query group without revisiting output blocks.

D = rowsum(dO ∘ O) is precomputed by the wrapper (one fused jnp reduction).
Masks reuse the forward's in-kernel position logic; statically-dead tiles
are predicated off with `pl.when`.
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

__all__ = ["flashd_bwd_pallas"]


def _recompute_p_ds(q, k, v, do, lam, dsum, q_pos, k_pos, mask, scale, kv_len):
    """P and dS of one (B_q, B_k) tile; lam, dsum and q_pos are [B_q, 1]."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    keep = _mask_bias(mask, q_pos, k_pos, kv_len)
    s = jnp.where(keep, s, NEG_INF)
    p = jnp.exp(s - lam)  # exponent ≤ 0 — overflow-free
    p = jnp.where(lam <= NEG_INF / 2, 0.0, p)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - dsum) * scale
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lam_ref, dsum_ref, dq_ref, acc_ref,
               *, mask, scale, block_q, block_k, kv_len, n_kv):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos, k_pos = _tile_pos(iq, ik, block_q, block_k)

    @pl.when(tile_live(mask, iq, ik, block_q, block_k, kv_len))
    def _body():
        _, ds = _recompute_p_ds(
            q_ref[0, 0].astype(jnp.float32), k_ref[0, 0].astype(jnp.float32),
            v_ref[0, 0].astype(jnp.float32), do_ref[0, 0].astype(jnp.float32),
            lam_ref[0, 0], dsum_ref[0, 0], q_pos, k_pos, mask, scale, kv_len,
        )
        acc_ref[...] += jax.lax.dot_general(
            ds, k_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == n_kv - 1)
    def _fin():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lam_ref, dsum_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, mask, scale, block_q, block_k, kv_len, n_q, group):
    ik, inner = pl.program_id(2), pl.program_id(3)
    iq = inner % n_q

    @pl.when(inner == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_pos, k_pos = _tile_pos(iq, ik, block_q, block_k)

    @pl.when(tile_live(mask, iq, ik, block_q, block_k, kv_len))
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        p, ds = _recompute_p_ds(
            q, k_ref[0, 0].astype(jnp.float32), v_ref[0, 0].astype(jnp.float32),
            do, lam_ref[0, 0], dsum_ref[0, 0], q_pos, k_pos, mask, scale, kv_len,
        )
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(inner == n_q * group - 1)
    def _fin():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flashd_bwd_pallas(
    q: jax.Array,  # [B, Hq, Sq, d]
    k: jax.Array,  # [B, Hkv, Skv, d]
    v: jax.Array,  # [B, Hkv, Skv, dv]
    o: jax.Array,  # [B, Hq, Sq, dv]
    lam: jax.Array,  # [B, Hq, Sq] f32
    do: jax.Array,  # [B, Hq, Sq, dv]
    *,
    mask: MaskSpec = MaskSpec("causal"),
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
):
    b, hq, sq, d = q.shape
    _, hkv, skv, dv = v.shape
    g = hq // hkv
    if scale is None:
        scale = float(1.0 / (d ** 0.5))
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    if pad_q:
        widths = ((0, 0), (0, 0), (0, pad_q), (0, 0))
        q, o, do = (jnp.pad(x, widths) for x in (q, o, do))
        lam = jnp.pad(lam, ((0, 0), (0, 0), (0, pad_q)), constant_values=NEG_INF)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    n_q = (sq + pad_q) // block_q
    n_k = (skv + pad_k) // block_k

    # per-row statistics enter as [.., Sq', 1] columns: legal block dims
    dsum = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )
    lam = lam[..., None]
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
    )

    # ---- dQ ----
    dq_kernel = functools.partial(
        _dq_kernel, mask=mask, scale=scale, block_q=block_q, block_k=block_k,
        kv_len=skv, n_kv=n_k,
    )
    dq = pl.pallas_call(
        dq_kernel,
        name="flashd_bwd_dq",
        grid=(b, hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h, iq, ik, g=g: (b_, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda b_, h, iq, ik, g=g: (b_, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_q, dv), lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h, iq, ik: (b_, h, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq + pad_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        compiler_params=params,
    )(q, k, v, do, lam, dsum)

    # ---- dK, dV (q-group folded into the inner loop) ----
    dkv_kernel = functools.partial(
        _dkv_kernel, mask=mask, scale=scale, block_q=block_q, block_k=block_k,
        kv_len=skv, n_q=n_q, group=g,
    )

    def qhead(b_, h, ik, inner, g=g, n_q=n_q):
        return (b_, h * g + inner // n_q, inner % n_q, 0)

    dk, dv_out = pl.pallas_call(
        dkv_kernel,
        name="flashd_bwd_dkv",
        grid=(b, hkv, n_k, n_q * g),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), qhead),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h, ik, inner: (b_, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda b_, h, ik, inner: (b_, h, ik, 0)),
            pl.BlockSpec((1, 1, block_q, dv), qhead),
            pl.BlockSpec((1, 1, block_q, 1), qhead),
            pl.BlockSpec((1, 1, block_q, 1), qhead),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h, ik, inner: (b_, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda b_, h, ik, inner: (b_, h, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, skv + pad_k, d), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, skv + pad_k, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=params,
    )(q, k, v, do, lam, dsum)

    return dq[:, :, :sq], dk[:, :, :skv], dv_out[:, :, :skv]
