"""Quantized KV page-pool format (DESIGN.md §3.8).

Pages are stored in a low-precision dtype with a per-(page, head) f32
scale side-band held as extra pool leaves (`k_scale`/`v_scale`, shaped
`[n_pages, Hkv]`) beside `k_pages`/`v_pages`. The format is WRITE-ORDER
DETERMINISTIC: a page's scale is fixed by its slot-0 row — amax over the
head dim of the page's first K (resp. V) row, divided by qmax/HEADROOM —
and is never revised afterwards, so a page's quantized bytes + scale are
a pure function of the page's own (token, position) stream. That is
exactly the precondition the radix prefix cache needs to alias quantized
pages content-addressed by token prefix (DESIGN.md §3.6), and it holds
across both write paths (the sequential `_paged_attn_step` scatter and
the packed `_packed_attn` scatter) because slot 0 of a page is always
written at-or-before every other slot of that page.

HEADROOM leaves part of the representable range unused by the slot-0 row
so later rows of the page — drawn from the same activation distribution —
rarely clip; rows that still exceed the range saturate symmetrically.
FLASH-D's max-free stable exponentials make the attention arithmetic
tolerant of exactly this kind of bounded relative K/V error: scores enter
the (acc, Λ) sigmoid carry without a running-max subtraction, so a small
score perturbation moves the blend weight smoothly instead of re-basing
the whole normalizer (the H-FA / fused-exp-mul line of work in PAPERS.md
runs these same blockwise kernels on cheap reduced-precision formats).

int8 and fp8 (e4m3) differ only by (dtype, qmax), which is the point of
the spec registry: fp8 is a dtype swap, not a new plumbing path.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = [
    "QuantSpec",
    "HEADROOM",
    "available",
    "get_spec",
    "spec_for_dtype",
    "kv_itemsize",
    "slot0_scale",
    "quantize_rows",
    "dequantize_pages",
]

# the slot-0 row maps to ±(qmax / HEADROOM); later rows get 4× margin. At
# 2× a page of Gaussian rows clipped often enough to move an attention
# output by >0.5; at 4× saturation is rare and the slot-0 row keeps 32
# levels a side.
HEADROOM = 4.0
_EPS = 1e-8  # all-zero slot-0 rows still get a positive, finite scale


@dataclasses.dataclass(frozen=True)
class QuantSpec:
    """One storage format for the KV page pool."""

    name: str
    dtype: object  # jnp dtype of the stored pages
    qmax: float  # largest representable magnitude to clip against
    itemsize: int = 1  # bytes per stored element


_SPECS = {
    "int8": QuantSpec("int8", jnp.int8, 127.0),
    "fp8": QuantSpec("fp8", jnp.float8_e4m3fn, 448.0),
}


def available() -> tuple:
    return tuple(sorted(_SPECS))


def get_spec(kv_dtype: str) -> Optional[QuantSpec]:
    """Spec for a ServeConfig.kv_dtype string; "" (native) → None."""
    if not kv_dtype:
        return None
    try:
        return _SPECS[kv_dtype]
    except KeyError:
        raise ValueError(
            f"unknown kv_dtype {kv_dtype!r}; available: {available()} "
            "(\"\" stores pages in the model compute dtype)"
        ) from None


def spec_for_dtype(dtype) -> Optional[QuantSpec]:
    """Spec whose storage dtype is `dtype`, else None (native pool).

    The cache pytree carries only arrays, so consumers that find scale
    leaves beside a pool recover the format from the pages' dtype."""
    dt = jnp.dtype(dtype)
    for spec in _SPECS.values():
        if jnp.dtype(spec.dtype) == dt:
            return spec
    return None


def kv_itemsize(kv_dtype: str) -> int:
    """Stored bytes per K/V element (feeds the tuning heuristics)."""
    spec = get_spec(kv_dtype)
    return 4 if spec is None else spec.itemsize


def slot0_scale(row: jax.Array, spec: QuantSpec) -> jax.Array:
    """Per-head page scale from the page's slot-0 row.

    row [..., Hkv, d] → scale [..., Hkv] f32. Deterministic in the row
    alone — the whole soundness argument for radix sharing rests on this
    function never seeing any other slot of the page."""
    amax = jnp.max(jnp.abs(row.astype(jnp.float32)), axis=-1)
    return jnp.maximum(amax, _EPS) / (spec.qmax / HEADROOM)


def quantize_rows(rows: jax.Array, scales: jax.Array, spec: QuantSpec) -> jax.Array:
    """rows [..., Hkv, d] × scales [..., Hkv] → stored dtype (saturating)."""
    x = rows.astype(jnp.float32) / scales[..., None]
    x = jnp.clip(x, -spec.qmax, spec.qmax)
    if jnp.issubdtype(jnp.dtype(spec.dtype), jnp.integer):
        x = jnp.round(x)
    return x.astype(spec.dtype)


def dequantize_pages(pages: jax.Array, scales: jax.Array) -> jax.Array:
    """[P, page, Hkv, d] pages × [P, Hkv] scales → f32 pool view.

    The jnp mirror of the kernels' in-tile dequant (one broadcast multiply
    after the DMA'd tile is upcast) — mathematically identical because the
    scale is constant over a (page, head) tile."""
    return pages.astype(jnp.float32) * scales[:, None, :, None]
