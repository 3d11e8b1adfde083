"""Every Pallas kernel carries its own name to the chip.

Each `pallas_call` passes `name=`; lowered for a TPU, its
`tpu_custom_call` carries that name as `kernel_name`, and the compiled
HLO instruction (the name a chip trace shows for the kernel's events) is
named after it, so a profile reads `flashd_varlen (custom-call)` and not
`closed_call`. Lowering needs no chip: the toy shapes below lower on the
CPU with `lowering_platforms=("tpu",)`.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.blockwise import MaskSpec
from repro.kernels.fa2_fwd import fa2_fwd_pallas
from repro.kernels.flashd_bwd import flashd_bwd_pallas
from repro.kernels.flashd_decode import (
    flashd_decode_paged_pallas,
    flashd_decode_pallas,
)
from repro.kernels.flashd_fwd import flashd_fwd_pallas
from repro.kernels.flashd_varlen import flashd_varlen_pallas

HQ, HKV, D, PAGE, B, S, T = 4, 2, 128, 16, 2, 64, 16
BF = jnp.bfloat16
CAUSAL = MaskSpec("causal")


def _paged(name):
    args = [((T if name == "varlen" else B, HQ, D), BF),
            ((8, PAGE, HKV, D), BF), ((8, PAGE, HKV, D), BF),
            ((B, 4), jnp.int32)]
    if name == "varlen":
        args += [((T,), jnp.int32), ((T,), jnp.int32), ((B,), jnp.int32)]
        return (lambda q, k, v, tbl, seq, pos, kv_len: flashd_varlen_pallas(
            q, k, v, tbl, seq, pos, kv_len, block_q=8)), args
    args += [((B,), jnp.int32)]
    return flashd_decode_paged_pallas, args


def _decode(fused):
    args = [((B, HQ, D), BF), ((B, HKV, S, D), BF), ((B, HKV, S, D), BF),
            ((B,), jnp.int32)]
    return (lambda q, k, v, n: flashd_decode_pallas(
        q, k, v, n, fused=fused, n_splits=2)), args


def _prefill(kernel):
    args = [((1, HQ, S, D), BF), ((1, HKV, S, D), BF), ((1, HKV, S, D), BF)]
    return (lambda q, k, v: kernel(q, k, v, mask=CAUSAL)), args


def _bwd():
    args = [((1, HQ, S, D), BF), ((1, HKV, S, D), BF), ((1, HKV, S, D), BF),
            ((1, HQ, S, D), BF), ((1, HQ, S), jnp.float32), ((1, HQ, S, D), BF)]
    return (lambda q, k, v, o, lam, do: flashd_bwd_pallas(
        q, k, v, o, lam, do, mask=CAUSAL)), args


# kernel name -> (function, argument shapes) whose lowering holds it
KERNELS = {
    "flashd_varlen": lambda: _paged("varlen"),
    "flashd_decode_paged": lambda: _paged("decode"),
    "flashd_decode": lambda: _decode(False),
    "flashd_decode_fused": lambda: _decode(True),
    "flashd_fwd": lambda: _prefill(flashd_fwd_pallas),
    "flashd_bwd_dq": _bwd,
    "flashd_bwd_dkv": _bwd,
    "fa2_fwd": lambda: _prefill(fa2_fwd_pallas),
}
_CALL = re.compile(r"stablehlo\.custom_call @tpu_custom_call\(.*kernel_name = \"(\w+)\"")


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_name_reaches_its_tpu_custom_call(name):
    fn, args = KERNELS[name]()
    shapes = [jax.ShapeDtypeStruct(s, dt) for s, dt in args]
    text = jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
    calls = [m.group(1) for m in map(_CALL.search, text.splitlines()) if m]
    assert name in calls
    assert set(calls) <= set(KERNELS)
