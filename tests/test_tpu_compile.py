"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Every other kernel test runs in Pallas interpret mode, which cannot see
Mosaic's block-tiling rules or its VMEM limit. These tests lower and
compile each kernel at qwen2-1.5b widths (Hq 12, Hkv 2, d 128, bf16, page
64) for one chip of a `v5e:2x2` topology: a refusal here is a refusal on
the chip. The paged kernels, whose K/V blocks hold whole pages, compile at
paper-llama widths too (MHA 6/6, d 48: a head narrower than a lane tile).
Nothing runs, so nothing here is a time or a result.

The model-step tests compile whole programs of the serving loop at the
benchmark's pool size and count the copies of a layer's page pool they
make: the paged kernels read the stacked pool through a layer index, so
there must be none (DESIGN.md §3.4).

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.blockwise import MaskSpec
from repro.kernels.fa2_fwd import fa2_fwd_pallas
from repro.kernels.flashd_bwd import flashd_bwd_pallas
from repro.kernels.flashd_decode import (
    flashd_decode_paged_pallas,
    flashd_decode_pallas,
)
from repro.kernels.flashd_fwd import flashd_fwd_pallas
from repro.kernels.flashd_varlen import flashd_varlen_pallas

HBM_BYTES = 16 * 10**9  # one v5e chip
QWEN2 = (12, 2, 128)  # qwen2-1.5b attention widths (Hq, Hkv, d)
PAPER_LLAMA = (6, 6, 48)  # launch/serve's default arch
HQ, HKV, D = QWEN2
PAGE = 64
B, N_TBL, N_PAGES, T, S = 8, 32, 512, 512, 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without the chip: keep it off
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _varlen(quantized, widths=QWEN2):
    kv = jnp.int8 if quantized else jnp.bfloat16
    HQ, HKV, D = widths

    def build():
        args = [
            ((T, HQ, D), jnp.bfloat16), ((N_PAGES, PAGE, HKV, D), kv),
            ((N_PAGES, PAGE, HKV, D), kv), ((B, N_TBL), jnp.int32),
            ((T,), jnp.int32), ((T,), jnp.int32), ((B,), jnp.int32),
        ]
        if quantized:
            args += [((N_PAGES, HKV), jnp.float32)] * 2

        def fn(q, k, v, tbl, seq, pos, kv_len, *scales):
            ks, vs = scales or (None, None)
            return flashd_varlen_pallas(
                q, k, v, tbl, seq, pos, kv_len, block_q=8,
                k_scale=ks, v_scale=vs,
            )

        return fn, args

    return build


def _decode_paged(quantized, widths=QWEN2):
    kv = jnp.int8 if quantized else jnp.bfloat16
    HQ, HKV, D = widths

    def build():
        args = [
            ((B, HQ, D), jnp.bfloat16), ((N_PAGES, PAGE, HKV, D), kv),
            ((N_PAGES, PAGE, HKV, D), kv), ((B, N_TBL), jnp.int32),
            ((B,), jnp.int32),
        ]
        if quantized:
            args += [((N_PAGES, HKV), jnp.float32)] * 2

        def fn(q, k, v, tbl, cache_len, *scales):
            ks, vs = scales or (None, None)
            return flashd_decode_paged_pallas(
                q, k, v, tbl, cache_len, k_scale=ks, v_scale=vs
            )

        return fn, args

    return build


def _decode_fused():
    args = [
        ((B, HQ, D), jnp.bfloat16), ((B, HKV, S, D), jnp.bfloat16),
        ((B, HKV, S, D), jnp.bfloat16), ((B,), jnp.int32), ((B,), jnp.int32),
    ]

    def fn(q, k, v, cache_len, start):
        return flashd_decode_pallas(
            q, k, v, cache_len, start=start, return_lam=True
        )

    return fn, args


def _prefill(kernel):
    def build():
        args = [
            ((1, HQ, S, D), jnp.bfloat16), ((1, HKV, S, D), jnp.bfloat16),
            ((1, HKV, S, D), jnp.bfloat16),
        ]
        return (lambda q, k, v: kernel(q, k, v, mask=MaskSpec("causal"))), args

    return build


def _bwd():
    args = [
        ((1, HQ, S, D), jnp.bfloat16), ((1, HKV, S, D), jnp.bfloat16),
        ((1, HKV, S, D), jnp.bfloat16), ((1, HQ, S, D), jnp.bfloat16),
        ((1, HQ, S), jnp.float32), ((1, HQ, S, D), jnp.bfloat16),
    ]

    def fn(q, k, v, o, lam, do):
        return flashd_bwd_pallas(q, k, v, o, lam, do, mask=MaskSpec("causal"))

    return fn, args


KERNELS = {
    "flashd_varlen_bf16": _varlen(False),
    "flashd_varlen_int8": _varlen(True),
    "flashd_decode_paged_bf16": _decode_paged(False),
    "flashd_decode_paged_int8": _decode_paged(True),
    "flashd_varlen_bf16_d48": _varlen(False, PAPER_LLAMA),
    "flashd_varlen_int8_d48": _varlen(True, PAPER_LLAMA),
    "flashd_decode_paged_bf16_d48": _decode_paged(False, PAPER_LLAMA),
    "flashd_decode_paged_int8_d48": _decode_paged(True, PAPER_LLAMA),
    "flashd_decode_fused": _decode_fused,
    "flashd_fwd": _prefill(flashd_fwd_pallas),
    "flashd_fwd_skip": _prefill(
        lambda *a, **kw: flashd_fwd_pallas(*a, skip=True, **kw)
    ),
    "fa2_fwd": _prefill(fa2_fwd_pallas),
    "flashd_bwd": _bwd,
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = KERNELS[name]()
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES


# ---------------------------------------------------------------------------
# model step: the stacked page pool is never copied per layer
# ---------------------------------------------------------------------------

POOL_PAGES = 2049  # the benchmark's 131,072-token pool in pages of 64, + garbage
STEP_LAYERS, STEP_SLOTS, STEP_PACK = 4, 64, 512


def _step_program(program, kv_dtype):
    """A serving program at qwen2-1.5b attention widths over a stack of 4
    layers and a pool of 2,049 pages of 64: the decode chunk (the
    engine's scan of `decode_step_lm`) or one 512-row `forward_packed`."""
    from repro.configs.qwen2_1_5b import CONFIG
    from repro.models import transformer as tf

    cfg = dataclasses.replace(
        CONFIG, n_layers=STEP_LAYERS, vocab_size=1024, param_dtype="bfloat16",
        attn_impl="flashd_pallas",
    )
    params = jax.eval_shape(lambda: tf.init_lm(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: tf.init_decode_cache(
        STEP_SLOTS, 8192, cfg, layout="paged", page_size=PAGE,
        n_pages=POOL_PAGES, kv_dtype=kv_dtype,
    ))
    i32 = lambda n: jax.ShapeDtypeStruct((n,), jnp.int32)
    if program == "decode_chunk":
        def fn(p, c, tok, pos):
            def body(carry, _):
                c, tok, pos = carry
                logits, c = tf.decode_step_lm(p, c, tok, pos, cfg)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (c, nxt, pos + 1), nxt
            return jax.lax.scan(body, (c, tok, pos), None, length=8)

        return fn, (params, cache, i32(STEP_SLOTS), i32(STEP_SLOTS))

    def fn(p, c, tokens, seq_ids, positions, kv_len, rows):
        return tf.forward_packed(
            p, tokens, seq_ids, positions, kv_len, c, cfg, rows, block_q=8
        )

    return fn, (params, cache, *[i32(STEP_PACK)] * 3, i32(STEP_SLOTS),
                i32(STEP_SLOTS))


@pytest.mark.parametrize("kv_dtype", ["", "int8"])
@pytest.mark.parametrize("program", ["decode_chunk", "forward_packed"])
def test_step_never_copies_a_layer_pool(one_chip, monkeypatch, program,
                                        kv_dtype):
    """No dynamic-slice or dynamic-update-slice of the compiled program
    has a layer pool's shape [.., 2049, 64, 2, 128]: the layer loop carries
    the stacked pool whole, scatters the step's rows into it in place, and
    the kernels pick their layer by scalar prefetch. In a bf16 pool the
    program's temp stays under one layer's K pool (67 MB; a layer
    copied out and back needed 87 MB). An int8 pool's default TPU layout
    is not the kernels' row-major one, so XLA relayouts the whole stack
    at the program's entry and exit — once per program, not per layer —
    and its temp holds those copies."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    fn, args = _step_program(program, kv_dtype)
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args,
    )
    compiled = jax.jit(fn).lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    pool = f"{POOL_PAGES},{PAGE},{HKV},{D}"
    layer_copies = re.findall(
        rf"= \w+\[(?:\d+,)*{pool}\]\S* dynamic-(?:update-)?slice\(", hlo
    )
    assert layer_copies == []
    if not kv_dtype:
        layer_k_pool = POOL_PAGES * PAGE * HKV * D * 2
        assert compiled.memory_analysis().temp_size_in_bytes < layer_k_pool
