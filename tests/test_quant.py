"""Quantized paged KV pool (DESIGN.md §3.8): format unit tests, the
quantized-vs-f32 differential error bounds over GQA/masks/raggedness for
both kernel paths, write-path determinism (sequential vs packed vs radix
warm hits), logprob drift on the serving decode loop, allocator scale-leaf
invariants, and terminal-cleanliness under chaos with kv_dtype=int8.

The load-bearing soundness claims pinned here:

  * a page's quantized bytes + scale are a pure function of its own token
    stream (slot-0 scale, never revised) — so the sequential step, the
    packed varlen step, and a radix-cache warm hit all produce identical
    pool state, and prefix-shared pages can alias one scale entry;
  * the jnp mirrors dequantize with arithmetic identical to the kernels'
    in-tile dequant, so they remain the differential oracle;
  * FLASH-D's stable exponentials keep the int8 K/V error a small, bounded
    output perturbation (no normalizer re-basing to amplify it).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings
from hypothesis import strategies as st

from repro.configs import paper_llama
from repro.core.attention import (
    decode_attention_paged,
    gather_pages,
    varlen_attention,
)
from repro.runtime import quant
from repro.serve import DONE, TERMINAL, Engine, FaultInjector, ServeConfig

# ---------------------------------------------------------------------------
# format unit tests
# ---------------------------------------------------------------------------


def test_spec_registry():
    spec = quant.get_spec("int8")
    assert spec.name == "int8" and spec.qmax == 127.0 and spec.itemsize == 1
    assert quant.get_spec("") is None  # "" = native pool
    with pytest.raises(ValueError, match="unknown kv_dtype"):
        quant.get_spec("int4")
    assert quant.kv_itemsize("") == 4
    assert quant.kv_itemsize("int8") == 1
    assert "int8" in quant.available()
    assert quant.spec_for_dtype(jnp.int8) is spec
    assert quant.spec_for_dtype(jnp.float32) is None


def test_slot0_scale_deterministic_and_positive():
    rng = np.random.default_rng(0)
    spec = quant.get_spec("int8")
    row = jnp.asarray(rng.standard_normal((3, 2, 16)), jnp.float32)
    s1, s2 = quant.slot0_scale(row, spec), quant.slot0_scale(row, spec)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert np.all(np.asarray(s1) > 0)
    # all-zero rows still get a positive, finite scale (the _EPS floor)
    z = quant.slot0_scale(jnp.zeros((2, 16)), spec)
    assert np.all(np.isfinite(np.asarray(z))) and np.all(np.asarray(z) > 0)


def test_roundtrip_error_bound():
    """Values inside the slot-0 row's headroom round-trip within half a
    quantization step; values beyond saturate symmetrically."""
    rng = np.random.default_rng(1)
    spec = quant.get_spec("int8")
    rows = jnp.asarray(rng.standard_normal((4, 8, 2, 16)), jnp.float32)
    scales = quant.slot0_scale(rows[:, 0], spec)  # [P, Hkv]
    q = quant.quantize_rows(rows, scales[:, None, :], spec)
    assert q.dtype == jnp.int8
    deq = quant.dequantize_pages(q, scales)
    step = np.asarray(scales)[:, None, :, None]
    bound = np.abs(np.asarray(rows))  # |x| clips to qmax·scale ≤ |x|
    err = np.abs(np.asarray(deq) - np.asarray(rows))
    assert np.all(err <= np.maximum(step / 2 + 1e-6, bound - 127.0 * step))


def _quantized_pool(rng, P, page, hkv, d, dv, spec):
    kf = jnp.asarray(rng.standard_normal((P, page, hkv, d)), jnp.float32)
    vf = jnp.asarray(rng.standard_normal((P, page, hkv, dv)), jnp.float32)
    ks = quant.slot0_scale(kf[:, 0], spec)
    vs = quant.slot0_scale(vf[:, 0], spec)
    kq = quant.quantize_rows(kf, ks[:, None, :], spec)
    vq = quant.quantize_rows(vf, vs[:, None, :], spec)
    return kf, vf, kq, vq, ks, vs


# ---------------------------------------------------------------------------
# differential suites: quantized vs f32 oracle, both kernel paths
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(
    group=st.sampled_from([1, 2, 4]),
    window=st.sampled_from([0, 0, 6]),
    chunk=st.sampled_from([0, 0, 8]),
    seed=st.integers(min_value=0, max_value=10**6),
)
def test_paged_decode_quantized_differential(group, window, chunk, seed):
    """Paged decode, quantized pool: kernel ≈ jnp mirror (tight — same
    arithmetic), mirror == attention over the dequantized pool (exact),
    and the int8-vs-f32 drift stays inside the error bound."""
    if window and chunk:
        chunk = 0
    rng = np.random.default_rng(seed)
    P, page, hkv, d, dv = 9, 8, 2, 16, 16
    B, N = 2, 4
    spec = quant.get_spec("int8")
    kf, vf, kq, vq, ks, vs = _quantized_pool(rng, P, page, hkv, d, dv, spec)
    tbl = jnp.asarray(rng.permutation(np.arange(1, P))[: B * N].reshape(B, N))
    clen = jnp.asarray(rng.integers(1, N * page + 1, (B,)), jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, 1, group * hkv, d)), jnp.float32)

    from repro.kernels.ops import pallas_decode_paged

    o_kernel = pallas_decode_paged(
        q, kq, vq, tbl, clen, window=window, chunk=chunk,
        k_scale=ks, v_scale=vs,
    )
    o_mirror = decode_attention_paged(
        q, kq, vq, tbl, clen, window=window, chunk=chunk,
        k_scale=ks, v_scale=vs,
    )
    o_dequant = decode_attention_paged(
        q, quant.dequantize_pages(kq, ks), quant.dequantize_pages(vq, vs),
        tbl, clen, window=window, chunk=chunk,
    )
    o_f32 = decode_attention_paged(
        q, kf, vf, tbl, clen, window=window, chunk=chunk,
    )
    assert float(jnp.max(jnp.abs(o_kernel - o_mirror))) < 5e-5
    assert float(jnp.max(jnp.abs(o_mirror - o_dequant))) < 1e-6
    assert float(jnp.max(jnp.abs(o_mirror - o_f32))) < 0.5  # coarse sanity bound


@settings(max_examples=10, deadline=None)
@given(
    group=st.sampled_from([1, 2, 4]),
    window=st.sampled_from([0, 0, 6]),
    ragged=st.booleans(),
    seed=st.integers(min_value=0, max_value=10**6),
)
# clipped 8 of 2,304 values when the slot-0 row kept only 2x headroom
@example(group=1, window=6, ragged=False, seed=873547)
def test_varlen_quantized_differential(group, window, ragged, seed):
    """Packed varlen, quantized pool: same oracle chain as paged decode,
    over mixed prefill/decode raggedness (per-sequence kv_len, padding
    rows) and GQA groupings."""
    rng = np.random.default_rng(seed)
    P, page, hkv, d, dv = 9, 8, 2, 16, 16
    B, N, block_q = 2, 4, 8
    spec = quant.get_spec("int8")
    kf, vf, kq, vq, ks, vs = _quantized_pool(rng, P, page, hkv, d, dv, spec)
    tbl = jnp.asarray(rng.permutation(np.arange(1, P))[: B * N].reshape(B, N))
    if ragged:
        kv_len = jnp.asarray(rng.integers(1, N * page + 1, (B,)), jnp.int32)
    else:
        kv_len = jnp.full((B,), N * page, jnp.int32)
    # one block_q-aligned segment per sequence, tail rows padded
    seq_ids, q_pos = [], []
    for b in range(B):
        n = int(rng.integers(1, block_q + 1))
        start = max(int(kv_len[b]) - n, 0)
        seq_ids += [b] * n + [-1] * (block_q - n)
        q_pos += list(range(start, start + n)) + [-1] * (block_q - n)
    seq_ids = jnp.asarray(seq_ids, jnp.int32)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    T = B * block_q
    q = jnp.asarray(rng.standard_normal((T, group * hkv, d)), jnp.float32)

    kw = dict(window=window, block_q=block_q)
    o_kernel = varlen_attention(
        q, kq, vq, tbl, seq_ids, q_pos, kv_len, impl="flashd_pallas",
        k_scale=ks, v_scale=vs, **kw,
    )
    o_mirror = varlen_attention(
        q, kq, vq, tbl, seq_ids, q_pos, kv_len, impl="flashd",
        k_scale=ks, v_scale=vs, **kw,
    )
    o_dequant = varlen_attention(
        q, quant.dequantize_pages(kq, ks), quant.dequantize_pages(vq, vs),
        tbl, seq_ids, q_pos, kv_len, impl="flashd", **kw,
    )
    o_f32 = varlen_attention(
        q, kf, vf, tbl, seq_ids, q_pos, kv_len, impl="flashd", **kw,
    )
    assert float(jnp.max(jnp.abs(o_kernel - o_mirror))) < 5e-5
    assert float(jnp.max(jnp.abs(o_mirror - o_dequant))) < 1e-6
    assert float(jnp.max(jnp.abs(o_mirror - o_f32))) < 0.5  # coarse sanity bound


def test_gather_pages_dequantizes():
    rng = np.random.default_rng(2)
    spec = quant.get_spec("int8")
    _, _, kq, _, ks, _ = _quantized_pool(rng, 5, 4, 2, 8, 8, spec)
    tbl = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    got = gather_pages(kq, tbl, scales=ks)
    want = gather_pages(quant.dequantize_pages(kq, ks), tbl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-7)


def test_fp8_is_a_dtype_swap():
    """The fp8 spec rides the exact same plumbing — only (dtype, qmax)
    differ. One mirror-vs-dequantized-oracle pass is enough to pin it."""
    rng = np.random.default_rng(3)
    spec = quant.get_spec("fp8")
    _, _, kq, vq, ks, vs = _quantized_pool(rng, 5, 4, 2, 8, 8, spec)
    assert kq.dtype == jnp.float8_e4m3fn
    tbl = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    clen = jnp.asarray([6, 8], jnp.int32)
    q = jnp.asarray(rng.standard_normal((2, 1, 4, 8)), jnp.float32)
    o = decode_attention_paged(q, kq, vq, tbl, clen, k_scale=ks, v_scale=vs)
    o_ref = decode_attention_paged(
        q, quant.dequantize_pages(kq, ks), quant.dequantize_pages(vq, vs),
        tbl, clen,
    )
    assert float(jnp.max(jnp.abs(o - o_ref))) < 1e-6


# ---------------------------------------------------------------------------
# serving: write determinism, warm hits, drift, chaos
# ---------------------------------------------------------------------------


def _cfg():
    return dataclasses.replace(
        paper_llama.CONFIG, n_layers=2, d_model=48, n_heads=4, n_kv_heads=2,
        d_ff=96, head_dim=12, vocab_size=64, vocab_pad_multiple=64,
    )


def _sc(mode="sequential", **kw):
    base = dict(max_batch=4, max_len=32, kv_layout="paged", page_size=4,
                kv_dtype="int8", step_mode=mode)
    base.update(kw)
    return ServeConfig(**base)


@pytest.fixture(scope="module")
def engine_fixture():
    cfg = _cfg()
    from repro.models.transformer import init_lm

    params = init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 60, (n,)).astype(np.int32) for n in (7, 11, 5)]
    return cfg, params, prompts


def test_write_determinism_sequential_vs_packed(engine_fixture):
    """The slot-0 scale rule makes pool state write-order deterministic:
    the sequential one-token step and the packed varlen step produce
    token-identical serves from the same quantized pool format."""
    cfg, params, prompts = engine_fixture
    out_seq = Engine(params, cfg, _sc("sequential")).serve(prompts, 6)
    out_mix = Engine(params, cfg, _sc("mixed")).serve(prompts, 6)
    for a, b in zip(out_seq, out_mix):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kv_dtype", ["", "int8"])
@pytest.mark.parametrize("step", ["decode", "packed"])
def test_stacked_step_pins_other_layers(step, kv_dtype):
    """A layer's step on the stacked pool (DESIGN.md §3.4) writes that
    layer alone: with the pool three layers deep and the step at layer
    1, the output and layer 1's new pages and scales equal the same step
    on a one-layer pool, and layers 0 and 2 come back bit-unchanged.
    Both steps write slot 0 of a page beside later slots, so the slot-0
    scale rule runs on the stacked scale leaf too."""
    from repro.models import transformer as tf

    cfg = dataclasses.replace(
        _cfg(), n_layers=3, dtype="bfloat16", attn_impl="flashd_pallas"
    )
    rng = np.random.default_rng(11)
    cache = tf.init_decode_cache(
        2, 16, cfg, layout="paged", page_size=4, kv_dtype=kv_dtype
    )["blocks"]["pos0"]

    def fill(name, x):  # distinct data in every layer: a stray write shows
        if name == "tbl":
            return jnp.broadcast_to(jnp.arange(1, 9).reshape(2, 4), x.shape)
        if name.endswith("scale"):
            return jnp.asarray(rng.uniform(0.01, 0.1, x.shape), x.dtype)
        if x.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, x.shape), x.dtype)
        return jnp.asarray(rng.standard_normal(x.shape), x.dtype)

    stack = {n: fill(n, x) for n, x in cache.items()}
    layer = 1
    p = tf._init_attn(jax.random.PRNGKey(2), cfg)
    if step == "decode":
        x = jnp.asarray(rng.standard_normal((2, 1, cfg.d_model)), jnp.bfloat16)
        pos = jnp.asarray([5, 8], jnp.int32)  # row 1 opens page 2 (slot 0)
        run = lambda c, i: tf._decode_attn(p, x, cfg, "attn", c, pos, i)
    else:
        x = jnp.asarray(rng.standard_normal((1, 8, cfg.d_model)), jnp.bfloat16)
        seq_ids = jnp.asarray([0, 0, 0, 0, 1, 1, -1, -1], jnp.int32)
        positions = jnp.asarray([3, 4, 5, 6, 0, 1, -1, -1], jnp.int32)
        kv_len = jnp.asarray([7, 2], jnp.int32)
        run = lambda c, i: tf._packed_attn(
            p, x, cfg, "attn", c, positions, seq_ids, kv_len, 4, i
        )

    def at_layer(c, i):  # the layer's view: pool leaves stay stacked
        return {n: x if n != "tbl" else x[i] for n, x in c.items()}

    y, new = run(at_layer(stack, layer), layer)
    one = {n: x[layer:layer + 1] for n, x in stack.items()}
    y1, new1 = run(at_layer(one, 0), 0)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y1))
    for name, old in stack.items():
        if name == "tbl":
            continue
        got = np.asarray(new[name])
        np.testing.assert_array_equal(got[layer], np.asarray(new1[name][0]))
        assert not np.array_equal(got[layer], np.asarray(old[layer]))
        for other in (0, 2):
            np.testing.assert_array_equal(got[other], np.asarray(old[other]))


def test_radix_warm_hit_token_identical_int8(engine_fixture):
    """A warm radix hit replays cached quantized pages: because a donated
    page's bytes+scale are a pure function of its token prefix, the warm
    serve is token-identical to the cold one."""
    cfg, params, prompts = engine_fixture
    eng = Engine(params, cfg, _sc("sequential"))
    cold = eng.serve(prompts, 6)
    warm = eng.serve(prompts, 6)
    assert eng.stats()["hit_tokens"] > 0
    for a, b in zip(cold, warm):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    eng._alloc.check(eng._paged_cache)


def test_stats_reports_pool_bytes(engine_fixture):
    cfg, params, prompts = engine_fixture
    eng8 = Engine(params, cfg, _sc())
    engf = Engine(params, cfg, _sc(kv_dtype=""))
    eng8.serve(prompts[:1], 2)
    engf.serve(prompts[:1], 2)
    s8, sf = eng8.stats(), engf.stats()
    assert s8["kv_dtype"] == "int8" and sf["kv_dtype"] == "native"
    # int8 pages + f32 scale side-band ≪ f32 pages
    assert s8["kv_bytes_per_token"] < sf["kv_bytes_per_token"] / 3
    assert s8["kv_pool_bytes"] > 0


def test_logprob_drift_bound(engine_fixture):
    """Teacher-forced paged decode, int8 vs native pool: max |Δ log p|
    over prefill + decode steps stays inside a small bound — the
    perplexity-style accuracy cost of the quantized cache."""
    cfg, params, _ = engine_fixture
    from jax import tree_util as jtu

    from repro.models.transformer import (
        decode_step_lm,
        init_decode_cache,
        prefill_lm,
    )

    rng = np.random.default_rng(3)
    B, plen, T, page, n_per = 2, 10, 6, 4, 8
    prompts = jnp.asarray(rng.integers(1, 60, (B, plen)), jnp.int32)
    tbl = jnp.asarray(
        [[1 + b * n_per + i for i in range(n_per)] for b in range(B)],
        jnp.int32,
    )

    def run(kv_dtype, forced):
        cache = init_decode_cache(
            B, 32, cfg, layout="paged", page_size=page,
            n_pages=1 + B * n_per, kv_dtype=kv_dtype,
        )

        def set_tbl(path, x):
            name = next(
                (e.key for e in reversed(path) if isinstance(e, jtu.DictKey)),
                None,
            )
            return jnp.broadcast_to(tbl, x.shape) if name == "tbl" else x

        cache = jtu.tree_map_with_path(set_tbl, cache)
        logits, cache = prefill_lm(params, prompts, cache, cfg)
        lps, toks = [jax.nn.log_softmax(logits[:, : cfg.vocab_size])], []
        for t in range(T):
            tok = (jnp.argmax(logits, -1).astype(jnp.int32)
                   if forced is None else forced[t])
            toks.append(tok)
            logits, cache = decode_step_lm(
                params, cache, tok, jnp.full((B,), plen + t), cfg
            )
            lps.append(jax.nn.log_softmax(logits[:, : cfg.vocab_size]))
        return jnp.stack(lps), toks

    lp_f32, toks = run("", None)
    lp_q, _ = run("int8", toks)
    assert float(jnp.max(jnp.abs(lp_q - lp_f32))) < 0.1


def test_allocator_check_validates_scales(engine_fixture):
    """`check(cache)` pins the scale side-band: leaf spans the physical
    page axis (shared pages therefore share one entry), in-use pages'
    scales finite and positive — and a corrupted scale trips it."""
    cfg, params, prompts = engine_fixture
    eng = Engine(params, cfg, _sc())
    eng.serve(prompts, 4)
    alloc, cache = eng._alloc, eng._paged_cache
    alloc.check(cache)  # healthy pool passes
    in_use = [pid for pid in range(alloc.n_pages) if alloc._ref[pid] > 0]
    assert in_use, "warm radix cache should retain pages"
    from repro.serve.engine import _map_paged

    bad = _map_paged(
        cache,
        pool=lambda x: (x.at[0, in_use[0]].set(-1.0)
                        if x.ndim == 3 else x),  # scale leaves only
    )
    with pytest.raises(AssertionError, match="non-positive"):
        alloc.check(bad)


@settings(max_examples=4, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10**6),
    rate=st.floats(min_value=0.05, max_value=0.3),
    mode=st.sampled_from(["sequential", "mixed"]),
)
def test_chaos_int8_terminal_clean(engine_fixture, seed, rate, mode):
    """kv_dtype=int8 under the chaos harness: every request terminal,
    DONE survivors token-identical to the fault-free int8 run, and the
    allocator invariants — scale leaves included — hold after recovery."""
    cfg, params, prompts = engine_fixture
    baseline = Engine(params, cfg, _sc(mode)).serve(prompts, 4)
    eng = Engine(params, cfg, _sc(mode),
                 fault_injector=FaultInjector(rate=rate, seed=seed))
    outs = eng.serve(prompts, 4)
    status = eng.stats()["request_status"]
    assert set(status) == set(range(len(prompts)))
    assert all(s in TERMINAL for s in status.values()), status
    for i, base in enumerate(baseline):
        if status[i] == DONE:
            np.testing.assert_array_equal(np.asarray(base), np.asarray(outs[i]))
    eng._alloc.check(eng._paged_cache)
