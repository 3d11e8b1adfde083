"""Property-based differential suite for the paged KV-cache path.

Three kernel families now share masks, splits and the FLASH-D sigmoid
merge (flashd/fa2 forward + bwd, fused/unfused decode, ring/cp) and the
paged decode adds block-table indirection on top — hand-enumerated cases
no longer cover the cross-product. This fuzzer draws
batch / GQA ratio / head_dim / page geometry / ragged cache_len / mask
family and asserts the three-way agreement

    paged decode (block-table gather) == contiguous fused decode == decode_ref

including the edges the allocator produces in real schedules: empty
sequences (cache_len = 0), a page boundary exactly at cache_len, a full
table, and block tables pointing at arbitrary (non-contiguous, unsorted)
physical pages. Engine-level properties: paged `serve` is token-identical
to the contiguous engine, shared-prefix CoW admission diverges without
cross-talk, and a page-starved pool still completes every request by
waiting for frees.

Runs on the real `hypothesis` when installed and on the deterministic
stub in `tests/conftest.py` otherwise (CI exercises both).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.attention import decode_attention_paged, gather_pages
from repro.kernels.flashd_decode import (
    flashd_decode_paged_pallas,
    flashd_decode_pallas,
)
from repro.kernels.ref import decode_ref
from repro.runtime import quant

_F32_TOL = 1e-4  # acceptance bound; observed agreement is ~2 f32 ulps


def _paged_case(seed, b, hkv, group, d, n_tbl, page, edge):
    """Random pool + per-row block tables of distinct physical pages
    (page 0 left as the garbage page, like the engine convention)."""
    rng = np.random.default_rng(seed)
    hq = hkv * group
    s_max = n_tbl * page
    n_pool = b * n_tbl + 2
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    k_pages = jnp.asarray(rng.normal(size=(n_pool, page, hkv, d)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(n_pool, page, hkv, d)), jnp.float32)
    perm = rng.permutation(np.arange(1, n_pool))[: b * n_tbl].reshape(b, n_tbl)
    tbl = jnp.asarray(perm, jnp.int32)
    if edge == "empty":
        cl = np.zeros((b,), np.int32)  # no visible key anywhere
    elif edge == "page_boundary":  # cache_len exactly at a page edge
        cl = page * rng.integers(0, n_tbl + 1, size=(b,))
    elif edge == "full":
        cl = np.full((b,), s_max, np.int32)
    else:
        cl = rng.integers(0, s_max + 1, size=(b,))
    return q, k_pages, v_pages, tbl, jnp.asarray(cl, jnp.int32)


def _mask_kw(maskkind, maskparam, s_max):
    if maskkind == "window":
        return {"window": 1 + maskparam % s_max, "chunk": 0}
    if maskkind == "chunk":
        return {"window": 0, "chunk": 1 + maskparam % s_max}
    return {"window": 0, "chunk": 0}


@settings(max_examples=14, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    b=st.integers(min_value=1, max_value=3),
    hkv=st.integers(min_value=1, max_value=2),
    group=st.sampled_from([1, 2, 4, 8]),
    d=st.sampled_from([8, 16, 32]),
    n_tbl=st.integers(min_value=1, max_value=4),
    page=st.sampled_from([4, 8, 16]),
    maskkind=st.sampled_from(["none", "window", "chunk"]),
    maskparam=st.integers(min_value=0, max_value=63),
    edge=st.sampled_from(["rand", "empty", "page_boundary", "full"]),
)
def test_paged_differential_fuzz(seed, b, hkv, group, d, n_tbl, page,
                                 maskkind, maskparam, edge):
    """paged kernel == contiguous fused kernel == decode_ref, model layout
    gather as the bridge, across the fuzzed shape/mask/raggedness grid."""
    q, k_pages, v_pages, tbl, cl = _paged_case(
        seed, b, hkv, group, d, n_tbl, page, edge
    )
    s_max = n_tbl * page
    kw = _mask_kw(maskkind, maskparam, s_max)

    o_paged = flashd_decode_paged_pallas(
        q, k_pages, v_pages, tbl, cl, interpret=True, **kw
    )
    # contiguous oracle: materialize the block-table gather
    kc = jnp.moveaxis(k_pages[tbl], 3, 1).reshape(-1, k_pages.shape[2], s_max, d)
    vc = jnp.moveaxis(v_pages[tbl], 3, 1).reshape(-1, v_pages.shape[2], s_max, d)
    o_fused = flashd_decode_pallas(
        q, kc, vc, cl, n_splits=n_tbl, fused=True, interpret=True, **kw
    )
    o_ref = decode_ref(q, kc, vc, cl, **kw)
    np.testing.assert_allclose(o_paged, o_fused, rtol=0, atol=_F32_TOL)
    np.testing.assert_allclose(o_paged, o_ref, rtol=_F32_TOL, atol=_F32_TOL)
    # dead rows obey the zero (dead-partial) convention through the table
    for i, n in enumerate(np.asarray(cl)):
        if n == 0:
            np.testing.assert_array_equal(np.asarray(o_paged[i]), 0.0)


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    group=st.sampled_from([1, 4]),
    page=st.sampled_from([4, 8]),
    n_tbl=st.integers(min_value=1, max_value=3),
)
def test_paged_jnp_route_matches_kernel(seed, group, page, n_tbl):
    """core.decode_attention_paged (gather + split-K jnp path — what
    non-pallas impls and the CP fallback run) agrees with the paged kernel
    and with gather_pages feeding decode_ref."""
    b, hkv, d = 2, 2, 16
    q, k_pages, v_pages, tbl, cl = _paged_case(
        seed, b, hkv, group, d, n_tbl, page, "rand"
    )
    o_jnp = decode_attention_paged(q[:, None], k_pages, v_pages, tbl, cl)[:, 0]
    o_kern = flashd_decode_paged_pallas(q, k_pages, v_pages, tbl, cl,
                                        interpret=True)
    np.testing.assert_allclose(o_jnp, o_kern, rtol=_F32_TOL, atol=_F32_TOL)
    # gather_pages is the shared bridge: one reshape of the table gather
    kc = gather_pages(k_pages, tbl)  # [B, S, Hkv, d] model layout
    np.testing.assert_array_equal(
        np.asarray(kc),
        np.asarray(k_pages)[np.asarray(tbl)].reshape(b, n_tbl * page, hkv, d),
    )


def _stack(x, layer, n_layers):
    """x as layer `layer` of an [n_layers, ...] stack whose other layers
    hold other pages (x rolled along its page axis)."""
    return jnp.stack([jnp.roll(x, i - layer, axis=0) for i in range(n_layers)])


def _typed_pool(kv, q, k_pages, v_pages):
    """The case in a bf16 pool, or int8 with slot-0 scales (DESIGN.md
    §3.8) → (q, pool dict of k_pages, v_pages[, k_scale, v_scale])."""
    if kv == "bf16":
        q, k_pages, v_pages = (x.astype(jnp.bfloat16) for x in (q, k_pages, v_pages))
        return q, {"k_pages": k_pages, "v_pages": v_pages}
    spec = quant.get_spec(kv)
    pool = {}
    for name, x in (("k", k_pages), ("v", v_pages)):
        sc = quant.slot0_scale(x[:, 0], spec)
        pool[f"{name}_pages"] = quant.quantize_rows(x, sc[:, None, :], spec)
        pool[f"{name}_scale"] = sc
    return q, pool


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@settings(max_examples=6, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    group=st.sampled_from([1, 4]),
    page=st.sampled_from([4, 8]),
    n_tbl=st.integers(min_value=1, max_value=3),
    n_layers=st.integers(min_value=2, max_value=3),
    layer=st.integers(min_value=0, max_value=2),
)
def test_paged_decode_stacked_pool(kv, seed, group, page, n_tbl, n_layers,
                                   layer):
    """The model's layer loop hands the kernel the whole stacked pool
    and a layer index (DESIGN.md §3.4): the kernel on [L, P, ...] returns
    bit for bit what it returns on pool[layer], scales included, and so
    does the jnp mirror; kernel and mirror agree. The drawn layer and the
    last are read, so a kernel stuck on layer 0 shows."""
    q, k_pages, v_pages, tbl, cl = _paged_case(
        seed, 2, 2, group, 16, n_tbl, page, "rand"
    )
    q, pool = _typed_pool(kv, q, k_pages, v_pages)

    def run(fn, pool, **kw):
        return fn(pool["k_pages"], pool["v_pages"], k_scale=pool.get("k_scale"),
                  v_scale=pool.get("v_scale"), **kw)

    kern = lambda k, v, **kw: flashd_decode_paged_pallas(
        q, k, v, tbl, cl, interpret=True, **kw)
    mirror = lambda k, v, **kw: decode_attention_paged(
        q[:, None], k, v, tbl, cl, **kw)[:, 0]
    o_kern, o_mirror = run(kern, pool), run(mirror, pool)
    for at in {layer % n_layers, n_layers - 1}:
        stack = {n: _stack(x, at, n_layers) for n, x in pool.items()}
        np.testing.assert_array_equal(
            np.asarray(run(kern, stack, layer=at)), np.asarray(o_kern))
        np.testing.assert_array_equal(
            np.asarray(run(mirror, stack, layer=at)), np.asarray(o_mirror))
    tol = 3e-2 if kv == "bf16" else 5e-5
    np.testing.assert_allclose(
        np.asarray(o_kern, np.float32), np.asarray(o_mirror, np.float32),
        rtol=tol, atol=tol,
    )


def test_paged_bf16_tolerance():
    q, k_pages, v_pages, tbl, cl = _paged_case(11, 2, 2, 2, 16, 3, 8, "rand")
    qb = q.astype(jnp.bfloat16)
    kb, vb = k_pages.astype(jnp.bfloat16), v_pages.astype(jnp.bfloat16)
    o = flashd_decode_paged_pallas(qb, kb, vb, tbl, cl, interpret=True)
    assert o.dtype == jnp.bfloat16
    kc = jnp.moveaxis(kb[tbl], 3, 1).reshape(2, 2, 24, 16)
    vc = jnp.moveaxis(vb[tbl], 3, 1).reshape(2, 2, 24, 16)
    o_ref = decode_ref(qb, kc, vc, cl)
    np.testing.assert_allclose(
        o.astype(jnp.float32), o_ref.astype(jnp.float32), rtol=3e-2, atol=3e-2
    )


def test_paged_garbage_table_slots_are_inert():
    """Table entries past cache_len may hold anything (the engine points
    dead rows at page 0): they must not leak into the output."""
    q, k_pages, v_pages, tbl, cl = _paged_case(3, 2, 1, 2, 8, 3, 4, "rand")
    cl = jnp.asarray([5, 9], jnp.int32)  # live pages: ⌈5/4⌉=2, ⌈9/4⌉=3
    o1 = flashd_decode_paged_pallas(q, k_pages, v_pages, tbl, cl, interpret=True)
    tbl2 = tbl.at[0, 2].set(0)  # row 0's dead tail page → garbage page
    o2 = flashd_decode_paged_pallas(q, k_pages, v_pages, tbl2, cl, interpret=True)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


# ---------------------------------------------------------------------------
# engine-level properties
# ---------------------------------------------------------------------------

def _cfg(**kw):
    from repro.configs import paper_llama

    return dataclasses.replace(
        paper_llama.CONFIG, n_layers=2, d_model=48, n_heads=4, n_kv_heads=2,
        d_ff=96, head_dim=12, vocab_size=64, vocab_pad_multiple=64, **kw,
    )


@pytest.fixture(scope="module")
def engine_fixture():
    from repro.models import get_model

    cfg = _cfg()
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_paged_serve_matches_contiguous(engine_fixture):
    """Token-identical continuous batching: the paged engine (pool +
    block tables + tail prefills) reproduces the contiguous engine's
    outputs for the same queue."""
    from repro.serve import Engine, ServeConfig

    cfg, params = engine_fixture
    rng = np.random.default_rng(0)
    reqs = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in (4, 9, 6, 3, 7)]
    want = Engine(params, cfg, ServeConfig(max_batch=2, max_len=32)).serve(reqs, 5)
    eng = Engine(params, cfg, ServeConfig(
        max_batch=2, max_len=32, kv_layout="paged", page_size=8))
    got = eng.serve(reqs, 5)
    for a, c in zip(want, got):
        np.testing.assert_array_equal(a, c)


def test_paged_serve_pallas_kernel_route(engine_fixture):
    """attn_impl=flashd_pallas decodes through the scalar-prefetch paged
    kernel inside the jitted chunk loop — same tokens as the jnp engine."""
    from repro.serve import Engine, ServeConfig

    cfg, params = engine_fixture
    rng = np.random.default_rng(1)
    reqs = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32) for n in (5, 8)]
    want = Engine(params, cfg, ServeConfig(max_batch=2, max_len=32)).serve(reqs, 4)
    got = Engine(params, dataclasses.replace(cfg, attn_impl="flashd_pallas"),
                 ServeConfig(max_batch=2, max_len=32, kv_layout="paged",
                             page_size=8)).serve(reqs, 4)
    for a, c in zip(want, got):
        np.testing.assert_array_equal(a, c)


def test_paged_shared_prefix_cow_after_divergence(engine_fixture):
    """Prompts sharing a >page prefix admit by reference + boundary CoW;
    after they diverge, every stream must still match the unshared
    contiguous engine (a corrupted shared page would flip the parent's or
    a sibling's tokens)."""
    from repro.serve import Engine, ServeConfig

    cfg, params = engine_fixture
    rng = np.random.default_rng(2)
    prefix = rng.integers(0, cfg.vocab_size, (10,)).astype(np.int32)
    reqs = [
        np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)])
        for n in (3, 2, 5)
    ]
    want = Engine(params, cfg, ServeConfig(max_batch=3, max_len=32)).serve(reqs, 5)
    eng = Engine(params, cfg, ServeConfig(
        max_batch=3, max_len=32, kv_layout="paged", page_size=8,
        prefix_sharing=True))
    got = eng.serve(reqs, 5)
    for a, c in zip(want, got):
        np.testing.assert_array_equal(a, c)


def test_paged_admission_waits_for_free_pages(engine_fixture):
    """Without preemption, admission reserves the worst case: a pool too
    small for all requests at once still completes every one (head-of-line
    requests wait for frees) and outputs match the ample-pool engine. With
    preemption (the default), the same pool is oversubscribed instead —
    every request admits optimistically and outputs stay identical."""
    from repro.serve import Engine, ServeConfig

    cfg, params = engine_fixture
    rng = np.random.default_rng(3)
    reqs = [rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32) for _ in range(4)]
    ample = Engine(params, cfg, ServeConfig(
        max_batch=4, max_len=32, kv_layout="paged", page_size=8,
        prefix_sharing=False))
    want = ample.serve(reqs, 4)
    tight = Engine(params, cfg, ServeConfig(
        max_batch=4, max_len=32, kv_layout="paged", page_size=8,
        kv_pool_tokens=48, prefix_sharing=False, preemption=False))
    got = tight.serve(reqs, 4)
    assert all(o.shape == (4,) for o in got)
    # the tight pool cannot host all four worst-case reservations at once
    assert tight.peak_active < 4
    for a, c in zip(want, got):
        np.testing.assert_array_equal(a, c)
    # preemptive mode: optimistic per-chunk allocation admits all four at
    # once and resolves the growth pressure by preemption, token-identical
    over = Engine(params, cfg, ServeConfig(
        max_batch=4, max_len=32, kv_layout="paged", page_size=8,
        kv_pool_tokens=48, prefix_sharing=False))
    got2 = over.serve(reqs, 4)
    assert over.peak_active == 4
    for a, c in zip(want, got2):
        np.testing.assert_array_equal(a, c)


def test_paged_serve_at_max_len_boundary(engine_fixture):
    """prompt + max_new == max_len: the speculative chunk slack must NOT
    grow the block table past its ⌈max_len/page⌉ width (writes past
    max_len clamp to the garbage page instead). Regression: this used to
    crash broadcasting a 1-page-too-long table row."""
    from repro.serve import Engine, ServeConfig

    cfg, params = engine_fixture
    rng = np.random.default_rng(5)
    reqs = [rng.integers(0, cfg.vocab_size, (26,)).astype(np.int32)]
    sc = dict(max_batch=1, max_len=32, decode_chunk=4)
    want = Engine(params, cfg, ServeConfig(**sc)).serve(reqs, 6)
    got = Engine(params, cfg, ServeConfig(**sc, kv_layout="paged",
                                          page_size=8)).serve(reqs, 6)
    np.testing.assert_array_equal(want[0], got[0])


def test_paged_hybrid_stack_disables_prefix_sharing(engine_fixture):
    """Ring/recurrent layers carry state the skipped prefill steps would
    have produced, so prefix sharing must auto-disable on hybrid stacks —
    shared-prefix prompts still serve token-identically to contiguous."""
    from repro.models import get_model
    from repro.serve import Engine, ServeConfig

    cfg = _cfg(pattern=(("attn_chunked", "swiglu"), ("attn", "swiglu")),
               attn_chunk=8)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(6)
    prefix = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
    reqs = [np.concatenate([prefix,
                            rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)])
            for n in (2, 3)]
    want = Engine(params, cfg, ServeConfig(max_batch=2, max_len=32)).serve(reqs, 4)
    eng = Engine(params, cfg, ServeConfig(max_batch=2, max_len=32,
                                          kv_layout="paged", page_size=8,
                                          prefix_sharing=True))
    assert eng._page_layout is not None  # the global-attn layers DO page
    assert not eng._can_share_prefix  # but sharing is gated off
    got = eng.serve(reqs, 4)
    for a, c in zip(want, got):
        np.testing.assert_array_equal(a, c)


def test_paged_pool_too_small_raises(engine_fixture):
    from repro.runtime.kvcache import PageError
    from repro.serve import Engine, ServeConfig

    cfg, params = engine_fixture
    eng = Engine(params, cfg, ServeConfig(
        max_batch=2, max_len=64, kv_layout="paged", page_size=8,
        kv_pool_tokens=16))
    req = np.arange(10, dtype=np.int32) % cfg.vocab_size
    with pytest.raises(PageError):
        eng.serve([req], max_new_tokens=8)


def test_paged_falls_back_without_global_attention(engine_fixture):
    """Pure ring/recurrent stacks have nothing to page: kv_layout='paged'
    must quietly serve through the contiguous layout."""
    from repro.models import get_model
    from repro.serve import Engine, ServeConfig

    cfg = _cfg(pattern=(("attn_local", "swiglu"),), attn_window=8)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    reqs = [rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)]
    eng = Engine(params, cfg, ServeConfig(max_batch=1, max_len=32,
                                          kv_layout="paged"))
    assert eng._page_layout is None
    want = Engine(params, cfg, ServeConfig(max_batch=1, max_len=32)).serve(reqs, 4)
    got = eng.serve(reqs, 4)
    np.testing.assert_array_equal(want[0], got[0])
