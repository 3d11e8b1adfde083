"""Property-based differential suite for the packed varlen path (§3.5).

The varlen kernel subsumes the prefill forward and both decode kernels on
the serving path, so its contract is checked against BOTH established
families on randomly drawn packs:

    packed varlen (jnp mirror) == packed varlen (Pallas kernel)
    packed prefill segments    == per-sequence flash_attention (naive ref)
    packed decode rows         == per-sequence decode_attention

across mask families (causal / window / chunked), GQA ratios, and
raggedness: empty sequences (zero rows in the pack), length-1 segments
(decode as the degenerate case), segments starting mid-sequence (chunked
prefill), and alignment padding rows (must come back zero).

Runs on the real `hypothesis` when installed and on the deterministic
stub in `tests/conftest.py` otherwise (CI exercises both).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.attention import (
    MaskSpec,
    decode_attention,
    flash_attention,
    gather_pages,
    varlen_attention,
)
from repro.runtime import quant

_TOL = 1e-4  # observed agreement is a few f32 ulps


def _align(n, bq):
    return -(-n // bq) * bq


def _varlen_case(seed, n_seqs, hkv, group, d, n_tbl, page, block_q, kinds):
    """Random pool + block tables + a pack of per-sequence segments.

    Each sequence draws kv_len ∈ [0, n_tbl·page] and a segment style:
      'empty'   — no rows in the pack;
      'decode'  — one row at position kv_len−1 (needs kv_len ≥ 1);
      'prefill' — the last `q_len` positions of kv_len (a chunked-prefill
                  tail; q_len = kv_len gives the whole-prompt case).
    Segments are packed block_q-aligned (the kernel contract); padding
    rows carry seq_id = q_pos = −1.
    """
    rng = np.random.default_rng(seed)
    hq = hkv * group
    s_max = n_tbl * page
    n_pool = n_seqs * n_tbl + 2
    k_pages = jnp.asarray(rng.normal(size=(n_pool, page, hkv, d)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(n_pool, page, hkv, d)), jnp.float32)
    perm = rng.permutation(np.arange(1, n_pool))[: n_seqs * n_tbl]
    tbl = jnp.asarray(perm.reshape(n_seqs, n_tbl), jnp.int32)

    kv_len = np.zeros((n_seqs,), np.int32)
    segs = []  # (seq, start, q_len)
    for s in range(n_seqs):
        kind = kinds[s % len(kinds)]
        if kind == "empty":
            kv_len[s] = rng.integers(0, s_max + 1)
            continue
        if kind == "decode":
            kv_len[s] = rng.integers(1, s_max + 1)
            segs.append((s, int(kv_len[s]) - 1, 1))
        else:  # prefill tail
            kv_len[s] = rng.integers(1, s_max + 1)
            q_len = int(rng.integers(1, kv_len[s] + 1))
            segs.append((s, int(kv_len[s]) - q_len, q_len))

    total = sum(_align(n, block_q) for _, _, n in segs) or block_q
    seq_ids = np.full((total,), -1, np.int32)
    q_pos = np.full((total,), -1, np.int32)
    off = 0
    rows = {}  # seq → (pack offset, start, q_len)
    for s, start, n in segs:
        seq_ids[off:off + n] = s
        q_pos[off:off + n] = np.arange(start, start + n)
        rows[s] = (off, start, n)
        off += _align(n, block_q)
    q = jnp.asarray(rng.normal(size=(total, hq, d)), jnp.float32)
    return q, k_pages, v_pages, tbl, seq_ids, q_pos, jnp.asarray(kv_len), rows


def _mask_kw(maskkind, maskparam, s_max):
    if maskkind == "window":
        return {"window": 1 + maskparam % s_max, "chunk": 0}
    if maskkind == "chunk":
        return {"window": 0, "chunk": 1 + maskparam % s_max}
    return {"window": 0, "chunk": 0}


@settings(max_examples=12, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    n_seqs=st.integers(min_value=1, max_value=4),
    hkv=st.integers(min_value=1, max_value=2),
    group=st.sampled_from([1, 2, 4]),
    d=st.sampled_from([8, 16]),
    n_tbl=st.integers(min_value=1, max_value=3),
    page=st.sampled_from([4, 8]),
    block_q=st.sampled_from([4, 8]),
    maskkind=st.sampled_from(["causal", "window", "chunk"]),
    maskparam=st.integers(min_value=0, max_value=63),
)
def test_varlen_pallas_vs_jnp(seed, n_seqs, hkv, group, d, n_tbl, page,
                              block_q, maskkind, maskparam):
    """Pallas varlen kernel == jnp mirror on random mixed packs."""
    q, kp, vp, tbl, sids, qpos, kvl, _ = _varlen_case(
        seed, n_seqs, hkv, group, d, n_tbl, page, block_q,
        kinds=("prefill", "decode", "empty"),
    )
    kw = _mask_kw(maskkind, maskparam, n_tbl * page)
    a = varlen_attention(q, kp, vp, tbl, sids, qpos, kvl, impl="flashd", **kw)
    b = varlen_attention(
        q, kp, vp, tbl, sids, qpos, kvl, impl="flashd_pallas",
        block_q=block_q, **kw,
    )
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=_TOL, rtol=_TOL)
    # alignment padding rows come back exactly zero on both paths
    pad = np.asarray(sids) < 0
    if pad.any():
        assert float(jnp.max(jnp.abs(a[pad]))) == 0.0
        assert float(jnp.max(jnp.abs(b[pad]))) == 0.0


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    n_seqs=st.integers(min_value=1, max_value=3),
    hkv=st.integers(min_value=1, max_value=2),
    group=st.sampled_from([1, 2, 4]),
    d=st.sampled_from([8, 16]),
    n_tbl=st.integers(min_value=1, max_value=3),
    page=st.sampled_from([4, 8]),
    impl=st.sampled_from(["flashd", "flashd_pallas"]),
)
def test_varlen_prefill_rows_vs_flash_attention(seed, n_seqs, hkv, group, d,
                                                n_tbl, page, impl):
    """Prefill segments of a pack == per-sequence flash_attention over the
    gathered contiguous cache (naive ref oracle, causal at the segment's
    absolute offset)."""
    bq = 4
    q, kp, vp, tbl, sids, qpos, kvl, rows = _varlen_case(
        seed, n_seqs, hkv, group, d, n_tbl, page, bq, kinds=("prefill",),
    )
    o = varlen_attention(
        q, kp, vp, tbl, sids, qpos, kvl, impl=impl, block_q=bq,
    )
    kc = gather_pages(kp, tbl)
    vc = gather_pages(vp, tbl)
    for s, (off, start, n) in rows.items():
        kv = int(kvl[s])
        want = flash_attention(
            q[off:off + n][None], kc[s:s + 1, :kv], vc[s:s + 1, :kv],
            mask=MaskSpec("causal", q_offset=start), impl="naive",
        )[0]
        np.testing.assert_allclose(
            np.asarray(o[off:off + n]), np.asarray(want), atol=_TOL, rtol=_TOL,
        )


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    n_seqs=st.integers(min_value=1, max_value=4),
    hkv=st.integers(min_value=1, max_value=2),
    group=st.sampled_from([1, 2, 4]),
    d=st.sampled_from([8, 16]),
    n_tbl=st.integers(min_value=1, max_value=3),
    page=st.sampled_from([4, 8]),
    maskkind=st.sampled_from(["causal", "window", "chunk"]),
    maskparam=st.integers(min_value=0, max_value=63),
    impl=st.sampled_from(["flashd", "flashd_pallas"]),
)
def test_varlen_decode_rows_vs_decode_attention(seed, n_seqs, hkv, group, d,
                                                n_tbl, page, maskkind,
                                                maskparam, impl):
    """Decode rows of a pack (q_len == 1 segments) == decode_attention over
    the gathered contiguous cache — the degenerate-case claim."""
    bq = 4
    q, kp, vp, tbl, sids, qpos, kvl, rows = _varlen_case(
        seed, n_seqs, hkv, group, d, n_tbl, page, bq, kinds=("decode", "empty"),
    )
    kw = _mask_kw(maskkind, maskparam, n_tbl * page)
    o = varlen_attention(q, kp, vp, tbl, sids, qpos, kvl, impl=impl,
                         block_q=bq, **kw)
    kc = gather_pages(kp, tbl)
    vc = gather_pages(vp, tbl)
    for s, (off, start, n) in rows.items():
        assert n == 1
        want = decode_attention(
            q[off:off + 1][None], kc[s:s + 1], vc[s:s + 1],
            jnp.asarray([int(kvl[s])]), n_splits=1, **kw,
        )
        np.testing.assert_allclose(
            np.asarray(o[off]), np.asarray(want[0, 0]), atol=_TOL, rtol=_TOL,
        )


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@settings(max_examples=6, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    n_seqs=st.integers(min_value=1, max_value=3),
    group=st.sampled_from([1, 4]),
    n_tbl=st.integers(min_value=1, max_value=3),
    page=st.sampled_from([4, 8]),
    n_layers=st.integers(min_value=2, max_value=3),
    layer=st.integers(min_value=0, max_value=2),
)
def test_varlen_stacked_pool(kv, seed, n_seqs, group, n_tbl, page, n_layers,
                             layer):
    """The packed step hands the kernel the whole stacked pool and a
    layer index (DESIGN.md §3.4): on [L, P, ...] the kernel returns bit
    for bit what it returns on pool[layer] — scales included — and so
    does the jnp mirror; kernel and mirror agree. The other layers hold
    other pages and the drawn layer and the last are read, so reading
    the wrong one shows."""
    q, kp, vp, tbl, sids, qpos, kvl, _ = _varlen_case(
        seed, n_seqs, 2, group, 16, n_tbl, page, 4,
        kinds=("prefill", "decode", "empty"),
    )
    if kv == "bf16":
        q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
        pool = {"k_pages": kp, "v_pages": vp}
    else:
        spec = quant.get_spec(kv)
        ks, vs = (quant.slot0_scale(x[:, 0], spec) for x in (kp, vp))
        pool = {"k_pages": quant.quantize_rows(kp, ks[:, None, :], spec),
                "v_pages": quant.quantize_rows(vp, vs[:, None, :], spec),
                "k_scale": ks, "v_scale": vs}
    def run(impl, pool, **kw):
        return np.asarray(varlen_attention(
            q, pool["k_pages"], pool["v_pages"], tbl, sids, qpos, kvl,
            impl=impl, block_q=4, k_scale=pool.get("k_scale"),
            v_scale=pool.get("v_scale"), **kw,
        ), np.float32)

    o_kern, o_mirror = run("flashd_pallas", pool), run("flashd", pool)
    for at in {layer % n_layers, n_layers - 1}:
        stack = {n: jnp.stack([jnp.roll(x, i - at, axis=0)
                               for i in range(n_layers)])
                 for n, x in pool.items()}
        np.testing.assert_array_equal(
            run("flashd_pallas", stack, layer=at), o_kern)
        np.testing.assert_array_equal(run("flashd", stack, layer=at), o_mirror)
    tol = 3e-2 if kv == "bf16" else 5e-5
    np.testing.assert_allclose(o_kern, o_mirror, rtol=tol, atol=tol)


def test_varlen_mixed_pack_three_way():
    """One pack holding a whole prompt, a mid-sequence chunk, a decode row
    and an empty sequence — jnp == pallas == per-row oracles."""
    rng = np.random.default_rng(7)
    hkv, group, d, page, n_tbl, bq = 2, 2, 16, 8, 3, 8
    hq = hkv * group
    n_seqs = 4
    n_pool = n_seqs * n_tbl + 2
    kp = jnp.asarray(rng.normal(size=(n_pool, page, hkv, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pool, page, hkv, d)), jnp.float32)
    tbl = jnp.asarray(
        rng.permutation(np.arange(1, n_pool))[: n_seqs * n_tbl]
        .reshape(n_seqs, n_tbl), jnp.int32)
    # seq0: whole prompt len 10; seq1: chunk [6, 13) of kv 13; seq2: decode
    # at 20 (kv 21); seq3: empty
    segs = [(0, 0, 10), (1, 6, 7), (2, 20, 1)]
    kvl = jnp.asarray([10, 13, 21, 5], jnp.int32)
    total = sum(_align(n, bq) for _, _, n in segs)
    sids = np.full((total,), -1, np.int32)
    qpos = np.full((total,), -1, np.int32)
    off, offs = 0, []
    for s, start, n in segs:
        sids[off:off + n] = s
        qpos[off:off + n] = np.arange(start, start + n)
        offs.append(off)
        off += _align(n, bq)
    q = jnp.asarray(rng.normal(size=(total, hq, d)), jnp.float32)

    a = varlen_attention(q, kp, vp, tbl, sids, qpos, kvl, impl="flashd")
    b = varlen_attention(q, kp, vp, tbl, sids, qpos, kvl,
                         impl="flashd_pallas", block_q=bq)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=_TOL, rtol=_TOL)

    kc = gather_pages(kp, tbl)
    vc = gather_pages(vp, tbl)
    for (s, start, n), o0 in zip(segs, offs):
        kv = int(kvl[s])
        want = flash_attention(
            q[o0:o0 + n][None], kc[s:s + 1, :kv], vc[s:s + 1, :kv],
            mask=MaskSpec("causal", q_offset=start), impl="naive",
        )[0]
        np.testing.assert_allclose(
            np.asarray(a[o0:o0 + n]), np.asarray(want), atol=_TOL, rtol=_TOL,
        )
    # padding + empty-seq rows are zero
    pad = sids < 0
    assert float(jnp.max(jnp.abs(a[pad]))) == 0.0


def test_varlen_registry_exposes_op():
    """The varlen entry point is registered and re-exported (kernels is a
    registry, not a hand-threaded import chain)."""
    from repro import kernels

    assert "varlen" in kernels.op_names()
    assert kernels.get_op("varlen") is kernels.pallas_varlen
    for name in ("attention_fwd", "decode", "decode_paged"):
        assert callable(kernels.get_op(name))
    with pytest.raises(KeyError):
        kernels.get_op("nope")


def test_varlen_rejects_misaligned_total():
    q = jnp.zeros((6, 2, 8), jnp.float32)
    kp = jnp.zeros((3, 4, 1, 8), jnp.float32)
    with pytest.raises(ValueError):
        from repro.kernels.flashd_varlen import flashd_varlen_pallas

        flashd_varlen_pallas(
            q, kp, kp, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((6,), jnp.int32), jnp.zeros((6,), jnp.int32),
            jnp.asarray([4]), block_q=4, interpret=True,
        )
