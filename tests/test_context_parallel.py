"""Context-parallel attention on forced 8-device host meshes (subprocess —
the main test process must keep seeing exactly one device).

Covered: ring_prefill == single-device flash_attention for every mask
family (both jnp and Pallas-interpret per-shard kernels), cp_decode ==
decode_ref on ragged cache_len including shard-empty shards, the wire
contract (per-hop ppermute of one KV shard / (O, Λ)-sized butterfly
messages, no score or cache gather, structured masks prune ring hops),
and the auto-routing through flash_attention / decode_attention / the
serving engine when the active ShardingCtx seq-shards the cache.
"""

import os
import subprocess
import sys
import textwrap

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Shared recursive jaxpr walker for the wire-contract assertions (handles
# both ClosedJaxpr params and the raw Jaxpr that shard_map carries).
_WALK_HELPER = """
def walk(jx, flat):
    for e in jx.eqns:
        flat.append(e)
        for p in e.params.values():
            for pi in (p if isinstance(p, (tuple, list)) else (p,)):
                if hasattr(pi, "jaxpr"):   # ClosedJaxpr
                    walk(pi.jaxpr, flat)
                elif hasattr(pi, "eqns"):  # raw Jaxpr (shard_map param)
                    walk(pi, flat)
    return flat
"""


def _run_in_subprocess(code: str):
    """Run `code` with 8 forced host devices; raise on failure."""
    prog = "import os\nos.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=8'\n" + _WALK_HELPER + textwrap.dedent(code)
    res = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": os.path.join(_REPO, "src"),
             "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "HOME": os.environ.get("HOME", "/root"), "JAX_PLATFORMS": "cpu"},
        cwd=_REPO,
    )
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return res.stdout


def test_ring_prefill_matches_single_device():
    _run_in_subprocess("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.core.attention import MaskSpec, flash_attention
    from repro.distributed.context import ring_prefill

    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    b, s, hq, hkv, d = 1, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    masks = [MaskSpec("causal"), MaskSpec("local", window=13),
             MaskSpec("chunked", chunk=8), MaskSpec("full")]
    for mask in masks:
        o_ref = flash_attention(q, k, v, mask=mask, impl="flashd",
                                block_q=16, block_k=16)
        for impl in ("flashd", "flashd_pallas"):
            o = ring_prefill(q, k, v, axis="data", mesh=mesh, mask=mask, impl=impl)
            assert o.dtype == q.dtype
            np.testing.assert_allclose(
                np.asarray(o), np.asarray(o_ref), rtol=1e-4, atol=1e-5,
                err_msg=f"{mask.kind}/{impl}",
            )
    print("ring_prefill OK")
    """)


def test_ring_prefill_wire_contract():
    """jaxpr-level roofline: each hop exchanges exactly one K and one V
    shard (ppermute), nothing else crosses the wire — no all_gather, no
    [S, S] score-sized collectives — and structured masks prune hops."""
    _run_in_subprocess("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.core.attention import MaskSpec
    from repro.distributed.context import ring_prefill
    from repro.kernels.tuning import choose_ring_schedule

    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(1)
    b, s, hq, hkv, d = 1, 64, 4, 2, 16
    n, s_sh = 8, 64 // 8
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)

    def collectives(mask):
        jaxpr = jax.make_jaxpr(lambda *a: ring_prefill(
            *a, axis="data", mesh=mesh, mask=mask, impl="flashd"))(q, k, v)
        return walk(jaxpr.jaxpr, [])  # walk: shared helper (test harness)

    for mask, want_hops in [
        (MaskSpec("causal"), 8),
        (MaskSpec("local", window=13), 3),   # hop 2 min distance 2·8−7=9 < 13 ⇒ 3 live hops
        (MaskSpec("chunked", chunk=8), 1),   # chunk == shard ⇒ diagonal only
    ]:
        sched = choose_ring_schedule(s_sh, s_sh, d, d, n_devices=n, mask=mask)
        assert sched.n_hops == want_hops, (mask.kind, sched)
        eqns = collectives(mask)
        perms = [e for e in eqns if e.primitive.name == "ppermute"]
        gathers = [e for e in eqns if "all_gather" in e.primitive.name
                   or "all_to_all" in e.primitive.name]
        assert not gathers, gathers
        # one K + one V rotation per hop after the first; every exchanged
        # buffer is exactly one KV shard — never the full sequence
        assert len(perms) == 2 * (want_hops - 1), (mask.kind, len(perms))
        for e in perms:
            shp = e.invars[0].aval.shape
            assert s_sh in shp and s not in shp, shp
    print("wire contract OK")
    """)


def test_cp_decode_matches_ref_ragged():
    _run_in_subprocess("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.distributed.context import cp_decode
    from repro.kernels.ref import decode_ref

    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(2)
    b, hq, hkv, S, d = 4, 8, 2, 64, 16
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(b, S, hkv, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, S, hkv, d)), jnp.float32)
    kck, vck = kc.transpose(0, 2, 1, 3), vc.transpose(0, 2, 1, 3)
    # ragged: full, shard-interior, GLOBALLY EMPTY, and mid — with 8 shards
    # of 8 the rows leave most shards empty (dead partials)
    cl = jnp.asarray([64, 5, 0, 23], jnp.int32)
    for w, c in [(0, 0), (12, 0), (0, 16)]:
        for use_kernel in (True, False):
            o = cp_decode(q, kc, vc, cl, axis="data", mesh=mesh,
                          window=w, chunk=c, use_kernel=use_kernel)
            o_ref = decode_ref(q, kck, vck, cl, window=w, chunk=c)
            np.testing.assert_allclose(
                np.asarray(o), np.asarray(o_ref), rtol=2e-5, atol=2e-5,
                err_msg=f"w={w} c={c} kernel={use_kernel}",
            )
    # butterfly wire: log2(8)=3 rounds x (o, lam) = 6 ppermutes of
    # (O, Λ)-sized messages; no cache-sized exchange
    jaxpr = jax.make_jaxpr(lambda *a: cp_decode(
        *a, axis="data", mesh=mesh, use_kernel=False))(q, kc, vc, cl)
    flat = walk(jaxpr.jaxpr, [])  # walk: shared helper (test harness)
    perms = [e for e in flat if e.primitive.name == "ppermute"]
    assert len(perms) == 6, len(perms)
    for e in perms:
        shp = e.invars[0].aval.shape
        # (O, Λ)-sized only: ≤ B·Hq·dv elements, never a seq-sized dim
        assert int(np.prod(shp)) <= b * hq * d and S not in shp, shp
    assert not any("all_gather" in e.primitive.name for e in flat)
    print("cp_decode OK")
    """)


def test_attention_api_cp_routing():
    """flash_attention / decode_attention select the context-parallel path
    exactly when the ShardingCtx kv_cache rule seq-shards the operands."""
    _run_in_subprocess("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.core.attention import MaskSpec, decode_attention, flash_attention
    from repro.distributed import sharding as shd
    from repro.kernels.ref import decode_ref

    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(3)
    b, s, hq, hkv, d = 1, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    o_ref = flash_attention(q, k, v, mask=MaskSpec("causal"), impl="flashd")

    ctx = shd.ShardingCtx(mesh, cp_prefill=True)
    with shd.activate(ctx), jax.set_mesh(mesh):
        assert shd.cp_axis_for_cache(k.shape) == "data"
        o = flash_attention(q, k, v, mask=MaskSpec("causal"), impl="flashd")
        jx = str(jax.make_jaxpr(lambda *a: flash_attention(
            *a, mask=MaskSpec("causal"), impl="flashd"))(q, k, v))
    assert "ppermute" in jx and "all_gather" not in jx
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-5)
    # cp_prefill defaults OFF: same ctx without the flag keeps GSPMD path
    with shd.activate(shd.ShardingCtx(mesh)), jax.set_mesh(mesh):
        jx_off = str(jax.make_jaxpr(lambda *a: flash_attention(
            *a, mask=MaskSpec("causal"), impl="flashd"))(q, k, v))
    assert "ppermute" not in jx_off

    # decode: B=2 doesn't divide data=8 ⇒ the kv_cache rule context-
    # parallels the sequence ⇒ decode_attention routes to cp_decode
    b2 = 2
    qd = jnp.asarray(rng.normal(size=(b2, 1, hq, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(b2, s, hkv, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b2, s, hkv, d)), jnp.float32)
    cl = jnp.asarray([40, 0], jnp.int32)
    o_ref = decode_ref(qd[:, 0], kc.transpose(0, 2, 1, 3),
                       vc.transpose(0, 2, 1, 3), cl)
    with shd.activate(shd.ShardingCtx(mesh)), jax.set_mesh(mesh):
        o = decode_attention(qd, kc, vc, cl)
        jx = str(jax.make_jaxpr(lambda *a: decode_attention(*a))(qd, kc, vc, cl))
    assert "ppermute" in jx
    np.testing.assert_allclose(np.asarray(o[:, 0]), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    print("routing OK")
    """)


def test_cp_decode_batch_and_seq_sharded_mesh():
    """Heads-not-divisible CP on a (data=2, model=4) mesh: the kv_cache
    rule shards batch over 'data' AND seq over 'model'; the cp shard_map
    must keep the batch sharding (specs carry cp_batch_axes_for_cache)
    and still match the reference."""
    _run_in_subprocess("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.core.attention import decode_attention
    from repro.distributed import sharding as shd
    from repro.kernels.ref import decode_ref

    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(5)
    b, hq, hkv, S, d = 2, 6, 2, 64, 16  # hkv=2 % model=4 != 0 ⇒ seq CP
    q = jnp.asarray(rng.normal(size=(b, 1, hq, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(b, S, hkv, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, S, hkv, d)), jnp.float32)
    cl = jnp.asarray([64, 11], jnp.int32)
    o_ref = decode_ref(q[:, 0], kc.transpose(0, 2, 1, 3),
                       vc.transpose(0, 2, 1, 3), cl)
    with shd.activate(shd.ShardingCtx(mesh)), jax.set_mesh(mesh):
        assert shd.cp_axis_for_cache(kc.shape) == "model"
        assert shd.cp_batch_axes_for_cache(kc.shape) == ("data",)
        o = decode_attention(q, kc, vc, cl)
    np.testing.assert_allclose(np.asarray(o[:, 0]), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    print("batch+seq CP OK")
    """)


def test_paged_decode_routes_through_cp_when_seq_sharded():
    """Paged caches must keep the context-parallel interplay: when the
    active ShardingCtx seq-shards the (gathered) cache, `_paged_attn_step`
    gathers its pages and merges per-shard partials through cp_decode —
    the jaxpr carries ppermutes — and still matches the unsharded paged
    decode step."""
    _run_in_subprocess("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.configs import paper_llama
    from repro.distributed import sharding as shd
    from repro.models import get_model
    from repro.models.transformer import init_decode_cache, prefill_lm

    cfg = dataclasses.replace(
        paper_llama.CONFIG, n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
        d_ff=64, head_dim=8, vocab_size=64, vocab_pad_multiple=32,
    )
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    b, max_len, page = 2, 64, 8
    cache = init_decode_cache(b, max_len, cfg, layout="paged", page_size=page)
    # distinct physical pages per row, every layer mirrors the same table
    tbl = jnp.asarray([np.arange(1, 9), np.arange(9, 17)], jnp.int32)
    cache = jax.tree_util.tree_map_with_path(
        lambda p, x: x.at[:].set(tbl[None]) if any(
            getattr(e, "key", None) == "tbl" for e in p) else x,
        cache,
    )
    prompts = np.random.default_rng(4).integers(0, 64, (b, 6)).astype(np.int32)
    logits_ref, cache_ref = prefill_lm(
        params, jnp.asarray(prompts, jnp.int32), cache, cfg)

    mesh = make_mesh((8,), ("data",))
    with shd.activate(shd.ShardingCtx(mesh)), jax.set_mesh(mesh):
        # gathered paged cache is [B=2, 64, 2, 8]: B < data ⇒ seq CP
        assert shd.cp_axis_for_cache((b, max_len, 2, 8)) == "data"
        logits_cp, _ = prefill_lm(
            params, jnp.asarray(prompts, jnp.int32), cache, cfg)
        tok = jnp.asarray(prompts[:, 0])
        pos = jnp.zeros((b,), jnp.int32)
        jx = str(jax.make_jaxpr(lambda p, c, t, z: api.decode_step(
            p, c, t, z, cfg))(params, cache, tok, pos))
    assert "ppermute" in jx  # paged decode merged cross-device, no gather-all
    np.testing.assert_allclose(np.asarray(logits_cp), np.asarray(logits_ref),
                               rtol=1e-4, atol=1e-4)
    print("paged cp OK")
    """)


def test_engine_decode_on_cp_mesh_matches_unsharded():
    """End-to-end: Engine.generate with a sharding ctx whose kv_cache rule
    seq-shards the cache (B < data axis) emits the same tokens as the
    single-device engine — greedy decode is merge-order robust."""
    _run_in_subprocess("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.configs import paper_llama
    from repro.distributed import sharding as shd
    from repro.models import get_model
    from repro.serve import Engine, ServeConfig

    cfg = dataclasses.replace(
        paper_llama.CONFIG, n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
        d_ff=64, head_dim=8, vocab_size=64, vocab_pad_multiple=32,
    )
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    prompts = np.random.default_rng(4).integers(0, 64, (2, 6)).astype(np.int32)
    sc = ServeConfig(max_len=64, temperature=0.0)

    toks_ref = Engine(params, cfg, sc).generate(prompts, 8)

    mesh = make_mesh((8,), ("data",))
    ctx = shd.ShardingCtx(mesh)  # B=2 < 8 ⇒ seq-sharded caches ⇒ cp_decode
    eng = Engine(params, cfg, sc, sharding_ctx=ctx)
    toks = eng.generate(prompts, 8)
    np.testing.assert_array_equal(toks, toks_ref)
    assert eng.host_syncs == 1  # the one-sync contract survives sharding
    print("engine cp OK")
    """)
