"""Decoder LM composer: pattern-of-(mixer, ffn) blocks scanned with remat.

One model class covers every assigned non-encdec architecture — dense GQA
(deepseek/qwen2/yi/qwen3/phi3v), MoE (qwen3-moe, llama4), SSM (mamba2),
hybrid (recurrentgemma) — by composing the mixer/ffn sublayers declared in
`ModelConfig.pattern`. Layers are stacked per pattern position and scanned
(`lax.scan`) over blocks; heterogeneous stacks stay compile-compact.

Attention runs through `repro.core.flash_attention` (FLASH-D by default) for
training/prefill and `repro.core.decode_attention` (FLASH-D split-K merge)
for serving. Sharding constraints are logical (`repro.distributed.sharding`)
and inert outside a mesh context.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.attention import (
    MaskSpec,
    decode_attention,
    decode_attention_paged,
    flash_attention,
    gather_pages,
    varlen_attention,
)
from repro.distributed.sharding import shard
from repro.models import mamba2 as m2
from repro.models import moe as moe_mod
from repro.models import rglru as rg
from repro.models.config import ModelConfig
from repro.models.layers import (
    dense_init,
    embed_lookup,
    logits_from_hidden,
    rms_norm,
    apply_rope,
)

__all__ = [
    "init_lm",
    "apply_lm",
    "lm_loss",
    "init_decode_cache",
    "decode_step_lm",
    "prefill_lm",
    "forward_packed",
    "packed_mixers_ok",
]

_AUX_KEYS = ("moe_aux_loss", "moe_z_loss", "moe_dropped")


# ---------------------------------------------------------------------------
# sublayer init
# ---------------------------------------------------------------------------

def _init_attn(key, cfg: ModelConfig) -> dict:
    d, hd = cfg.d_model, cfg.head_dim_
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    dt = cfg.master_dtype
    p = {
        "wq": dense_init(ks[0], (d, hq * hd), dtype=dt),
        "wk": dense_init(ks[1], (d, hkv * hd), dtype=dt),
        "wv": dense_init(ks[2], (d, hkv * hd), dtype=dt),
        "wo": dense_init(ks[3], (hq * hd, d), dtype=dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), dt)
        p["bk"] = jnp.zeros((hkv * hd,), dt)
        p["bv"] = jnp.zeros((hkv * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dt)
        p["k_norm"] = jnp.zeros((hd,), dt)
    return p


def _init_swiglu(key, cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = cfg.master_dtype
    return {
        "wg": dense_init(ks[0], (d, f), dtype=dt),
        "wu": dense_init(ks[1], (d, f), dtype=dt),
        "wd": dense_init(ks[2], (f, d), dtype=dt),
    }


_MIXER_INIT = {
    "attn": _init_attn,
    "attn_bidir": _init_attn,
    "attn_local": _init_attn,
    "attn_chunked": _init_attn,
    "attn_nope": _init_attn,
    "ssm": m2.init_mamba2,
    "rglru": rg.init_rglru,
}
_FFN_INIT = {"swiglu": _init_swiglu, "moe": moe_mod.init_moe}


def _init_block(key, cfg: ModelConfig, spec) -> dict:
    mixer, ffn = spec
    dt = cfg.master_dtype
    p: dict = {"norm1": jnp.zeros((cfg.d_model,), dt)}
    p["mixer"] = _MIXER_INIT[mixer](jax.random.fold_in(key, 1), cfg)
    if ffn != "none":
        p["norm2"] = jnp.zeros((cfg.d_model,), dt)
        p["ffn"] = _FFN_INIT[ffn](jax.random.fold_in(key, 2), cfg)
    return p


def init_lm(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 6)
    dt = cfg.master_dtype
    params: dict = {
        "embed": dense_init(ks[0], (cfg.padded_vocab, cfg.d_model), scale=0.02, dtype=dt),
        "final_norm": jnp.zeros((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[1], (cfg.d_model, cfg.padded_vocab), dtype=dt)
    if cfg.frontend == "vision":
        params["patch_proj"] = dense_init(ks[2], (cfg.d_model, cfg.d_model), dtype=dt)

    def stack_blocks(base_key, n, pattern):
        per_block = []
        for i in range(n):
            bk = jax.random.fold_in(base_key, i)
            per_block.append(
                {f"pos{j}": _init_block(jax.random.fold_in(bk, j), cfg, spec)
                 for j, spec in enumerate(pattern)}
            )
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per_block)

    if cfg.n_blocks > 0:
        params["blocks"] = stack_blocks(ks[3], cfg.n_blocks, cfg.pattern)
    if cfg.remainder:
        params["rem_blocks"] = stack_blocks(ks[4], 1, cfg.remainder)
    return params


# ---------------------------------------------------------------------------
# sublayer apply (full sequence)
# ---------------------------------------------------------------------------

def _attn_mask(cfg: ModelConfig, kind: str) -> MaskSpec:
    if kind == "attn_bidir":
        return MaskSpec("full")
    if kind == "attn_local":
        return MaskSpec("local", window=cfg.attn_window)
    if kind == "attn_chunked":
        return MaskSpec("chunked", chunk=cfg.attn_chunk)
    return MaskSpec("causal")


def _qkv(p, x, cfg, kind, positions, kv_x=None):
    cdt = cfg.compute_dtype
    hd = cfg.head_dim_
    b, s, _ = x.shape
    src = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dh->bsh", src, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dh->bsh", src, p["wv"].astype(cdt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, src.shape[1], cfg.n_kv_heads, hd)
    v = v.reshape(b, src.shape[1], cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if kind not in ("attn_nope", "cross"):
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions if kv_x is None else jnp.arange(src.shape[1]), cfg.rope_theta)
    return q, k, v


def _apply_attn(p, x, cfg: ModelConfig, kind: str, positions, kv_x=None):
    q, k, v = _qkv(p, x, cfg, kind, positions, kv_x)
    q, k, v = shard(q, "heads"), shard(k, "heads"), shard(v, "heads")
    mask = MaskSpec("full") if kind == "cross" else _attn_mask(cfg, kind)
    o = flash_attention(
        q, k, v,
        mask=mask,
        impl=cfg.attn_impl,
        block_q=cfg.attn_block_q,
        block_k=cfg.attn_block_k,
        skip=cfg.attn_skip,
    )
    o = shard(o, "heads")
    b, s = x.shape[:2]
    o = o.reshape(b, s, cfg.n_heads * cfg.head_dim_)
    return jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(cfg.compute_dtype))


def _apply_swiglu(p, x, cfg: ModelConfig):
    cdt = cfg.compute_dtype
    g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(cdt))
    u = jnp.einsum("bsd,df->bsf", x, p["wu"].astype(cdt))
    h = shard(jax.nn.silu(g) * u, "ff")
    return jnp.einsum("bsf,fd->bsd", h, p["wd"].astype(cdt))


def _apply_block(bp: dict, h, cfg: ModelConfig, spec, positions, kv_x=None):
    """One (mixer, ffn) block with pre-norms and residuals. Returns (h, aux)."""
    mixer, ffn = spec
    aux = {k: jnp.float32(0.0) for k in _AUX_KEYS}
    x = rms_norm(h, bp["norm1"], cfg.norm_eps)
    if mixer.startswith("attn") or mixer == "cross":
        y = _apply_attn(bp["mixer"], x, cfg, mixer, positions, kv_x)
    elif mixer == "ssm":
        y = m2.apply_mamba2(bp["mixer"], x, cfg)
    elif mixer == "rglru":
        y = rg.apply_rglru(bp["mixer"], x, cfg)
    else:
        raise ValueError(mixer)
    y = _shard_out(y)
    h = shard(h + y, "residual")
    if ffn != "none":
        x = rms_norm(h, bp["norm2"], cfg.norm_eps)
        if ffn == "swiglu":
            y = _apply_swiglu(bp["ffn"], x, cfg)
        else:
            y, aux = moe_mod.apply_moe(bp["ffn"], x, cfg)
            aux = {**{k: jnp.float32(0.0) for k in _AUX_KEYS}, **aux}
        y = _shard_out(y)
        h = shard(h + y, "residual")
    return h, aux


def _shard_out(y):
    """Reduce-scatter placement: constraining the row-parallel output to the
    seq-sharded residual spec makes GSPMD lower its partial-sum psum as
    reduce-scatter (wire = size) instead of all-reduce (wire = 2·size)."""
    from repro.distributed.sharding import active_ctx

    ctx = active_ctx()
    if ctx is not None and getattr(ctx, "rs_outputs", False):
        return shard(y, "residual")
    return y


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)


def _scan_pattern(params_key, params, h, cfg, pattern, positions, kv_x=None):
    """Scan stacked blocks of a repeating pattern. Returns (h, aux_sums)."""

    def body(carry, block_params):
        h, aux_acc = carry
        for j, spec in enumerate(pattern):
            h, aux = _apply_block(block_params[f"pos{j}"], h, cfg, spec, positions, kv_x)
            aux_acc = {k: aux_acc[k] + aux[k] for k in _AUX_KEYS}
        return (h, aux_acc), None

    body = _remat(body, cfg)
    init_aux = {k: jnp.float32(0.0) for k in _AUX_KEYS}
    stacked = params[params_key]
    if not cfg.scan_layers:
        carry = (h, init_aux)
        nb = jax.tree.leaves(stacked)[0].shape[0]
        for i in range(nb):
            bp = jax.tree.map(lambda x: x[i], stacked)
            carry, _ = body(carry, bp)
        return carry
    (h, aux), _ = jax.lax.scan(body, (h, init_aux), stacked)
    return h, aux


def _embed_inputs(params, batch: Dict, cfg: ModelConfig):
    """Token (+ modality-stub) embedding. Returns (h, positions)."""
    tokens = batch["tokens"]
    h = embed_lookup(params["embed"], tokens, cfg.compute_dtype)
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        patches = jnp.einsum(
            "bnd,de->bne",
            batch["patch_embeds"].astype(cfg.compute_dtype),
            params["patch_proj"].astype(cfg.compute_dtype),
        )
        h = jnp.concatenate([patches, h], axis=1)
    positions = jnp.arange(h.shape[1])
    return h, positions


def apply_lm(params: dict, batch: Dict, cfg: ModelConfig, *, last_only: bool = False):
    """Forward pass → (logits [B, S_total, Vpad] f32, aux dict).

    last_only=True returns logits for the final position only — the prefill
    serving path (next-token sampling) that avoids materializing [B, S, V].
    """
    h, positions = _embed_inputs(params, batch, cfg)
    h = shard(h, "residual")
    aux = {k: jnp.float32(0.0) for k in _AUX_KEYS}
    if cfg.n_blocks > 0:
        h, aux1 = _scan_pattern("blocks", params, h, cfg, cfg.pattern, positions)
        aux = {k: aux[k] + aux1[k] for k in _AUX_KEYS}
    if cfg.remainder:
        h, aux2 = _scan_pattern("rem_blocks", params, h, cfg, cfg.remainder, positions)
        aux = {k: aux[k] + aux2[k] for k in _AUX_KEYS}
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    if last_only:
        h = h[:, -1:]
    head = params["lm_head"] if not cfg.tie_embeddings else params["embed"].T
    logits = logits_from_hidden(h, head, cfg.vocab_size)
    return shard(logits, "logits"), aux


def lm_loss(params: dict, batch: Dict, cfg: ModelConfig):
    """Causal-LM cross entropy (+ MoE aux). labels == -1 are masked."""
    logits, aux = apply_lm(params, batch, cfg)
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:  # modality prefix (vision stub)
        logits = logits[:, logits.shape[1] - labels.shape[1]:]
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    ce = jnp.sum((lse - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    loss = ce + aux["moe_aux_loss"] + aux["moe_z_loss"]
    metrics = {"ce": ce, **aux}
    return loss, metrics


# ---------------------------------------------------------------------------
# serving: per-layer caches + one-token decode
# ---------------------------------------------------------------------------

def paged_mixers(cfg: ModelConfig) -> Tuple[str, ...]:
    """Mixer kinds that take the paged layout: full-length (global)
    attention caches only. Local/chunked layers keep their window-sized
    ring regions — those are already compact (a ring IS the dense packing
    of what the layer can see), so paging them buys nothing and would
    complicate the ring write index. SSM/RG-LRU state is O(1)/slot."""
    return tuple(
        m for m, _ in (*cfg.pattern, *cfg.remainder)
        if m.startswith("attn") and m not in ("attn_local", "attn_chunked")
    )


def _layer_cache(spec, batch: int, max_len: int, cfg: ModelConfig,
                 *, paged_geom=None, kv_spec=None):
    mixer, _ = spec
    hd = cfg.head_dim_
    if mixer.startswith("attn"):
        if paged_geom is not None and mixer not in ("attn_local", "attn_chunked"):
            n_pages, page_size, pages_per_seq = paged_geom
            pshape = (n_pages, page_size, cfg.n_kv_heads, hd)
            pool_dtype = cfg.compute_dtype if kv_spec is None else kv_spec.dtype
            cache = {
                "k_pages": jnp.zeros(pshape, pool_dtype),
                "v_pages": jnp.zeros(pshape, pool_dtype),
                # all rows start on the garbage page (id 0) — a dead slot's
                # lockstep writes land there until the engine installs a table
                "tbl": jnp.zeros((batch, pages_per_seq), jnp.int32),
            }
            if kv_spec is not None:
                # per-(page, head) scale side-band (DESIGN.md §3.8); 1.0 on
                # never-written pages keeps every entry finite and positive
                sshape = (n_pages, cfg.n_kv_heads)
                cache["k_scale"] = jnp.ones(sshape, jnp.float32)
                cache["v_scale"] = jnp.ones(sshape, jnp.float32)
            return cache
        shape = (batch, max_len, cfg.n_kv_heads, hd)
        return {
            "k": jnp.zeros(shape, cfg.compute_dtype),
            "v": jnp.zeros(shape, cfg.compute_dtype),
        }
    if mixer == "ssm":
        return m2.init_mamba2_cache(batch, cfg, cfg.compute_dtype)
    if mixer == "rglru":
        return rg.init_rglru_cache(batch, cfg, cfg.compute_dtype)
    raise ValueError(mixer)


def init_decode_cache(
    batch: int,
    max_len: int,
    cfg: ModelConfig,
    *,
    layout: str = "contiguous",
    page_size: Optional[int] = None,
    n_pages: Optional[int] = None,
    kv_dtype: str = "",
) -> dict:
    """Stacked per-block caches matching the params tree structure.

    Local/chunked attention layers allocate only a window-sized ring region
    (window or chunk length), which is what makes long_500k serveable for
    recurrentgemma/llama4 (DESIGN.md §5).

    layout="paged" (DESIGN.md §3.4) replaces each *global* attention
    layer's per-slot [batch, max_len, ...] region with a page pool
    [n_pages, page_size, ...] plus a per-slot block table [batch, N]
    (N = ⌈max_len / page_size⌉). Every layer shares one logical table (the
    engine mirrors the allocator's tables into each layer's `tbl` leaf);
    ring-region and recurrent layers keep their contiguous layout. With no
    geometry given, `repro.kernels.tuning.choose_page_layout` sizes the
    pool at `batch · max_len` tokens — the contiguous footprint — so the
    default is never worse; engines shrink it to oversubscribe.

    kv_dtype ∈ runtime.quant.available() stores each paged pool in that
    quantized format with per-(page, head) f32 scale leaves (`k_scale` /
    `v_scale`, DESIGN.md §3.8) beside the pages; "" keeps the compute
    dtype. Only paged global-attention pools quantize — ring regions and
    recurrent state stay native."""
    from repro.runtime import quant  # lazy: no cycle

    kv_spec = quant.get_spec(kv_dtype)
    if kv_spec is not None and layout != "paged":
        raise ValueError("kv_dtype quantization requires layout='paged'")
    paged_geom = None
    if layout == "paged" and paged_mixers(cfg):
        from repro.kernels.tuning import choose_page_layout  # lazy: no cycle

        pl_ = choose_page_layout(
            max_len, cfg.head_dim_, cfg.head_dim_,
            group=cfg.n_heads // cfg.n_kv_heads,
            pool_tokens=(n_pages - 1) * page_size if (n_pages and page_size)
            else batch * max_len,
            page_size=page_size,
            kv_itemsize=quant.kv_itemsize(kv_dtype),
        )
        paged_geom = (pl_.n_pages, pl_.page_size, pl_.pages_per_seq)
    elif layout not in ("contiguous", "paged"):
        raise ValueError(f"unknown cache layout {layout!r}")

    def cache_len_for(spec):
        mixer, _ = spec
        if mixer == "attn_local" and cfg.attn_window:
            return min(max_len, cfg.attn_window)
        if mixer == "attn_chunked" and cfg.attn_chunk:
            return min(max_len, cfg.attn_chunk)
        return max_len

    cache: dict = {}
    if cfg.n_blocks > 0:
        per = {
            f"pos{j}": _layer_cache(
                spec, batch, cache_len_for(spec), cfg,
                paged_geom=paged_geom, kv_spec=kv_spec,
            )
            for j, spec in enumerate(cfg.pattern)
        }
        cache["blocks"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_blocks,) + x.shape), per
        )
    if cfg.remainder:
        per = {
            f"pos{j}": _layer_cache(
                spec, batch, cache_len_for(spec), cfg,
                paged_geom=paged_geom, kv_spec=kv_spec,
            )
            for j, spec in enumerate(cfg.remainder)
        }
        cache["rem_blocks"] = jax.tree.map(lambda x: x[None], per)
    return cache


def _decode_attn(p, x, cfg: ModelConfig, kind: str, cache, pos, layer):
    """One-token attention against the cache. pos: [B] absolute position;
    layer: this layer's index into a paged cache's stacked pool."""
    b = x.shape[0]
    hd = cfg.head_dim_
    cdt = cfg.compute_dtype
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"].astype(cdt))
    if cfg.qkv_bias:
        q, k, v = q + p["bq"].astype(cdt), k + p["bk"].astype(cdt), v + p["bv"].astype(cdt)
    q = q.reshape(b, 1, cfg.n_heads, hd)
    k = k.reshape(b, 1, cfg.n_kv_heads, hd)
    v = v.reshape(b, 1, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if kind != "attn_nope":
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)

    if "tbl" in cache:  # paged layout (DESIGN.md §3.4) — global attn only
        return _paged_attn_step(p, q, k, v, cfg, cache, pos, layer)

    max_len = cache["k"].shape[1]
    write_idx = pos % max_len  # ring buffer (exact for local/chunked windows)
    bidx = jnp.arange(b)
    k_cache = cache["k"].at[bidx, write_idx].set(k[:, 0])
    v_cache = cache["v"].at[bidx, write_idx].set(v[:, 0])
    k_cache = shard(k_cache, "kv_cache")
    v_cache = shard(v_cache, "kv_cache")

    # Ring-buffer semantics: local caches hold exactly the last `window`
    # positions (all slots valid once full); chunked caches map position
    # p → slot p % chunk, so valid slots are 0..p%chunk — no extra masks.
    if kind == "attn_local":
        eff_len = jnp.minimum(pos + 1, max_len)
    elif kind == "attn_chunked":
        eff_len = write_idx + 1
    else:
        eff_len = pos + 1
    from repro.distributed.context import maybe_cp_decode

    # seq-sharded cache (context parallel): per-shard decode partials
    # merged across devices with the FLASH-D blend — no cache gather
    o = maybe_cp_decode(
        q, k_cache, v_cache, eff_len,
        use_kernel=cfg.attn_impl.endswith("_pallas"),
    )
    if o is None:
        if cfg.attn_impl.endswith("_pallas"):
            # fused split-K decode kernel: in-VMEM sigmoid merge, no HBM
            # partials
            from repro.kernels import ops as kernel_ops  # lazy: no cycle

            o = kernel_ops.pallas_decode(q, k_cache, v_cache, eff_len)
        else:
            o = decode_attention(q, k_cache, v_cache, eff_len)
    o = o.reshape(b, 1, cfg.n_heads * hd)
    y = jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(cdt))
    return y, {"k": k_cache, "v": v_cache}


def _paged_attn_step(p, q, k, v, cfg: ModelConfig, cache, pos, layer):
    """One-token attention against a paged cache: scatter the new K/V into
    the position's physical page via the block table, then attend through
    the table. Writes past the table (dead slots whose `pos` keeps
    advancing in the lockstep batch, or rows the engine retired by zeroing
    their table row) land on the garbage page 0 — the engine's convention
    for harmless speculative writes (DESIGN.md §3.4).

    The pool leaves arrive stacked over layers ([L, P, page, Hkv, d],
    scales [L, P, Hkv]); `layer` picks this layer's pages for the writes
    and the attention, so the stack is updated in place (`_POOL_LEAVES`).

    Quantized pools (`k_scale`/`v_scale` leaves, DESIGN.md §3.8) quantize
    at write time: a slot-0 write fixes its page's per-head scale from
    that row alone (never revised — the write-order determinism the radix
    cache's content-addressed page sharing relies on), every other write
    reuses the page's existing scale."""
    b = q.shape[0]
    k_pages, v_pages, tbl = cache["k_pages"], cache["v_pages"], cache["tbl"]
    k_scale, v_scale = cache.get("k_scale"), cache.get("v_scale")
    page = k_pages.shape[2]
    n_tbl = tbl.shape[1]
    bidx = jnp.arange(b)
    page_idx = pos // page
    slot = pos % page
    in_tbl = page_idx < n_tbl
    pid = jnp.where(in_tbl, tbl[bidx, jnp.minimum(page_idx, n_tbl - 1)], 0)
    k_new, v_new = k[:, 0], v[:, 0]
    if k_scale is not None:
        from repro.runtime import quant  # lazy: no cycle

        spec = quant.spec_for_dtype(k_pages.dtype)
        is_slot0 = slot == 0
        # masked scatter: non-slot0 rows are routed to the garbage page so
        # a row sharing its page with a slot-0 writer can't scatter a stale
        # scale over the fresh one
        spid = jnp.where(is_slot0, pid, 0)
        k_scale = k_scale.at[layer, spid].set(jnp.where(
            is_slot0[:, None], quant.slot0_scale(k_new, spec), k_scale[layer, 0]
        ))
        v_scale = v_scale.at[layer, spid].set(jnp.where(
            is_slot0[:, None], quant.slot0_scale(v_new, spec), v_scale[layer, 0]
        ))
        k_new = quant.quantize_rows(k_new, k_scale[layer, pid], spec)
        v_new = quant.quantize_rows(v_new, v_scale[layer, pid], spec)
    k_pages = k_pages.at[layer, pid, slot].set(k_new)
    v_pages = v_pages.at[layer, pid, slot].set(v_new)
    eff_len = pos + 1

    use_kernel = cfg.attn_impl.endswith("_pallas")
    o = None
    from repro.distributed.context import maybe_cp_decode
    from repro.distributed.sharding import active_ctx

    if active_ctx() is not None:
        # sharding interplay: a seq-sharded (gathered) cache still merges
        # per-shard partials cross-device — paged pools replicate, the
        # gather materializes the [B, S, H, hd] shape the rules engine and
        # cp_decode reason about. Traced only under an active ctx; DCE'd
        # (returns None at trace time) when the rule doesn't seq-shard.
        o = maybe_cp_decode(
            q,
            gather_pages(k_pages, tbl, scales=k_scale, layer=layer),
            gather_pages(v_pages, tbl, scales=v_scale, layer=layer),
            eff_len, use_kernel=use_kernel,
        )
    if o is None:
        if use_kernel:
            from repro.kernels import ops as kernel_ops  # lazy: no cycle

            o = kernel_ops.pallas_decode_paged(
                q, k_pages, v_pages, tbl, eff_len,
                k_scale=k_scale, v_scale=v_scale, layer=layer,
            )
        else:
            o = decode_attention_paged(
                q, k_pages, v_pages, tbl, eff_len,
                k_scale=k_scale, v_scale=v_scale, layer=layer,
            )
    o = o.reshape(b, 1, cfg.n_heads * cfg.head_dim_)
    y = jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(cfg.compute_dtype))
    new_cache = {"k_pages": k_pages, "v_pages": v_pages, "tbl": tbl}
    if k_scale is not None:
        new_cache["k_scale"], new_cache["v_scale"] = k_scale, v_scale
    return y, new_cache


def packed_mixers_ok(cfg: ModelConfig) -> bool:
    """Can this stack run the packed varlen mixed step (DESIGN.md §3.5)?

    The packed step feeds every layer flat tokens from MANY sequences in
    one dispatch, so each mixer must read/write per-sequence state through
    the paged cache alone: global causal attention ('attn', 'attn_nope').
    Ring-region (local/chunked) and recurrent (SSM/RG-LRU) layers carry
    sequential state a packed step cannot replay row-by-row; bidirectional
    layers would need future keys a chunked prefill has not seen. Engines
    fall back to the sequential paths for those stacks."""
    return all(
        m in ("attn", "attn_nope")
        for m, _ in (*cfg.pattern, *cfg.remainder)
    )


def _packed_attn(p, x, cfg: ModelConfig, kind: str, cache, positions, seq_ids,
                 kv_len, block_q, layer):
    """Packed varlen attention for one layer: scatter the pack's new K/V
    into each row's physical page through the block table, then attend the
    pack through `varlen_attention` (the fused Pallas kernel under
    `*_pallas` impls, the jnp mirror otherwise). x [1, T, D]; positions /
    seq_ids [T] (−1 = padding row → writes land on the garbage page and
    the row returns zeros); kv_len [B] per-sequence visible KV length.
    The pool leaves are stacked over layers and `layer` picks this one's
    pages, as in `_paged_attn_step`."""
    t = x.shape[1]
    hd = cfg.head_dim_
    cdt = cfg.compute_dtype
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"].astype(cdt))
    if cfg.qkv_bias:
        q, k, v = q + p["bq"].astype(cdt), k + p["bk"].astype(cdt), v + p["bv"].astype(cdt)
    q = q.reshape(1, t, cfg.n_heads, hd)
    k = k.reshape(1, t, cfg.n_kv_heads, hd)
    v = v.reshape(1, t, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if kind != "attn_nope":
        q = apply_rope(q, positions[None], cfg.rope_theta)
        k = apply_rope(k, positions[None], cfg.rope_theta)

    k_pages, v_pages, tbl = cache["k_pages"], cache["v_pages"], cache["tbl"]
    k_scale, v_scale = cache.get("k_scale"), cache.get("v_scale")
    page = k_pages.shape[2]
    n_tbl = tbl.shape[1]
    sid = jnp.maximum(seq_ids, 0)
    page_idx = positions // page
    in_tbl = (seq_ids >= 0) & (positions >= 0) & (page_idx < n_tbl)
    pid = jnp.where(in_tbl, tbl[sid, jnp.clip(page_idx, 0, n_tbl - 1)], 0)
    slot = jnp.where(positions >= 0, positions % page, 0)
    k_new, v_new = k[0], v[0]
    if k_scale is not None:  # quantized pool: same slot-0 rule as the
        from repro.runtime import quant  # sequential step (DESIGN.md §3.8)

        spec = quant.spec_for_dtype(k_pages.dtype)
        is_slot0 = (slot == 0) & in_tbl
        # scale updates scatter FIRST (non-slot0 rows routed to the garbage
        # page), then every row quantizes with its page's updated scale —
        # a pack writing slot 0 and slots 1..n of one page in the same
        # dispatch sees exactly the sequential write order's values
        spid = jnp.where(is_slot0, pid, 0)
        k_scale = k_scale.at[layer, spid].set(jnp.where(
            is_slot0[:, None], quant.slot0_scale(k_new, spec), k_scale[layer, 0]
        ))
        v_scale = v_scale.at[layer, spid].set(jnp.where(
            is_slot0[:, None], quant.slot0_scale(v_new, spec), v_scale[layer, 0]
        ))
        k_new = quant.quantize_rows(k_new, k_scale[layer, pid], spec)
        v_new = quant.quantize_rows(v_new, v_scale[layer, pid], spec)
    k_pages = k_pages.at[layer, pid, slot].set(k_new)
    v_pages = v_pages.at[layer, pid, slot].set(v_new)

    o = varlen_attention(
        q[0], k_pages, v_pages, tbl, seq_ids, positions, kv_len,
        impl=cfg.attn_impl, block_q=block_q,
        k_scale=k_scale, v_scale=v_scale, layer=layer,
    )
    o = o.reshape(1, t, cfg.n_heads * hd)
    y = jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(cdt))
    new_cache = {"k_pages": k_pages, "v_pages": v_pages, "tbl": tbl}
    if k_scale is not None:
        new_cache["k_scale"], new_cache["v_scale"] = k_scale, v_scale
    return y, new_cache


def forward_packed(
    params: dict,
    tokens: jax.Array,  # [T] packed flat tokens (many sequences)
    seq_ids: jax.Array,  # [T] owning batch row / table row (−1 = padding)
    positions: jax.Array,  # [T] absolute position in the row's sequence
    kv_len: jax.Array,  # [B] per-sequence KV length AFTER this pack
    cache: dict,  # paged decode cache (init_decode_cache(layout="paged"))
    cfg: ModelConfig,
    last_rows: jax.Array,  # [B] or [B, R] pack rows to read logits at (<0: none)
    block_q: Optional[int] = None,  # pack alignment granularity (the packer's)
):
    """One packed varlen step over the whole stack (DESIGN.md §3.5).

    The serving engine's mixed prefill/decode dispatch: prefill chunks and
    single decode tokens ride in one flat [T] batch; every layer writes
    the pack's new K/V straight into the sequences' pages and attends
    through `varlen_attention` — there is no prefill-vs-decode fork
    anywhere in the stack. Returns (logits at `last_rows` — [B, Vpad] for
    1-D rows, [B, R, Vpad] for 2-D rows (speculative verify reads logits
    at every draft row of a segment, DESIGN.md §3.9) — garbage where
    rows < 0 — and the updated cache). Requires `packed_mixers_ok(cfg)`
    (global paged attention only).

    `block_q` MUST be the granularity the caller aligned segments to (the
    Pallas kernel derives per-block sequence ids from it); None falls back
    to cfg.attn_block_q for jnp impls, where alignment is irrelevant."""
    if not packed_mixers_ok(cfg):
        raise ValueError(
            f"{cfg.name}: packed step needs a pure global-attention stack "
            f"(got {[m for m, _ in (*cfg.pattern, *cfg.remainder)]})"
        )
    seq_ids = jnp.asarray(seq_ids, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    h = embed_lookup(params["embed"], tokens[None], cfg.compute_dtype)  # [1, T, D]

    def block_step(bp, bc, h, pattern, layer):
        new_bc = {}
        for j, spec in enumerate(pattern):
            mixer, ffn = spec
            bpj, bcj = bp[f"pos{j}"], bc[f"pos{j}"]
            x = rms_norm(h, bpj["norm1"], cfg.norm_eps)
            y, nc = _packed_attn(
                bpj["mixer"], x, cfg, mixer, bcj, positions, seq_ids, kv_len,
                block_q if block_q is not None else cfg.attn_block_q, layer,
            )
            h = h + y
            if ffn != "none":
                x = rms_norm(h, bpj["norm2"], cfg.norm_eps)
                if ffn == "swiglu":
                    y = _apply_swiglu(bpj["ffn"], x, cfg)
                else:
                    y, _ = moe_mod.apply_moe(bpj["ffn"], x, cfg)
                h = h + y
            new_bc[f"pos{j}"] = nc
        return h, new_bc

    h, new_cache = _run_cached_groups(params, cache, h, cfg, block_step)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    rows = jnp.asarray(last_rows)
    sel = h[0, jnp.maximum(rows, 0)]  # [B, D] or [B, R, D]; rows < 0 garbage
    head = params["lm_head"] if not cfg.tie_embeddings else params["embed"].T
    if rows.ndim == 1:
        logits = logits_from_hidden(sel[:, None], head, cfg.vocab_size)[:, 0]
    else:
        logits = logits_from_hidden(sel, head, cfg.vocab_size)  # [B, R, Vpad]
    return logits, new_cache


def _decode_block(bp, h, cfg, spec, cache, pos, layer):
    mixer, ffn = spec
    x = rms_norm(h, bp["norm1"], cfg.norm_eps)
    if mixer.startswith("attn"):
        y, new_cache = _decode_attn(bp["mixer"], x, cfg, mixer, cache, pos, layer)
    elif mixer == "ssm":
        y, new_cache = m2.decode_mamba2(bp["mixer"], x, cache, cfg)
    elif mixer == "rglru":
        y, new_cache = rg.decode_rglru(bp["mixer"], x, cache, cfg)
    else:
        raise ValueError(mixer)
    h = h + y
    if ffn != "none":
        x = rms_norm(h, bp["norm2"], cfg.norm_eps)
        if ffn == "swiglu":
            y = _apply_swiglu(bp["ffn"], x, cfg)
        else:
            y, _ = moe_mod.apply_moe(bp["ffn"], x, cfg)
        h = h + y
    return h, new_cache


# A paged cache's page pool and its scales: leaves with no batch axis,
# read and written in place as one stack over the layers.
_POOL_LEAVES = ("k_pages", "v_pages", "k_scale", "v_scale")


def _map_cache(fn, pool_fn, cache, *rest):
    """`tree_map` over cache trees: `pool_fn` on the paged pool leaves
    (`_POOL_LEAVES`), `fn` on every other leaf."""
    from jax import tree_util as jtu

    def apply(path, *xs):
        name = next(
            (e.key for e in reversed(path) if isinstance(e, jtu.DictKey)), None
        )
        return (pool_fn if name in _POOL_LEAVES else fn)(*xs)

    return jtu.tree_map_with_path(apply, cache, *rest)


def _run_cached_groups(params: dict, cache: dict, h, cfg: ModelConfig, block_step):
    """Run every stacked block group through `block_step(bp, bc, h,
    pattern, layer) → (h, new_bc)`, carrying the cache. The layer loop is
    a `fori_loop` that CARRIES the stacked cache — passing caches through
    scan xs/ys would materialize input + output + working copies
    (measured: 19 GiB temp vs ~0 on deepseek-7b decode_32k) and defeat
    buffer donation. Shared by `decode_step_lm` (one token) and
    `forward_packed` (a packed varlen batch) — the loop does not care how
    wide the token axis is.

    Paged pool leaves (`_POOL_LEAVES`) are never sliced: `block_step`
    gets them whole and stacked, writes the step's new rows into layer
    `layer` of them, and the kernels read that layer through their index
    maps — so XLA updates the carried pool in place and no layer's pool
    is copied out and back (DESIGN.md §3.4). Every other leaf (ring
    regions, recurrent state, block tables) is small per layer: it goes
    in as layer `layer`'s slice and comes back with
    `dynamic_update_index_in_dim`."""

    def run_group(stacked_p, stacked_c, h, pattern):
        def body(i, carry):
            h, cache_st = carry
            bp = jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False),
                stacked_p,
            )
            bc = _map_cache(
                lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False),
                lambda x: x, cache_st,
            )
            h, nc = block_step(bp, bc, h, pattern, i)
            cache_st = _map_cache(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n, i, 0),
                lambda c, n: n, cache_st, nc,
            )
            return h, cache_st

        nb = jax.tree.leaves(stacked_p)[0].shape[0]
        if cfg.scan_layers:
            return jax.lax.fori_loop(0, nb, body, (h, stacked_c))
        carry = (h, stacked_c)
        for i in range(nb):  # unrolled: the same body, a static layer index
            carry = body(i, carry)
        return carry

    new_cache = {}
    if cfg.n_blocks > 0:
        h, new_cache["blocks"] = run_group(
            params["blocks"], cache["blocks"], h, cfg.pattern
        )
    if cfg.remainder:
        h, new_cache["rem_blocks"] = run_group(
            params["rem_blocks"], cache["rem_blocks"], h, cfg.remainder
        )
    return h, new_cache


def decode_step_lm(params: dict, cache: dict, token: jax.Array, pos: jax.Array, cfg: ModelConfig):
    """One decode step. token [B], pos [B] → (logits [B, Vpad], new cache)."""
    h = embed_lookup(params["embed"], token[:, None], cfg.compute_dtype)

    def block_step(bp, bc, h, pattern, layer):
        new_bc = {}
        for j, spec in enumerate(pattern):
            h, nc = _decode_block(
                bp[f"pos{j}"], h, cfg, spec, bc[f"pos{j}"], pos, layer
            )
            new_bc[f"pos{j}"] = nc
        return h, new_bc

    h, new_cache = _run_cached_groups(params, cache, h, cfg, block_step)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params["lm_head"] if not cfg.tie_embeddings else params["embed"].T
    logits = logits_from_hidden(h, head, cfg.vocab_size)
    return logits[:, 0], new_cache


def _freeze_dead_rows(new_cache: dict, old_cache: dict, alive: jax.Array):
    """Keep only live batch rows' cache updates: per-batch leaves (batch on
    axis 1 after block stacking) revert to the old value where ¬alive; POOL
    leaves (`_POOL_LEAVES`, no batch axis) pass through — a dead row's
    page writes land in slots beyond its effective length, which decode
    overwrites before it ever reads them (the bucketed-prefill argument in
    DESIGN.md §3.5)."""
    return _map_cache(
        lambda new, old: jnp.where(
            alive.reshape((1, -1) + (1,) * (new.ndim - 2)), new, old
        ),
        lambda new, old: new, new_cache, old_cache,
    )


def prefill_lm(params: dict, tokens: jax.Array, cache: dict, cfg: ModelConfig,
               *, start_pos=0, lengths: Optional[jax.Array] = None):
    """Prefill a decode cache by scanning `decode_step_lm` over the prompt.

    Universal across mixer types (attention, SSM, RG-LRU) and exact: the
    cache after prefill is bit-identical to incremental decoding. Returns
    (logits of the LAST prompt token [B, Vpad], filled cache). Production
    TPU serving uses `forward_packed` (the varlen mixed step); this path
    favors exactness and works for every architecture.

    start_pos > 0 prefills only a *tail*: `tokens` are the positions
    [start_pos, start_pos + s) and the cache is assumed to already hold
    the first start_pos positions — the paged engine's shared-prefix
    admission (KV pages aliased from a live parent, DESIGN.md §3.4) and
    the radix prefix cache's warm-hit resume (pages matched out of the
    content-addressed tree, DESIGN.md §3.6) both enter here. FLASH-D is
    what makes this resume state-free: a finished tile leaves only (O, Λ)
    behind — no running max or pending division — so continuing from a
    page boundary needs nothing beyond the cached K/V pages themselves.
    Only valid for pure global-attention stacks: ring-region and
    recurrent layers carry state the skipped steps would have produced.
    It may be a traced i32 scalar, so varying tails reuse one compilation.

    lengths [B] (per-row REAL token count of `tokens` ≤ s) enables static-shape
    bucketing (DESIGN.md §3.5): `tokens` may be padded past each row's
    real prompt, the scan still runs s steps, but a dead row's cache
    updates are dropped (`_freeze_dead_rows`) and its logits are captured
    at position lengths−1 — so a power-of-two-padded prompt compiles
    O(log max_len) programs while returning exactly the unpadded result.
    """
    b, s = tokens.shape
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32).reshape(b)

    def body(carry, tok_pos):
        cache, prev_logits = carry
        tok, p = tok_pos
        logits, new_cache = decode_step_lm(params, cache, tok, jnp.full((b,), p), cfg)
        if lengths is not None:
            rel = p - start_pos  # step index within this (tail-)prefill
            new_cache = _freeze_dead_rows(new_cache, cache, rel < lengths)
            logits = jnp.where((rel == lengths - 1)[:, None], logits, prev_logits)
        return (new_cache, logits), None

    positions = start_pos + jnp.arange(s)
    (cache, logits), _ = jax.lax.scan(
        body,
        (cache, jnp.zeros((b, cfg.padded_vocab), jnp.float32)),
        (tokens.T, positions),
    )
    return logits, cache
