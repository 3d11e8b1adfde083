"""Sharding rules engine: logical axes → mesh axes, with divisibility guards.

Strategy (DESIGN.md §4):
  batch               → ('pod', 'data')      data parallel across pods
  seq (residual SP)   → 'model'              Megatron-style sequence parallel
  heads / ff / vocab  → 'model'              tensor parallel
  experts             → 'model'              expert parallel
  fsdp (param in-dim) → 'data'               ZeRO-3 within a pod; parameters
                                             replicate across pods (DCN is
                                             slow; grad all-reduce is
                                             hierarchical: ICI then DCN)

Every rule is divisibility-checked against the active mesh; non-divisible
dims fall back to replication (e.g. qwen2's 12 query heads on a 16-way model
axis). Models call `shard(x, kind)` at activation boundaries; with no active
context this is the identity, so smoke tests and single-device runs never
touch device state.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "activate",
    "active_ctx",
    "shard",
    "spec_for",
    "param_specs",
    "ShardingCtx",
    "cp_axis_for_cache",
    "cp_batch_axes_for_cache",
    "batch_parallel",
]

_TL = threading.local()


class ShardingCtx:
    def __init__(self, mesh: Mesh, *, use_sp: bool = True, fsdp_axis="data",
                 use_cp: bool = True, cp_prefill: bool = False):
        """fsdp_axis: 'data' (default — params replicate across pods, grad
        all-reduce is hierarchical ICI→DCN) or ('pod','data') (ZeRO across
        pods too — halves state at the cost of DCN param all-gathers; the
        only way 235B-scale training fits 16 GB/chip HBM).

        use_cp: when the kv_cache rule seq-shards a cache (see
        `cp_axis_for_cache`), route decode attention through the
        cross-device FLASH-D merge (`repro.distributed.context.cp_decode`)
        instead of letting GSPMD gather the cache. cp_prefill additionally
        routes `flash_attention` through the ring-prefill schedule — off by
        default because the ring path is forward-only (serving/prefill);
        training keeps the differentiable GSPMD lowering."""
        self.mesh = mesh
        self.axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.use_sp = use_sp
        if isinstance(fsdp_axis, str):
            fsdp_axis = (fsdp_axis,)
        fsdp_axis = tuple(a for a in (fsdp_axis or ()) if a in self.axis_sizes)
        self.fsdp_axis = fsdp_axis or None
        self.has_pod = "pod" in self.axis_sizes
        # constrain mixer/FFN OUTPUTS to the seq-sharded residual spec so the
        # row-parallel matmuls' partial sums lower to reduce-scatter instead
        # of all-reduce (Megatron-SP placement; §Perf lever, halves that wire)
        self.rs_outputs = True
        # TP the activations (classic Megatron). False = keep weights sharded
        # for memory but let XLA gather them at use and compute full-DP —
        # wins whenever tokens ≫ weights (32k prefill: weights/layer ~270 MB
        # bf16 vs ~1 GiB f32 activation all-reduce; §Perf lever 'notp')
        self.tp_activations = True
        self.use_cp = use_cp
        self.cp_prefill = cp_prefill

    @property
    def batch_axes(self) -> Tuple[str, ...]:
        return ("pod", "data") if self.has_pod else ("data",)

    def axis_size(self, axes) -> int:
        if axes is None:
            return 1
        if isinstance(axes, str):
            return self.axis_sizes.get(axes, 1)
        return int(np.prod([self.axis_sizes.get(a, 1) for a in axes]))


def active_ctx() -> Optional[ShardingCtx]:
    return getattr(_TL, "ctx", None)


@contextlib.contextmanager
def activate(ctx: Optional[ShardingCtx]):
    prev = getattr(_TL, "ctx", None)
    _TL.ctx = ctx
    try:
        yield
    finally:
        _TL.ctx = prev


def _fit(ctx: ShardingCtx, dim_size: int, axes):
    """Return axes if dim_size divides by their product, else None.

    Axes absent from the active mesh never shard: their size defaults to 1
    (always divides), but naming them in a spec would be a mesh-resolution
    error — e.g. the kv_cache rule on a ('data',)-only serving mesh."""
    if axes is None:
        return None
    if isinstance(axes, str):
        if axes not in ctx.axis_sizes:
            return None
        return axes if dim_size % ctx.axis_size(axes) == 0 else None
    axes = tuple(a for a in axes if a in ctx.axis_sizes)
    if not axes:
        return None
    if dim_size % ctx.axis_size(axes) == 0:
        return axes
    # try a prefix (e.g. ('pod','data') → ('pod',)) before giving up
    for cut in range(len(axes) - 1, 0, -1):
        sub = axes[:cut]
        if dim_size % ctx.axis_size(sub) == 0:
            return sub
    return None


def _heads_spec(c: "ShardingCtx", s):
    """[B, S, H, hd] attention activations.

    Preferred: heads over 'model' (Megatron TP). When the head count does
    not divide the model axis (yi-34b's 56, qwen2's 12), fall back to
    full-DP attention: batch over as many mesh axes as divide it, remaining
    axes onto the sequence dim — bounding per-device attention memory
    without padding head counts (GSPMD keeps semantics; only collective
    placement changes)."""
    b = _fit(c, s[0], c.batch_axes)
    h = _fit(c, s[2], "model")
    if h is not None:
        return P(b, None, h, None)
    axes_all = c.batch_axes + ("model",)
    b2 = _fit(c, s[0], axes_all)
    used = set(b2) if isinstance(b2, tuple) else ({b2} if b2 else set())
    rest = tuple(a for a in axes_all if a not in used)
    sspec = _fit(c, s[1], rest) if rest else None
    return P(b2, sspec, None, None)


# activation kinds → per-dim logical roles
_ACT_RULES = {
    # [B, S, D] residual stream between layers (SP shards S over model)
    "residual": lambda c, s: P(_fit(c, s[0], c.batch_axes), _fit(c, s[1], "model") if c.use_sp else None, None),
    # [B, S, D] inside a block (seq gathered for attention/mixing)
    "hidden": lambda c, s: P(_fit(c, s[0], c.batch_axes), None, None),
    # [B, S, H, hd] attention activations — heads over model
    "heads": _heads_spec,
    # [B, S, F] ffn hidden — ff over model
    "ff": lambda c, s: P(_fit(c, s[0], c.batch_axes), None, _fit(c, s[2], "model")),
    # [B, S, V] logits — vocab over model
    "logits": lambda c, s: P(_fit(c, s[0], c.batch_axes), None, _fit(c, s[2], "model")),
    # [E, C, D] expert dispatch buffers — experts over model
    "experts": lambda c, s: P(_fit(c, s[0], "model"), None, None),
    # [G, t, D] MoE token groups — groups over the batch axes
    "moe_groups": lambda c, s: P(_fit(c, s[0], c.batch_axes), None, None),
    # [G, E, C, D] group-local dispatch buffers — G over batch, E over model
    "moe_dispatch": lambda c, s: P(
        _fit(c, s[0], c.batch_axes), _fit(c, s[1], "model"), None, None
    ),
    # KV cache [B, S, H, hd]: batch if divisible, else seq (context parallel)
    "kv_cache": lambda c, s: _kv_cache_spec(c, s),
}


def _kv_cache_spec(c: ShardingCtx, s):
    b_axes = _fit(c, s[0], c.batch_axes)
    h = _fit(c, s[2], "model")
    if b_axes is not None:
        if h is not None:
            return P(b_axes, None, h, None)
        # heads don't divide TP: context-parallel the cache sequence over
        # 'model' — decode attention merges seq-sharded partials via LSE
        return P(b_axes, _fit(c, s[1], "model"), None, None)
    # batch too small (long-context, B=1): context-parallel over 'data'
    return P(None, _fit(c, s[1], "data"), h, None)


def cp_axis_for_cache(shape) -> Optional[str]:
    """Mesh axis the kv_cache rule puts on the SEQUENCE dim of a
    [B, S, H, hd] cache (context parallelism), or None.

    This is the selector for the cross-device FLASH-D merge paths
    (`repro.distributed.context`): when the rules engine decides a cache is
    seq-sharded (batch too small, or heads not divisible by TP), attention
    must merge per-shard (O, Λ) partials instead of gathering the cache."""
    ctx = active_ctx()
    if ctx is None or ctx.mesh is None or not getattr(ctx, "use_cp", True):
        return None
    if len(shape) != 4:
        return None
    spec = _kv_cache_spec(ctx, tuple(shape))
    ax = spec[1] if len(spec) > 1 else None
    if isinstance(ax, tuple):
        ax = ax[0] if len(ax) == 1 else None
    if ax is None:
        return None
    n = ctx.axis_size(ax)
    return ax if n > 1 and shape[1] % n == 0 else None


def cp_batch_axes_for_cache(shape) -> Optional[Tuple[str, ...]]:
    """Mesh axes the kv_cache rule puts on the BATCH dim of a [B, S, H, hd]
    cache. The context-parallel paths keep this sharding inside their
    shard_map (heads-not-divisible CP shards batch over data AND seq over
    model — replicating batch there would re-gather the cache)."""
    ctx = active_ctx()
    if ctx is None or ctx.mesh is None or len(shape) != 4:
        return None
    ax = _kv_cache_spec(ctx, tuple(shape))[0]
    if ax is None:
        return None
    return (ax,) if isinstance(ax, str) else tuple(ax)


_TP_KINDS = ("ff", "heads", "logits", "experts", "moe_dispatch")


def spec_for(kind: str, shape: Sequence[int]) -> Optional[P]:
    ctx = active_ctx()
    if ctx is None:
        return None
    if not getattr(ctx, "tp_activations", True) and kind in _TP_KINDS:
        # full-DP activations: batch over every axis that divides
        b = _fit(ctx, shape[0], ctx.batch_axes + ("model",))
        return P(b, *([None] * (len(shape) - 1)))
    return _ACT_RULES[kind](ctx, tuple(shape))


def batch_parallel(fn, *args):
    """`fn(*args)` run on each device's share of the leading (batch) axis.

    GSPMD cannot partition a Mosaic kernel, so under an active ShardingCtx
    a Pallas call runs inside a shard_map: the batch is split over every
    mesh axis that divides it (the full-DP attention layout of
    `_heads_spec`) and whatever does not divide is replicated. With no
    active context this is `fn(*args)`. Every operand and result must be
    batch-leading."""
    ctx = active_ctx()
    if ctx is None or ctx.mesh is None:
        return fn(*args)
    axes = _fit(ctx, args[0].shape[0], ctx.batch_axes + ("model",))

    def spec(x):
        return P(axes, *([None] * (len(x.shape) - 1)))

    out_specs = jax.tree.map(spec, jax.eval_shape(fn, *args))
    return jax.shard_map(
        fn, mesh=ctx.mesh, in_specs=tuple(spec(a) for a in args),
        out_specs=out_specs, check_vma=False,
    )(*args)


def shard(x: jax.Array, kind: str) -> jax.Array:
    """Apply a logical sharding constraint; identity with no active ctx."""
    spec = spec_for(kind, x.shape)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Parameter sharding: path-name driven rules
# ---------------------------------------------------------------------------

def _param_rule(ctx: ShardingCtx, path: str, shape: Tuple[int, ...]) -> P:
    """TP dim from the weight's role; FSDP on the largest remaining dim."""
    fsdp = ctx.fsdp_axis
    nd = len(shape)
    tp_dim = None  # which dim gets 'model'

    def last(*names):
        return any(path.endswith(n) or f".{n}." in path or f"/{n}" in path for n in names)

    # embeddings / lm head: vocab over model
    if last("embed", "lm_head"):
        tp_dim = 0 if shape[0] > shape[-1] else nd - 1
    # column-parallel (out-dim sharded): q/k/v/gate/up, moe wi, router
    elif last("wq", "wk", "wv", "wg", "wu", "w_in", "w_gate"):
        tp_dim = nd - 1
    # row-parallel (in-dim sharded): output projections / down proj
    elif last("wo", "wd", "w_out"):
        tp_dim = nd - 2 if nd >= 2 else None
    elif last("router"):
        tp_dim = None  # small; replicate
    # moe expert stacks [E, d, f]: shard E over model
    if last("experts") and nd == 3:
        tp_dim = 0

    spec = [None] * nd
    if tp_dim is not None and nd >= 1 and "model" in ctx.axis_sizes:
        if shape[tp_dim] % ctx.axis_size("model") == 0:
            spec[tp_dim] = "model"
    # FSDP: biggest dim not already sharded (params ≥ 2 dims, skip tiny)
    if fsdp and nd >= 2 and int(np.prod(shape)) >= 2 ** 16:
        cands = sorted(range(nd), key=lambda i: -shape[i])
        for i in cands:
            if spec[i] is None and shape[i] % ctx.axis_size(fsdp) == 0:
                spec[i] = fsdp
                break
    return P(*spec)


def param_specs(params_shapes) -> "jax.tree_util.PyTreeDef":
    """PartitionSpec tree for a params(-shaped) tree. Requires active ctx."""
    ctx = active_ctx()
    if ctx is None:
        raise RuntimeError("param_specs needs an active sharding context")
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)
    specs = []
    for path, leaf in flat:
        name = "/".join(
            getattr(p, "key", getattr(p, "name", str(getattr(p, "idx", p))))
            for p in path
        )
        shape = tuple(leaf.shape)
        # stacked-layer leading dim [n_blocks, ...]: rule applies to the rest
        if name.startswith("blocks") or "/blocks/" in name or name.startswith("enc_blocks") or name.startswith("dec_blocks"):
            inner = _param_rule(ctx, name, shape[1:])
            specs.append(P(None, *inner))
        else:
            specs.append(_param_rule(ctx, name, shape))
    return jax.tree_util.tree_unflatten(treedef, specs)


def named_sharding_tree(params_shapes):
    ctx = active_ctx()
    specs = param_specs(params_shapes)
    return jax.tree.map(lambda s: NamedSharding(ctx.mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# batch / decode-cache sharding
# ---------------------------------------------------------------------------

def batch_specs(batch_shapes):
    """Inputs [B, ...]: batch over ('pod','data') when divisible."""
    ctx = active_ctx()

    def rule(leaf):
        b = _fit(ctx, leaf.shape[0], ctx.batch_axes)
        return P(b, *([None] * (len(leaf.shape) - 1)))

    return jax.tree.map(rule, batch_shapes)


def cache_specs_tree(cache_shapes):
    """Decode caches: leading [n_blocks] unsharded; KV [nb,B,S,H,hd] shards
    batch (or seq when B=1 — context parallel) + heads; recurrent states
    [nb,B,...] shard batch."""
    ctx = active_ctx()

    def rule(leaf):
        s = leaf.shape
        if len(s) == 5:  # [nb, B, S, H, hd] attention KV
            inner = _kv_cache_spec(ctx, s[1:])
            return P(None, *inner)
        if len(s) >= 2:  # recurrent state [nb, B, ...]
            b = _fit(ctx, s[1], ctx.batch_axes)
            return P(None, b, *([None] * (len(s) - 2)))
        return P(*([None] * len(s)))

    return jax.tree.map(rule, cache_shapes)


def to_named(spec_tree):
    ctx = active_ctx()
    return jax.tree.map(
        lambda s: NamedSharding(ctx.mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
