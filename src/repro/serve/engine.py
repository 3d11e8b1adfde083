"""Batched serving engine: continuous batching over contiguous, paged, or
packed-varlen KV memory, with automatic prefix caching and preemptive
priority scheduling (DESIGN.md §3.4–§3.6).

Requests join a slot array; finished slots are refilled from a priority
queue (FIFO within a class). Slot lifecycle (queue, per-slot outputs,
EOS/max-token completion, refill, preemption bookkeeping, peak-concurrency
and per-request TTFT accounting) lives in `repro.serve.scheduler.Scheduler`
— shared by every path below; this module owns memory admission and device
dispatch only. Sampling: greedy / temperature / top-k.

Three serving modes (ServeConfig.kv_layout × ServeConfig.step_mode):

  contiguous (default) — each slot owns a fixed max_len-wide cache region;
    memory commits max_batch × max_len tokens up front.
  paged (DESIGN.md §3.4) — KV lives in a global page pool with
    per-sequence block tables (runtime/kvcache.py); admission is by FREE
    PAGES and decode runs the block-table scalar-prefetch kernel under
    `*_pallas`.
  mixed (step_mode="mixed", DESIGN.md §3.5) — chunked-prefill continuous
    batching over the paged pool: every step packs each decoding slot's
    one pending token TOGETHER WITH the next prefill chunks of admitted
    prompts into one flat varlen batch and dispatches ONE jitted
    `forward_packed` step.

Cache-aware, preemptible serving core (DESIGN.md §3.6) — paged + mixed:

  * automatic prefix caching — the allocator's content-addressed radix
    tree persists ACROSS serve() calls on this engine (`self._alloc` and
    the device page pool are engine-lifetime state). Admission walks the
    tree with the prompt's page chain; matched full pages are aliased
    into the new block table and prefill starts at the first uncached
    token (`prefill_lm(start_pos=…)` / the mixed packer's `fed0`), so a
    warm system prompt costs O(new tokens) TTFT. Prompts are indexed once
    their prefill completes (live sharing); retirement donates the whole
    clean token stream — including generated tokens — so a multi-turn
    follow-up that replays the previous conversation hits the cache too.
  * preemptive scheduling — with `ServeConfig.preemption` (default on),
    worst-case `reserve_tokens` admission is replaced by optimistic
    per-chunk allocation: a request is admitted when its PROMPT fits, and
    growth draws the free pool. When the pool (or the slot array, given a
    higher-priority arrival) is exhausted, the scheduler's victim — the
    lowest-priority, youngest slot — is preempted: its pages are donated
    to the prefix cache (making resume nearly free) and the request is
    re-queued with recompute-on-resume, which keeps every output stream
    token-identical to an unconstrained run while letting the pool be
    oversubscribed (pool < worst-case demand still completes).

`Engine.stats()` exposes the hit-rate / preemption / eviction counters,
cumulative over the engine's lifetime.

Static-shape bucketing (DESIGN.md §3.5): prompt lengths and packed-batch
sizes are padded to powers of two (`tuning.bucket_pow2`) before they reach
a jitted program — prefills run with per-row `lengths` masking
(`prefill_lm`), packs carry −1 padding rows — so `serve()` compiles
O(log max_len) programs instead of one per distinct length (pinned by
tests/test_scheduler.py).

The decode hot loop is fully on-device (DESIGN.md §3.3): `generate` is one
jitted prefill + `lax.scan` (exactly ONE device→host sync, counted in
`self.host_syncs`); the sequential `serve` loops decode in jitted
`decode_chunk`-token chunks (one sync per chunk); the mixed loop syncs
once per packed step.

Sharded serving: pass a `repro.distributed.sharding.ShardingCtx` and the
engine activates it (plus the ambient mesh) around every trace/dispatch;
seq-sharded KV caches route decode through the cross-device FLASH-D merge
(`repro.distributed.context.cp_decode`, DESIGN.md §4.1).

Fault tolerance (DESIGN.md §3.7): every request gets a lifecycle contract
— it ends DONE, FAILED (fault-retry budget exhausted), or EXPIRED
(deadline), never silently dropped. A seeded `FaultInjector` can raise
simulated failures at four named sites threaded through all three serve
loops (page_alloc / kernel_dispatch / device_step / host_sync); faults are
isolated to the request (or step) they hit — the faulted request rolls
back through the same recompute-on-resume path preemption uses, charged
against `ServeConfig.max_retries`, while its neighbors keep decoding. A
real crash no longer resets the page pool: the recovery handler folds
live slots back into the queue (pages donated — their KV is committed
state) and keeps the allocator + radix tree warm. `snapshot()/restore()`
serialize the queue, results, and the radix tree's TOKEN chains through
`runtime/checkpoint.py` — never KV pages, because FLASH-D's (O, Λ) state
is a pure function of the token stream and `restore()` recomputes it
exactly. Repeated kernel faults downgrade a `*_pallas` attention impl to
its registered jnp fallback for the rest of the engine's life
(`kernels/ops.py`), recorded in `stats()`.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence, Set, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import ModelConfig, get_model
from repro.models.transformer import forward_packed, packed_mixers_ok, prefill_lm
from repro.runtime.resilience import FaultInjector, InjectedFault
from repro.serve.scheduler import TERMINAL, Request, Scheduler, StepPlan

__all__ = ["ServeConfig", "Engine", "sample_token"]


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_batch: int = 8
    max_len: int = 256
    temperature: float = 0.0  # 0 → greedy
    top_k: int = 0
    eos_id: int = -1  # <0: run to max_new_tokens
    seed: int = 0
    decode_chunk: int = 8  # tokens per device dispatch in sequential `serve`
    # ---- paged KV cache (DESIGN.md §3.4) ----
    kv_layout: str = "contiguous"  # "paged": page-pool KV in `serve`
    page_size: int = 0  # 0 → repro.kernels.tuning heuristic
    kv_pool_tokens: int = 0  # pool size in tokens; 0 → max_batch·max_len
    # quantized page pool (DESIGN.md §3.8): "" keeps the compute dtype;
    # a name from repro.runtime.quant.available() ("int8", "fp8") stores
    # pages in that format with per-(page, head) f32 scale leaves,
    # dequantized inside the attention kernels
    kv_dtype: str = ""
    # prefix reuse: `prefix_sharing` is the soundness gate (global-attn
    # stacks only — auto-disabled on hybrid stacks), `prefix_cache` the
    # mechanism (the radix tree, which subsumes the old live-scan sharing:
    # live prompts are indexed at prefill). Either False disables ALL
    # prefix reuse — every prompt prefills in full.
    prefix_sharing: bool = True
    # ---- radix prefix cache + preemption (DESIGN.md §3.6) ----
    prefix_cache: bool = True  # content-addressed page cache across requests
    cache_min_free_pages: int = -1  # eviction watermark; -1 → tuning heuristic
    cache_max_pages: int = -1  # retained-page cap; -1 → tuning heuristic
    preemption: bool = True  # optimistic admission + victim preemption
    # ---- mixed varlen step (DESIGN.md §3.5) ----
    step_mode: str = "sequential"  # "mixed": chunked-prefill packed steps
    token_budget: int = 0  # packed tokens per mixed step; 0 → heuristic
    prefill_chunk: int = 16  # max prompt tokens one sequence feeds per step
    # ---- speculative decoding (DESIGN.md §3.9) ----
    # K draft tokens verified per target step through one packed varlen
    # dispatch; 0 disables. Needs `Engine(draft=...)` (a (params, cfg)
    # pair for a small draft model, or a host callable), greedy sampling
    # (temperature 0 — acceptance is argmax-exact), and a paged,
    # packed-capable stack for the verify step.
    spec_tokens: int = 0
    # ---- fault tolerance (DESIGN.md §3.7) ----
    max_retries: int = 3  # per-request fault-retry budget (then FAILED)
    retry_backoff_s: float = 0.0  # base of the exponential requeue backoff
    deadline_s: float = 0.0  # default per-request deadline; 0 → none
    fault_rate: float = 0.0  # chaos: per-site injected-fault probability
    fault_seed: int = 0  # chaos: injector stream seed
    downgrade_after: int = 3  # consecutive kernel faults before jnp fallback


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_pool_pages(pages, srcs, dsts):
    """pages[:, d] ← pages[:, s] for every owed CoW copy, in one update.
    The pool is donated so backends that support donation do it in place
    (O(pages copied), not O(pool))."""
    return pages.at[:, dsts].set(pages[:, srcs])


def _map_paged(cache, *rest, pool=None, tbl=None, batch=None):
    """Tree-map over a (possibly paged) cache with per-leaf-kind functions.

    Leaf kinds by dict key: `k_pages`/`v_pages` are POOL leaves (global
    page arrays, no batch axis — [n_blocks, P, page, Hkv, hd]), as are the
    quantized pool's scale side-bands `k_scale`/`v_scale` ([n_blocks, P,
    Hkv] — physical-page axis in the same position, so page copies move
    page bytes and scale together); everything else — including the block
    table `tbl` — is a PER-BATCH leaf (batch on axis 1 after block
    stacking). `tbl=` overrides the per-batch handler for table leaves
    (engine table mirroring); a missing handler leaves the leaf unchanged.
    Extra cache trees in `rest` are zipped leaf-wise."""
    from jax import tree_util as jtu

    def leaf_name(path):
        for e in reversed(path):
            if isinstance(e, jtu.DictKey):
                return e.key
        return None

    def apply(path, x, *xs):
        name = leaf_name(path)
        if name in ("k_pages", "v_pages", "k_scale", "v_scale"):
            fn = pool
        elif name == "tbl":
            fn = tbl if tbl is not None else batch
        else:
            fn = batch
        return x if fn is None else fn(x, *xs)

    return jtu.tree_map_with_path(apply, cache, *rest)


def sample_token(logits: jax.Array, key, cfg: ServeConfig) -> jax.Array:
    """logits [B, V] → token [B]."""
    if cfg.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / cfg.temperature
    if cfg.top_k > 0:
        kth = jax.lax.top_k(logits, cfg.top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


class _PoolCtx:
    """Mutable per-serve() context of the paged loops: the device cache
    tree plus the slot → allocator-sequence map and which slots' prompts
    are already indexed in the radix tree."""

    __slots__ = ("cache", "seq_of", "inserted")

    def __init__(self, cache):
        self.cache = cache
        self.seq_of: Dict[int, int] = {}
        self.inserted: Set[int] = set()


class Engine:
    def __init__(self, params, model_cfg: ModelConfig, serve_cfg: ServeConfig,
                 *, sharding_ctx=None,
                 fault_injector: Optional[FaultInjector] = None,
                 draft=None):
        self.params = params
        self.mc = model_cfg
        self.sc = serve_cfg
        self.ctx = sharding_ctx  # Optional[repro.distributed.sharding.ShardingCtx]
        self.api = get_model(model_cfg)
        self._build_jits()
        self._key = jax.random.PRNGKey(serve_cfg.seed)
        self.host_syncs = 0  # device→host transfers issued by this engine
        self.peak_active = 0  # max concurrent sequences observed by `serve`
        self.ttft = {}  # rid → time-to-first-token of the last serve() call
        self._page_layout = None
        if serve_cfg.kv_layout == "paged" or serve_cfg.step_mode == "mixed":
            from repro.kernels.tuning import choose_page_layout  # lazy
            from repro.models.transformer import paged_mixers

            if getattr(model_cfg, "is_encdec", False) or not paged_mixers(model_cfg):
                # no global-attention layer to page (pure SSM/ring stacks,
                # enc-dec) — serve falls back to the contiguous layout
                pass
            else:
                from repro.runtime import quant  # lazy: no cycle

                self._page_layout = choose_page_layout(
                    serve_cfg.max_len,
                    model_cfg.head_dim_,
                    model_cfg.head_dim_,
                    group=model_cfg.n_heads // model_cfg.n_kv_heads,
                    pool_tokens=serve_cfg.kv_pool_tokens
                    or serve_cfg.max_batch * serve_cfg.max_len,
                    page_size=serve_cfg.page_size or None,
                    kv_itemsize=quant.kv_itemsize(serve_cfg.kv_dtype),
                )
        # the mixed varlen step runs every layer on flat packed tokens
        # through the paged pool — global-attention-only stacks
        self._mixed_ok = (
            serve_cfg.step_mode == "mixed"
            and self._page_layout is not None
            and packed_mixers_ok(model_cfg)
        )
        # prefix reuse skips the shared positions' prefill steps, which is
        # only sound when EVERY mixer reads the paged cache: ring
        # (local/chunked) and SSM/RG-LRU layers carry state those steps
        # would have produced (see prefill_lm's start_pos contract)
        self._can_share_prefix = (
            self._page_layout is not None
            and serve_cfg.prefix_sharing
            and all(
                m in ("attn", "attn_nope", "attn_bidir")
                for m, _ in (*model_cfg.pattern, *model_cfg.remainder)
            )
        )
        # radix prefix cache (DESIGN.md §3.6): page-content addressing is
        # sound exactly when prefix reuse is (KV at position p is a pure
        # function of tokens [0, p] for a global-attention stack)
        self._cache_on = self._can_share_prefix and serve_cfg.prefix_cache
        # engine-lifetime paged state: the allocator (and its radix tree)
        # plus the device page pool persist across serve() calls so cached
        # prefixes survive between request batches
        self._alloc = None
        self._paged_cache = None
        self._seq_base = 0  # allocator sequence ids, unique across calls
        self._stats = {
            "prefix_lookups": 0, "prefix_hits": 0, "hit_tokens": 0,
            "prompt_tokens": 0, "preemptions": 0,
            "failed": 0, "retried": 0, "expired": 0,
            "downgrades": 0,
            "spec_rounds": 0, "spec_drafted": 0, "spec_accepted": 0,
        }
        # ---- speculative decoding (DESIGN.md §3.9) ----
        self._spec = None
        if serve_cfg.spec_tokens > 0:
            from repro.serve.speculative import DraftModel, SpecState  # lazy

            if draft is None:
                raise ValueError(
                    "spec_tokens > 0 needs Engine(draft=...): a (params, "
                    "ModelConfig) pair for a draft model or a host callable"
                )
            if serve_cfg.temperature > 0:
                raise ValueError(
                    "speculative decoding is greedy-only: the accept rule "
                    "compares draft tokens against the target's argmax, so "
                    "temperature must be 0"
                )
            if self._page_layout is None or not packed_mixers_ok(model_cfg) \
                    or not (self._mixed_ok or serve_cfg.kv_layout == "paged"):
                raise ValueError(
                    "speculative decoding verifies drafts through the packed "
                    "varlen step over the paged pool — needs kv_layout="
                    "'paged' or step_mode='mixed' on a packed-capable stack"
                )
            if isinstance(draft, tuple):
                dparams, dcfg = draft
                draft = DraftModel(
                    dparams, dcfg, max_batch=serve_cfg.max_batch,
                    max_len=serve_cfg.max_len,
                )
            self._spec = SpecState(k=int(serve_cfg.spec_tokens), draft=draft)
        # ---- fault tolerance (DESIGN.md §3.7) ----
        if fault_injector is None and serve_cfg.fault_rate > 0:
            fault_injector = FaultInjector(
                serve_cfg.fault_rate, serve_cfg.fault_seed
            )
        self._injector = fault_injector
        self._kernel_faults = 0  # consecutive kernel-site faults (downgrade)
        self._step_faults = 0  # consecutive faulted steps (victim charging)
        self._sched: Optional[Scheduler] = None  # last/current serve's scheduler
        self._resume_state: Optional[dict] = None  # restored snapshot, pre-resume

    def _build_jits(self) -> None:
        """(Re)build every jitted entry point. Each wrapper closes over
        `self.mc` / `self.api`, which jit treats as trace-time constants —
        so the graceful-degradation path MUST call this after swapping
        `attn_impl` (mutating `self.mc` alone would keep serving the old
        compiled programs)."""
        self._decode = jax.jit(
            lambda p, c, t, pos: self.api.decode_step(p, c, t, pos, self.mc)
        )
        self._gen = jax.jit(self._gen_fn, static_argnums=(5,))
        self._chunk = jax.jit(self._chunk_fn, static_argnums=(5,))
        # bucketed prefill: one program per power-of-two prompt bucket;
        # start_pos rides as a traced scalar so shared-prefix tails of any
        # length reuse the same program
        self._prefill = jax.jit(
            lambda p, t, c, sp, ln: prefill_lm(
                p, t, c, self.mc, start_pos=sp, lengths=ln
            )
        )
        self._mixed = jax.jit(self._mixed_fn, static_argnums=(8,))
        self._verify = jax.jit(self._verify_fn, static_argnums=(8,))

    def _scope(self):
        """Sharding scope for traces/dispatches: activates the ctx and the
        ambient mesh so logical constraints (and context-parallel routing)
        resolve inside the jitted loops. No-op without a sharding_ctx."""
        if self.ctx is None:
            return contextlib.nullcontext()
        from repro.distributed import sharding as shd  # lazy: optional dep

        stack = contextlib.ExitStack()
        stack.enter_context(shd.activate(self.ctx))
        stack.enter_context(jax.set_mesh(self.ctx.mesh))
        return stack

    def _to_host(self, x) -> np.ndarray:
        """The engine's ONLY device→host sync point (counted for tests)."""
        self.host_syncs += 1
        return np.asarray(x)

    def _bucket(self, n: int) -> int:
        from repro.kernels.tuning import bucket_pow2  # lazy: no cycle

        return bucket_pow2(n, lo=8, hi=self.sc.max_len)

    # ---- fault injection / degradation (DESIGN.md §3.7) ----
    def _inj(self, site: str, rid: Optional[int] = None) -> None:
        if self._injector is not None:
            self._injector.check(site, rid=rid)

    def _sync(self, x, rid: Optional[int] = None) -> np.ndarray:
        """Serve-loop device→host sync: the host_sync injection site."""
        self._inj("host_sync", rid)
        return self._to_host(x)

    def _note_fault(self, exc: InjectedFault) -> None:
        """Record an injected fault; consecutive kernel-site faults on a
        Pallas impl trigger the jnp downgrade."""
        if exc.site in ("kernel_dispatch", "device_step"):
            self._kernel_faults += 1
            if (self._kernel_faults >= self.sc.downgrade_after
                    and self.mc.attn_impl.endswith("_pallas")):
                self._downgrade()

    def _clear_fault_streak(self) -> None:
        """Any successful dispatch breaks the consecutive-fault streaks."""
        self._kernel_faults = 0
        self._step_faults = 0

    def _downgrade(self) -> None:
        """Graceful degradation: flip the attention impl to its registered
        jnp fallback and rebuild the jitted entry points (they close over
        `self.mc` — see `_build_jits`). One-way for the engine's lifetime;
        recorded in `stats()["downgrades"]` / `["attn_impl"]`."""
        from repro.kernels.ops import fallback_impl  # lazy: no cycle

        fb = fallback_impl(self.mc.attn_impl)
        if fb == self.mc.attn_impl:
            return
        self._stats["downgrades"] += 1
        self.mc = dataclasses.replace(self.mc, attn_impl=fb)
        self.api = get_model(self.mc)
        self._build_jits()
        self._kernel_faults = 0

    def _on_step_fault(self, sched: Scheduler, exc: InjectedFault,
                       release) -> None:
        """A step-wide injected fault: the step's uncommitted device
        results were discarded, so retrying it is exact (committed host
        state never advanced). To guarantee progress under a hostile
        schedule, after `max_retries` consecutive faulted steps the
        scheduler's victim slot is charged one retry (requeue, or FAILED
        when its budget is out) via `release` — which also frees/donates
        its memory — and the streak resets."""
        self._note_fault(exc)
        self._step_faults += 1
        if self._step_faults > sched.max_retries:
            self._step_faults = 0
            v = sched.victim_slot()
            if v is not None:
                release(v)

    def _await_backoff(self, sched: Scheduler) -> bool:
        """No live slot: everything left is queued (usually behind a retry
        backoff gate). Sleep until the earliest becomes eligible; False
        when the queue is empty too (serving is over)."""
        if not sched.queue:
            return False
        wait = sched.next_ready_in()
        if wait is not None and wait > 0:
            time.sleep(wait)
        return True

    def _make_sched(self, requests, max_new_tokens: int, priorities,
                    deadlines) -> Scheduler:
        if deadlines is None and self.sc.deadline_s > 0:
            deadlines = [self.sc.deadline_s] * len(requests)
        self._call_stats = {"packed_rows": 0, "real_rows": 0, "steps": {}}
        sched = Scheduler(
            requests, max_new_tokens, self.sc.max_batch, self.sc.eos_id,
            priorities=priorities, deadlines=deadlines,
            max_retries=self.sc.max_retries,
            retry_backoff_s=self.sc.retry_backoff_s,
        )
        self._sched = sched
        return sched

    def _finish_serve(self, sched: Scheduler) -> None:
        self.ttft = dict(sched.first_token_at)
        self._stats["preemptions"] += sched.preemptions
        self._stats["retried"] += sched.retried
        self._stats["failed"] += sched.failed
        self._stats["expired"] += sched.expired
        self._stats["spec_rounds"] += sched.spec_rounds
        self._stats["spec_drafted"] += sched.spec_drafted
        self._stats["spec_accepted"] += sched.spec_accepted

    # ---- observability ----
    def stats(self) -> dict:
        """Serving counters, cumulative over this engine's lifetime:
        prefix-cache hit rate (token-weighted), preemption / eviction /
        donation counts, pool occupancy. Of the last serve() call only:
        per-request TTFT (`ttft`), status (`request_status`) and first
        admission into a slot (`admitted_at`), all in scheduler seconds;
        and, of the mixed loop, its steps by kind (`steps`: mixed /
        chunk / spec) and the rows its packed mixed steps dispatched
        (`packed_rows`, after padding and bucketing) of which
        `real_rows` held a token."""
        s = dict(self._stats)
        s["hit_rate"] = s["hit_tokens"] / max(s["prompt_tokens"], 1)
        s["prefix_cache_enabled"] = self._cache_on
        s["preemption_enabled"] = bool(self.sc.preemption)
        s["spec_enabled"] = self._spec is not None
        s["spec_rejected"] = s["spec_drafted"] - s["spec_accepted"]
        s["spec_acceptance_rate"] = (
            s["spec_accepted"] / max(s["spec_drafted"], 1)
        )
        # committed tokens per verify round = accepted drafts + the bonus
        # token every round emits — the speedup lever BENCH_spec sweeps
        s["spec_mean_accepted"] = (
            s["spec_accepted"] / max(s["spec_rounds"], 1)
        )
        if self._alloc is not None:
            s.update(
                evictions=self._alloc.evictions,
                donated_pages=self._alloc.donated_pages,
                cached_pages=self._alloc.cached_pages,
                pages_in_use=self._alloc.pages_in_use,
                free_pages=self._alloc.free_pages,
            )
        if self._paged_cache is not None and self._page_layout is not None:
            # actual device footprint of the page pools (quantized pages +
            # scale side-band included) per pool token — the equal-HBM
            # denominator BENCH_quant.json budgets against
            seen = 0
            from jax import tree_util as jtu

            for path, leaf in jtu.tree_leaves_with_path(self._paged_cache):
                name = next(
                    (e.key for e in reversed(path)
                     if isinstance(e, jtu.DictKey)), None,
                )
                if name in ("k_pages", "v_pages", "k_scale", "v_scale"):
                    seen += leaf.nbytes
            pool_tokens = self._page_layout.n_pages * self._page_layout.page_size
            s["kv_pool_bytes"] = int(seen)
            s["kv_bytes_per_token"] = seen / max(pool_tokens, 1)
            s["kv_dtype"] = self.sc.kv_dtype or "native"
        s["peak_active"] = self.peak_active
        s["ttft"] = dict(self.ttft)
        s["attn_impl"] = self.mc.attn_impl
        if self._injector is not None:
            s["injected_faults"] = dict(self._injector.fired)
            s["fault_checks"] = dict(self._injector.calls)
        if self._sched is not None:
            s["request_status"] = dict(self._sched.status)
            s["admitted_at"] = dict(self._sched.admitted_at)
            s["packed_rows"] = self._call_stats["packed_rows"]
            s["real_rows"] = self._call_stats["real_rows"]
            s["steps"] = dict(self._call_stats["steps"])
        return s

    # ---- jitted device loops ----
    def _gen_fn(self, params, prompts, cache, key, real_len, max_new_tokens: int):
        """Prefill + full decode loop as one device program → tokens [B, T].

        `prompts` may be padded past the real prompt to a power-of-two
        bucket; `real_len` (traced i32 scalar) is the shared true length —
        prefill_lm masks the padding steps, so the bucket only decides
        which compiled program runs, never the result.

        Early-EOS masking: once a sequence has emitted eos_id, subsequent
        positions emit eos_id (the decode steps still run — a lax.scan has
        static trip count — but their tokens are masked in the output)."""
        b, _ = prompts.shape
        logits, cache = prefill_lm(
            params, prompts, cache, self.mc,
            lengths=jnp.full((b,), real_len, jnp.int32),
        )
        pos0 = jnp.full((b,), real_len, jnp.int32)
        done0 = jnp.zeros((b,), bool)
        eos = self.sc.eos_id

        def body(carry, k_i):
            logits, cache, pos, done = carry
            tok = sample_token(logits, k_i, self.sc)
            if eos >= 0:
                emit = jnp.where(done, jnp.int32(eos), tok)
                done = jnp.logical_or(done, tok == eos)
            else:
                emit = tok
            logits, cache = self.api.decode_step(params, cache, tok, pos, self.mc)
            return (logits, cache, pos + 1, done), emit

        keys = jax.random.split(key, max_new_tokens)
        _, toks = jax.lax.scan(body, (logits, cache, pos0, done0), keys)
        return toks.T  # [B, T]

    def _chunk_fn(self, params, cache, tok, pos, key, n: int):
        """`n` decode+sample steps as one device program (continuous batching)."""

        def body(carry, k_i):
            cache, tok, pos = carry
            logits, cache = self.api.decode_step(params, cache, tok, pos, self.mc)
            nxt = sample_token(logits, k_i, self.sc)
            return (cache, nxt, pos + 1), nxt

        keys = jax.random.split(key, n)
        (cache, tok, pos), toks = jax.lax.scan(body, (cache, tok, pos), keys)
        return cache, tok, pos, toks  # toks [n, B]

    def _mixed_fn(self, params, cache, tokens, seq_ids, positions, kv_len,
                  last_rows, key, block_q: int):
        """ONE mixed prefill/decode step (DESIGN.md §3.5): the packed
        varlen forward over the whole stack + sampling at each emitting
        sequence's last row. Retraces only per packed-length bucket.
        `block_q` is the packer's alignment granularity (static)."""
        logits, cache = forward_packed(
            params, tokens, seq_ids, positions, kv_len, cache, self.mc,
            last_rows, block_q=block_q,
        )
        return cache, sample_token(logits, key, self.sc)

    def _verify_fn(self, params, cache, tokens, seq_ids, positions, kv_len,
                   rows, draft_toks, block_q: int):
        """ONE speculative verify step (DESIGN.md §3.9): the packed varlen
        forward with logits read at EVERY verify row, plus the on-device
        longest-accepted-prefix rule — all inside the jitted step, so a
        speculative round costs exactly one host sync.

        `rows` [B, R]: each verify segment's pack rows (row 0 is the
        committed pending token, rows 1..n its draft chain; −1 pads — a
        prefill-final segment uses only row 0). `draft_toks` [B, R−1]
        (−1 = no draft) may live on device (DraftModel proposals never
        visit the host): they are scattered into the pack's placeholder
        token rows here, before the forward. Returns (cache, [B, R+1]):
        the target's greedy token at every row, with the accepted-draft
        count appended as the last column (split host-side after the one
        sync). Greedy only — acceptance compares drafts against argmax,
        which makes the committed stream token-identical to
        non-speculative greedy decoding by construction."""
        t = tokens.shape[0]
        dr = rows[:, 1:]
        # clamp proposals into the real vocab: an out-of-range id would
        # embed as NaN (jnp.take fills OOB gathers) and poison the whole
        # packed step through the masked accumulation. Acceptance below
        # compares against the CLAMPED id — the rule is "accept iff the
        # token actually fed equals the previous row's argmax", so output
        # stays token-identical whatever a (vocab-mismatched, buggy,
        # adversarial) draft proposes. Negatives stay −1 = no draft.
        dt = jnp.minimum(draft_toks, self.mc.vocab_size - 1)
        ok = (dr >= 0) & (dt >= 0)
        # out-of-bounds index (t) + mode="drop" skips masked entries
        # (−1 would WRAP to the last row)
        idx = jnp.where(ok, dr, t)
        vals = jnp.where(ok, dt, 0).astype(tokens.dtype)
        tokens = tokens.at[idx.reshape(-1)].set(
            vals.reshape(-1), mode="drop"
        )
        logits, cache = forward_packed(
            params, tokens, seq_ids, positions, kv_len, cache, self.mc,
            rows, block_q=block_q,
        )  # [B, R, Vpad]
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, R]
        match = (g[:, :-1] == dt) & (dt >= 0)
        n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
        return cache, jnp.concatenate([g, n_acc[:, None]], axis=1)

    # ---- single-prompt-batch generation (prefill + n decode steps) ----
    def generate(self, prompts: np.ndarray, max_new_tokens: int) -> np.ndarray:
        """prompts [B, S_prompt] int32 (right-aligned, no padding support in
        this minimal path) → generated tokens [B, max_new_tokens].

        Both the prompt length and the decode-step count are bucketed to
        powers of two (excess steps run masked, excess output is sliced
        off), so repeated calls at drifting lengths reuse O(log max_len)
        compiled programs."""
        b, s = prompts.shape
        if s + max_new_tokens > self.sc.max_len:
            raise ValueError(
                f"prompt {s} + {max_new_tokens} exceeds max_len {self.sc.max_len}"
            )
        from repro.kernels.tuning import bucket_pow2  # lazy: no cycle

        sb = self._bucket(s)
        nb = bucket_pow2(max_new_tokens, lo=1)
        padded = np.zeros((b, sb), np.int32)
        padded[:, :s] = prompts
        with self._scope():
            cache = self.api.init_cache(b, self.sc.max_len, self.mc)
            self._key, k = jax.random.split(self._key)
            toks = self._gen(
                self.params, jnp.asarray(padded), cache, k, jnp.int32(s),
                int(nb),
            )
        return self._to_host(toks)[:, :max_new_tokens]

    # ---- continuous batching over a request queue ----
    def serve(self, requests: Sequence[Union[np.ndarray, Request]],
              max_new_tokens: int,
              priorities: Optional[Sequence[int]] = None,
              deadlines: Optional[Sequence[Optional[float]]] = None,
              ) -> List[np.ndarray]:
        """Each request: 1-D prompt array (or a `Request` carrying resume
        state, e.g. from a snapshot). Returns generated arrays, in order —
        one per request, ALWAYS: a FAILED or EXPIRED request's entry holds
        whatever it generated before going terminal (`stats()
        ["request_status"]` tells them apart).

        `priorities` (optional, higher = more urgent, default all-0 FIFO)
        steer admission order and — with `ServeConfig.preemption` — let a
        high-priority arrival preempt a lower-priority victim.
        `deadlines` (seconds from enqueue, None = none; default from
        `ServeConfig.deadline_s`) cancel overdue requests exactly like
        EOS.

        Routing: `step_mode="mixed"` (and a packed-capable stack) runs the
        chunked-prefill mixed varlen loop; otherwise the paged or
        contiguous sequential loop. All three share the Scheduler's slot
        lifecycle and are token-identical under greedy sampling — with
        the prefix cache and preemption enabled or disabled."""
        with self._scope():
            if self._mixed_ok:
                return self._serve_mixed(
                    requests, max_new_tokens, priorities, deadlines
                )
            # fall back along the CONFIGURED memory model: a mixed request
            # on a non-packed-capable stack must not silently switch an
            # explicitly contiguous engine onto the page pool
            if self._page_layout is not None and self.sc.kv_layout == "paged":
                return self._serve_paged(
                    requests, max_new_tokens, priorities, deadlines
                )
            return self._serve_impl(
                requests, max_new_tokens, priorities, deadlines
            )

    def _check_len(self, rid: int, n_prompt: int, max_new_tokens: int) -> None:
        if n_prompt + max_new_tokens > self.sc.max_len:
            raise ValueError(
                f"request {rid}: prompt {n_prompt} + {max_new_tokens}"
                f" exceeds max_len {self.sc.max_len}"
            )

    def _set_tbl_row(self, cache, slot: int, table: List[int]):
        """Mirror one slot's allocator block table into every layer's
        device `tbl` leaf (zero-padded: unmapped logical pages point at
        the garbage page). Shared by the paged and mixed loops."""
        row = np.zeros((self._page_layout.pages_per_seq,), np.int32)
        row[: len(table)] = table
        row_j = jnp.asarray(row)
        return _map_paged(cache, tbl=lambda x: x.at[:, slot].set(row_j[None]))

    def _prefill_bucketed(self, prompt: np.ndarray, cache, *, start_pos: int = 0):
        """Prefill `prompt[start_pos:]` into a batch-1 cache view with the
        token axis padded to a power-of-two bucket (prefill_lm masks the
        padding rows), so distinct prompt lengths share compiled programs."""
        tail = np.asarray(prompt[start_pos:])
        n = len(tail)
        nb = self._bucket(n)
        padded = np.zeros((1, nb), np.int32)
        padded[0, :n] = tail
        return self._prefill(
            self.params, jnp.asarray(padded), cache,
            jnp.int32(start_pos), jnp.asarray([n], jnp.int32),
        )

    # ---- contiguous continuous batching ----
    def _serve_impl(self, requests, max_new_tokens: int,
                    priorities=None, deadlines=None) -> List[np.ndarray]:
        b = self.sc.max_batch
        sched = self._make_sched(requests, max_new_tokens, priorities,
                                 deadlines)
        cache = self.api.init_cache(b, self.sc.max_len, self.mc)
        tok = jnp.zeros((b,), jnp.int32)
        pos = jnp.zeros((b,), jnp.int32)
        chunk_n = max(1, min(self.sc.decode_chunk, max_new_tokens))

        def _write_slot(c, o, slot):
            # caches are stacked [n_blocks, batch, ...]: batch is axis 1
            return c.at[:, slot].set(o[:, 0])

        def assign(slot: int):
            """Prefill the next queued request into `slot`. The prefill's
            sampled token is output token 0 (same as `generate`); a
            resumed request's effective prompt replays its pre-preemption
            tokens (recompute-on-resume). Requests that complete
            immediately are finalized and the next is taken. An injected
            fault is isolated to the request in hand: it re-queues (or
            goes FAILED) and the next head is tried — the live neighbors
            never notice."""
            nonlocal cache, tok, pos
            while (req := sched.take_head()) is not None:
                toks = req.tokens
                self._check_len(req.rid, len(req.prompt), max_new_tokens)
                try:
                    self._inj("kernel_dispatch", req.rid)
                    one_cache = self.api.init_cache(1, self.sc.max_len, self.mc)
                    logits, one_cache = self._prefill_bucketed(toks, one_cache)
                    self._inj("device_step", req.rid)
                    self._key, k = jax.random.split(self._key)
                    t0 = int(self._sync(
                        sample_token(logits, k, self.sc), rid=req.rid
                    )[0])
                except InjectedFault as e:
                    self._note_fault(e)
                    sched.retry_request(req)
                    continue
                self._clear_fault_streak()
                if not sched.admit_request(slot, req, t0):
                    continue
                cache = jax.tree.map(
                    lambda c, o: _write_slot(c, o, slot), cache, one_cache
                )
                tok = tok.at[slot].set(t0)
                pos = pos.at[slot].set(len(toks))
                return

        def preempt_for_priority():
            """A queued request of strictly higher priority than a live
            slot evicts that slot (lowest-priority, youngest first): the
            victim re-queues with recompute-on-resume, the arrival takes
            its place. Slot-array pressure is the contiguous engine's
            analogue of page pressure."""
            if not self.sc.preemption:
                return
            while (req := sched.head()) is not None and sched.free_slot() is None:
                v = sched.victim_slot(below=req.priority)
                if v is None:
                    return
                sched.preempt(v)
                assign(v)

        def refill():
            for s in range(b):
                if not sched.slots[s].live:
                    assign(s)
            preempt_for_priority()

        try:
            refill()
            self.peak_active = sched.note_peak()
            while sched.has_active() or sched.queue:
                for s in sched.expire_overdue():
                    sched.retire(s)  # the slot's cache region just goes stale
                if not sched.has_active():
                    if not self._await_backoff(sched):
                        break
                    refill()
                    continue
                self._key, k = jax.random.split(self._key)
                try:
                    self._inj("kernel_dispatch")
                    cache2, tok2, pos2, toks = self._chunk(
                        self.params, cache, tok, pos, k, chunk_n
                    )
                    self._inj("device_step")
                    toks_np = self._sync(toks)  # one sync per chunk
                except InjectedFault as e:
                    # discard the uncommitted step and retry it — exact,
                    # because committed host state never advanced
                    self._on_step_fault(sched, e, sched.fault_slot)
                    continue
                self._clear_fault_streak()
                cache, tok, pos = cache2, tok2, pos2
                for s in sched.absorb_chunk(toks_np):
                    sched.retire(s)
                    assign(s)  # refill overwrites the slot's cache / tok / pos
                preempt_for_priority()
                self.peak_active = sched.note_peak()
        except Exception:
            # crash recovery: fold live slots back into the queue so a
            # snapshot() sees every unfinished request (contiguous KV has
            # no engine-lifetime state to preserve)
            for s, sl in enumerate(sched.slots):
                if sl.live:
                    sched.preempt(s)
            raise
        self._finish_serve(sched)
        return sched.results_list()

    # ---- paged-pool shared machinery (DESIGN.md §3.4 + §3.6) ----
    def _paged_state(self):
        """Engine-lifetime paged state: allocator (radix tree included)
        and the device page pool, created lazily and reused across
        serve() calls so cached prefixes persist between request batches."""
        if self._alloc is None:
            from repro.kernels.tuning import choose_cache_policy
            from repro.runtime.kvcache import CachePolicy, PagedKVAllocator

            lay = self._page_layout
            if self._cache_on:
                policy = choose_cache_policy(
                    lay.n_pages, lay.page_size,
                    min_free_pages=(
                        None if self.sc.cache_min_free_pages < 0
                        else self.sc.cache_min_free_pages
                    ),
                    max_cached_pages=(
                        None if self.sc.cache_max_pages < 0
                        else self.sc.cache_max_pages
                    ),
                )
            else:
                policy = CachePolicy(max_cached_pages=0)
            self._alloc = PagedKVAllocator(
                lay.n_pages, lay.page_size, cache_policy=policy
            )
            self._paged_cache = self.api.init_cache(
                self.sc.max_batch, self.sc.max_len, self.mc,
                layout="paged", page_size=lay.page_size, n_pages=lay.n_pages,
                kv_dtype=self.sc.kv_dtype,
            )
        return self._alloc, self._paged_cache

    def _recover_paged(self, sched: Scheduler, alloc, ctx: _PoolCtx) -> None:
        """Crash recovery (DESIGN.md §3.7): roll every live slot back into
        the queue — pages donated, their KV is committed state — free any
        orphaned admissions, zero every device table row, and KEEP the
        allocator, radix tree, and page pool. The pre-PR-6 behavior
        (dropping the whole pool) killed every in-flight neighbor of one
        poisoned request and restarted the cache cold; now the escaping
        exception still reports the crash, but a retry — or a
        `snapshot()`/`restore()`d successor — resumes warm, and the next
        serve() admits against an intact pool (`alloc.check()` holds)."""
        for s, sl in enumerate(sched.slots):
            if sl.live and s in ctx.seq_of:
                try:
                    self._pool_preempt(sched, alloc, ctx, s)
                except Exception:  # unstructured damage: sweep below
                    ctx.seq_of.pop(s, None)
            elif sl.live:
                sched.preempt(s)  # admitted to the slot but not the pool
        # sequences admitted but never slot-bound (crash mid-admission)
        alloc.reset_live()
        # every slot is dead now: park all table rows on the garbage page
        for s in range(len(sched.slots)):
            ctx.cache = self._set_tbl_row(ctx.cache, s, [])
        self._paged_cache = ctx.cache

    def _pool_fault_slot(self, sched: Scheduler, alloc, ctx: _PoolCtx,
                         s: int) -> None:
        """Fault-retry rollback of a live slot: the same memory motion as
        `_pool_preempt` (donate the valid-KV pages, zero the table row)
        but the requeue is charged against the request's retry budget —
        and goes terminal-FAILED when that budget is out."""
        stream = sched.slots[s].cache_tokens()
        seq = ctx.seq_of.pop(s)
        ctx.inserted.discard(s)
        sched.fault_slot(s)
        if self._cache_on:
            alloc.donate(seq, stream)
        else:
            alloc.free(seq)
        ctx.cache = self._set_tbl_row(ctx.cache, s, [])

    def _copy_pages(self, cache, cows):
        if not cows:
            return cache
        # one jitted gather-scatter for ALL owed copies per leaf, with
        # the pool buffer donated: XLA updates the pages in place
        # instead of rewriting a pool-sized array per CowCopy
        srcs = jnp.asarray([cw.src for cw in cows], jnp.int32)
        dsts = jnp.asarray([cw.dst for cw in cows], jnp.int32)
        return _map_paged(cache, pool=lambda x: _copy_pool_pages(x, srcs, dsts))

    def _pool_retire(self, sched: Scheduler, alloc, ctx: _PoolCtx, s: int) -> None:
        """Retire a finished slot: donate its clean token stream's pages
        to the radix tree (or plain-free them with the cache off) and
        point the dead slot's table row at the garbage page before the
        freed pages can be reassigned."""
        stream = sched.slots[s].cache_tokens()
        seq = ctx.seq_of.pop(s)
        ctx.inserted.discard(s)
        if self._cache_on:
            alloc.donate(seq, stream)
        else:
            alloc.free(seq)
        sched.retire(s)
        ctx.cache = self._set_tbl_row(ctx.cache, s, [])

    def _pool_preempt(self, sched: Scheduler, alloc, ctx: _PoolCtx, s: int) -> None:
        """Victim preemption: donate the slot's pages (a resumed match
        makes recompute-on-resume nearly free — FLASH-D's (O, Λ) carry
        needs no state beyond the cached pages to continue from a page
        boundary) and re-queue the request."""
        stream = sched.slots[s].cache_tokens()
        seq = ctx.seq_of.pop(s)
        ctx.inserted.discard(s)
        sched.preempt(s)
        if self._cache_on:
            alloc.donate(seq, stream)
        else:
            alloc.free(seq)
        ctx.cache = self._set_tbl_row(ctx.cache, s, [])

    def _pool_grow(self, sched: Scheduler, alloc, ctx: _PoolCtx, s: int,
                   want: int) -> bool:
        """Materialize pages so slot `s` can write up to `want` positions,
        preempting victims under page pressure (optimistic per-chunk
        allocation). Returns False when `s` itself was the victim."""
        from repro.runtime.kvcache import PageError

        while True:
            seq = ctx.seq_of[s]
            before = len(alloc.table(seq))
            try:
                cows = alloc.extend(seq, want)
            except PageError:
                v = sched.victim_slot() if self.sc.preemption else None
                if v is None or sched.active_count() == 1:
                    raise
                self._pool_preempt(sched, alloc, ctx, v)
                if v == s:
                    return False
                continue
            ctx.cache = self._copy_pages(ctx.cache, cows)
            if cows or len(alloc.table(seq)) != before:
                ctx.cache = self._set_tbl_row(ctx.cache, s, alloc.table(seq))
            return True

    def _pool_reserve(self, req: Request, max_new_tokens: int,
                      chunk_n: int) -> int:
        """Admission reservation: just the prompt under preemption
        (optimistic per-chunk allocation, DESIGN.md §3.6) or the worst
        case (prompt + remaining new tokens + speculative chunk slack,
        clamped to max_len — writes past it hit the garbage page) without.
        With speculative decoding the slack must also cover a full K+1-row
        verify segment: rejected rows return their pages to the seq's
        reservation (`alloc.rollback`), so the worst case never compounds
        across rounds — one verify's overhang is enough."""
        n = len(req.tokens)
        if self.sc.preemption:
            return n
        slack = max(chunk_n, self._spec.k + 1 if self._spec else 0)
        remaining = max_new_tokens - len(req.out)
        return min(n + remaining + slack, self.sc.max_len)

    def _pool_match(self, alloc, toks: np.ndarray):
        """Radix lookup for an admission, capped so ≥ 1 token prefills."""
        if not self._cache_on:
            return None
        return alloc.match_prefix(toks, max_tokens=len(toks) - 1)

    def _preempting_could_admit(self, sched: Scheduler, alloc, ctx: _PoolCtx,
                                req: Request, reserve: int, cached) -> bool:
        """Upper bound on admission-pressure preemption: even rolling back
        EVERY strictly-lower-priority victim frees at most their table
        pages — if that still cannot cover the arrival, preempting would
        discard running work for nothing, so the head waits instead."""
        from repro.runtime.kvcache import pages_for

        need = pages_for(reserve, alloc.page_size)
        if cached is not None:
            need -= len(cached.pages)
        bound = alloc.free_pages + alloc.evictable_pages
        for s, sl in enumerate(sched.slots):
            if sl.live and sl.priority < req.priority:
                bound += len(alloc.table(ctx.seq_of[s]))
        return need <= bound

    def _note_admission(self, toks, cached) -> None:
        self._stats["prefix_lookups"] += 1
        self._stats["prompt_tokens"] += len(toks)
        if cached is not None and cached.n_tokens > 0:
            self._stats["prefix_hits"] += 1
            self._stats["hit_tokens"] += cached.n_tokens

    # ---- speculative decoding rounds (DESIGN.md §3.9) ----
    def _plan_grown(self, sched: Scheduler, alloc, ctx: _PoolCtx,
                    budget: int, pchunk: int, drafts=None) -> StepPlan:
        """Plan a packed step and materialize its pages; any slot
        rollback — victim preemption, growth-fault requeue, or a retry
        budget running out — invalidates the plan (a dead slot's segment
        must not dispatch), so re-plan until a whole growth pass stays
        stable. `drafts` adds speculative draft rows (plan_step funds
        them from leftover budget only)."""
        while True:
            plan = sched.plan_step(budget, pchunk, drafts=drafts)
            r0 = sched.rollbacks
            for seg in plan.segments:
                end = min(seg.start + len(seg.tokens), self.sc.max_len)
                try:
                    if end > alloc.seq_len(ctx.seq_of[seg.slot]):
                        self._inj("page_alloc", sched.slots[seg.slot].rid)
                        self._pool_grow(sched, alloc, ctx, seg.slot, end)
                except InjectedFault as e:
                    self._note_fault(e)
                    self._pool_fault_slot(sched, alloc, ctx, seg.slot)
                if sched.rollbacks != r0:
                    break
            if sched.rollbacks == r0:
                return plan

    def _spec_round(self, sched: Scheduler, alloc, ctx: _PoolCtx, *,
                    budget: int, pchunk: int, block_q: int) -> List[int]:
        """One speculative serving round: draft-propose K tokens per
        decoding slot, verify them ALL (plus any prefill chunks in
        flight) in ONE packed varlen dispatch, commit the longest
        accepted prefix of each chain, and roll rejected rows' pages back
        through the allocator. One host sync per round, exactly like a
        plain mixed step — acceptance is pure throughput.

        Memory soundness (DESIGN.md §3.9): `commit` leaves each slot's
        `kv` at its accepted length, so `alloc.rollback(seq, kv)` frees
        every page wholly past it — those pages are never donated to the
        radix tree, and every retirement/donation path reads the stream
        truncated to `kv`, so cached bytes stay a pure function of the
        committed token stream (prefix caching and the int8 slot-0 scale
        rule both survive speculation). Stale rejected KV inside the
        boundary page sits at positions ≥ kv_len — masked by every
        kernel, and overwritten by the next round's writes before any row
        can attend to it."""
        from repro.kernels.tuning import bucket_pow2, padded_rows
        from repro.serve.speculative import DraftModel

        spec = self._spec
        K, R = spec.k, spec.k + 1
        b = self.sc.max_batch
        # 1. per-slot draft quota, deadline-clamped (the expire_overdue
        #    bugfix: deadlines are only checked BETWEEN steps, so the
        #    quota shrinks near one instead of overshooting it by K rows)
        quota = {
            s: sched.draft_quota(s, K, max_len=self.sc.max_len,
                                 per_row_s=spec.row_ewma)
            for s, sl in enumerate(sched.slots)
            if sl.live and not sl.prefilling
        }
        # 2. propose
        dev_drafts = None
        drafts: Dict[int, np.ndarray] = {}
        if isinstance(spec.draft, DraftModel):
            spec.draft.sync(sched)
            dev_drafts = spec.draft.propose(sched, K)  # [B, K], on device
            # placeholder rows — the verify jit scatters the device ids
            drafts = {s: np.zeros((q,), np.int32)
                      for s, q in quota.items() if q > 0}
        else:
            for s, q in quota.items():
                if q <= 0:
                    continue
                sl = sched.slots[s]
                stream = np.concatenate([
                    np.asarray(sl.prompt, np.int64),
                    np.asarray(sl.out[sl.resumed:], np.int64),
                ])
                prop = np.asarray(spec.draft(sl.rid, stream, q), np.int32)
                if len(prop):
                    drafts[s] = prop[:q]
        # 3. plan + grow (re-plan on any slot rollback)
        plan = self._plan_grown(sched, alloc, ctx, budget, pchunk,
                                drafts=drafts)
        if not plan.segments:
            return []
        # 4. pack + ONE verify dispatch + ONE sync
        t0 = time.monotonic()
        off, spans = 0, []
        for seg in plan.segments:
            spans.append(off)
            off += padded_rows(len(seg.tokens), block_q)
        total = bucket_pow2(max(off, 1), lo=block_q)
        tokens = np.zeros((total,), np.int32)
        seq_ids = np.full((total,), -1, np.int32)
        positions = np.full((total,), -1, np.int32)
        kv_len = np.zeros((b,), np.int32)
        rows = np.full((b, R), -1, np.int32)
        dmat = np.full((b, K), -1, np.int32)
        for seg, o in zip(plan.segments, spans):
            n = len(seg.tokens)
            tokens[o:o + n] = seg.tokens
            seq_ids[o:o + n] = seg.slot
            positions[o:o + n] = np.arange(seg.start, seg.start + n)
            kv_len[seg.slot] = seg.start + n
            if not seg.emits:
                continue
            if sched.slots[seg.slot].prefilling:
                rows[seg.slot, 0] = o + n - 1  # prefill-final: last row only
            else:
                rows[seg.slot, :n] = np.arange(o, o + n)
                dmat[seg.slot, :n - 1] = seg.tokens[1:]
        draft_arg = (
            jnp.where(jnp.asarray(dmat) >= 0, dev_drafts, -1)
            if dev_drafts is not None else jnp.asarray(dmat)
        )
        self._inj("kernel_dispatch")
        cache2, out = self._verify(
            self.params, ctx.cache,
            jnp.asarray(tokens), jnp.asarray(seq_ids),
            jnp.asarray(positions), jnp.asarray(kv_len),
            jnp.asarray(rows), draft_arg, block_q,
        )
        self._inj("device_step")
        out_np = self._sync(out)  # one sync per speculative round
        # commit the device cache only past the sync: a step fault above
        # discards the round entirely, so its retry is exact
        ctx.cache = cache2
        g, n_acc = out_np[:, :R], out_np[:, R]
        # 5. commit the accepted prefixes, then roll the allocator back
        #    past every rejected row (freed, never donated)
        finished = sched.commit(plan, g, n_acc=n_acc)
        if isinstance(spec.draft, DraftModel):
            spec.draft.committed(sched)
        for seg in plan.segments:
            sl = sched.slots[seg.slot]
            if not sl.live or seg.slot not in ctx.seq_of:
                continue
            seq = ctx.seq_of[seg.slot]
            if alloc.seq_len(seq) > sl.kv:
                alloc.rollback(seq, sl.kv)
        per_row = (time.monotonic() - t0) / max(plan.n_tokens, 1)
        spec.row_ewma = (per_row if spec.row_ewma is None
                         else 0.7 * spec.row_ewma + 0.3 * per_row)
        return finished

    # ---- paged continuous batching (DESIGN.md §3.4 + §3.6) ----
    def _serve_paged(self, requests, max_new_tokens: int,
                     priorities=None, deadlines=None) -> List[np.ndarray]:
        """Sequential continuous batching over a page-pool KV cache.

        Differences from the contiguous loop:

          * admission is by FREE PAGES, not slot count: with preemption, a
            request is admitted as soon as its PROMPT fits (growth is
            optimistic and backed by victim preemption); without, the
            worst case is reserved up front and a blocked head waits for
            frees. Priority order is respected either way, and a
            higher-priority arrival may preempt a lower-priority victim;
          * prompts walk the radix prefix cache: matched full pages are
            aliased into the block table and only the tail is prefilled
            (`start_pos`), so a warm shared prefix costs O(new tokens);
          * before every chunk the allocator materializes pages covering
            the chunk's writes (preempting under pressure) and the engine
            mirrors grown block tables to the device; finished slots
            donate their pages to the cache and point their table row at
            the garbage page, so lockstep speculative writes from dead
            slots stay harmless.
        """
        from repro.runtime.kvcache import PageError, pages_for

        lay = self._page_layout
        page = lay.page_size
        b = self.sc.max_batch
        sched = self._make_sched(requests, max_new_tokens, priorities,
                                 deadlines)
        alloc, cache0 = self._paged_state()
        ctx = _PoolCtx(cache0)
        tok = jnp.zeros((b,), jnp.int32)
        pos = jnp.zeros((b,), jnp.int32)
        chunk_n = max(1, min(self.sc.decode_chunk, max_new_tokens))
        spec_block_q = spec_budget = 0
        if self._spec is not None:
            from repro.kernels.tuning import bucket_pow2, choose_varlen_blocks

            spec_budget = b * (self._spec.k + 1)
            spec_block_q = choose_varlen_blocks(
                bucket_pow2(spec_budget, lo=8),
                self.mc.head_dim_, self.mc.head_dim_,
                group=self.mc.n_heads // self.mc.n_kv_heads,
                kv_heads=self.mc.n_kv_heads, page=page,
                segment_hint=self._spec.k + 1,
            ).block_q

        def assign(slot: int) -> bool:
            """Admit the highest-priority queued request into `slot` if
            the pool can cover it (evicting cached pages, then preempting
            strictly-lower-priority victims, as needed). Returns False
            (and leaves the queue intact) when it cannot — the request
            waits. Head-of-line order within the priority order is
            preserved: later requests never jump a blocked head."""
            nonlocal tok, pos
            while (req := sched.head()) is not None:
                toks = req.tokens
                n = len(toks)
                self._check_len(req.rid, len(req.prompt), max_new_tokens)
                reserve = self._pool_reserve(req, max_new_tokens, chunk_n)
                cached = self._pool_match(alloc, toks)
                if not alloc.can_admit(reserve, cached=cached):
                    if self.sc.preemption and self._preempting_could_admit(
                        sched, alloc, ctx, req, reserve, cached
                    ) and (
                        v := sched.victim_slot(below=req.priority)
                    ) is not None:
                        self._pool_preempt(sched, alloc, ctx, v)
                        continue  # re-match: donation may extend the prefix
                    if sched.has_active():
                        return False  # live sequences will free pages
                    raise PageError(
                        f"request {req.rid} needs {pages_for(reserve, page)}"
                        f" pages but the pool holds {lay.n_pages - 1}"
                    )
                sched.take_head()
                try:
                    self._inj("page_alloc", req.rid)
                except InjectedFault as e:
                    # fault isolation: only the request in hand rolls back
                    self._note_fault(e)
                    sched.retry_request(req)
                    continue
                seq = self._seq_base
                self._seq_base += 1
                alloc.admit(seq, prompt_len=n, reserve_tokens=reserve,
                            cached=cached)
                start = cached.n_tokens if cached is not None else 0
                ctx.cache = self._set_tbl_row(ctx.cache, slot, alloc.table(seq))
                try:
                    self._inj("kernel_dispatch", req.rid)
                    # tail-only prefill: cached pages already hold [0, start)
                    view = _map_paged(
                        ctx.cache, batch=lambda x: x[:, slot:slot + 1]
                    )
                    logits, view = self._prefill_bucketed(
                        toks, view, start_pos=start
                    )
                    self._inj("device_step", req.rid)
                    ctx.cache = _map_paged(
                        ctx.cache, view,
                        pool=lambda x, o: o,  # updated pool (slot's pages only)
                        batch=lambda x, o: x.at[:, slot].set(o[:, 0]),
                    )
                    self._key, k = jax.random.split(self._key)
                    t0 = int(self._sync(
                        sample_token(logits, k, self.sc), rid=req.rid
                    )[0])
                except InjectedFault as e:
                    # the faulted prefill may have left garbage KV in the
                    # pages — FREE them (donating would poison the radix
                    # tree's content addressing), zero the row, retry
                    self._note_fault(e)
                    alloc.free(seq)
                    ctx.cache = self._set_tbl_row(ctx.cache, slot, [])
                    sched.retry_request(req)
                    continue
                self._clear_fault_streak()
                self._note_admission(toks, cached)
                if not sched.admit_request(slot, req, t0):
                    # finished on its first token: its pages already hold
                    # the whole prompt's KV — donate them
                    if self._cache_on:
                        alloc.donate(seq, toks)
                    else:
                        alloc.free(seq)
                    ctx.cache = self._set_tbl_row(ctx.cache, slot, [])
                    continue
                ctx.seq_of[slot] = seq
                if self._cache_on:  # index the live prompt (its KV is valid now)
                    alloc.insert(seq, toks)
                    ctx.inserted.add(slot)
                tok = tok.at[slot].set(t0)
                pos = pos.at[slot].set(n)
                return True
            return False

        def refill():
            for s in range(b):
                if not sched.slots[s].live and sched.head() is not None:
                    if not assign(s):
                        break
            if not self.sc.preemption:
                return
            # a higher-priority arrival may evict a lower-priority victim
            while (req := sched.head()) is not None and sched.free_slot() is None:
                v = sched.victim_slot(below=req.priority)
                if v is None:
                    return
                self._pool_preempt(sched, alloc, ctx, v)
                if not assign(v):
                    return

        try:
            refill()
            self.peak_active = sched.note_peak()
            while sched.has_active() or sched.queue:
                for s in sched.expire_overdue():
                    self._pool_retire(sched, alloc, ctx, s)
                if not sched.has_active():
                    if not self._await_backoff(sched):
                        break
                    refill()
                    self.peak_active = sched.note_peak()
                    continue
                if self._spec is not None:
                    # speculative round replaces the per-token chunk loop:
                    # growth happens inside _plan_grown, per-slot
                    try:
                        finished = self._spec_round(
                            sched, alloc, ctx, budget=spec_budget,
                            pchunk=1, block_q=spec_block_q,
                        )
                    except InjectedFault as e:
                        self._on_step_fault(
                            sched, e,
                            lambda v: self._pool_fault_slot(
                                sched, alloc, ctx, v
                            ),
                        )
                        continue
                    self._clear_fault_streak()
                    for s in finished:
                        self._pool_retire(sched, alloc, ctx, s)
                    refill()
                    self.peak_active = sched.note_peak()
                    continue
                # materialize pages for this chunk's writes (clamped to
                # max_len: the table is ⌈max_len/page⌉ wide and writes past
                # it clamp to the garbage page in _paged_attn_step). A
                # growth fault is rid-scoped: only that slot rolls back.
                for s in range(b):
                    sl = sched.slots[s]
                    if not sl.live:
                        continue
                    try:
                        self._inj("page_alloc", sl.rid)
                        self._pool_grow(
                            sched, alloc, ctx, s,
                            min(sl.kv + chunk_n, self.sc.max_len),
                        )
                    except InjectedFault as e:
                        self._note_fault(e)
                        self._pool_fault_slot(sched, alloc, ctx, s)
                if not sched.has_active():
                    continue
                self._key, k = jax.random.split(self._key)
                try:
                    self._inj("kernel_dispatch")
                    cache2, tok2, pos2, toks = self._chunk(
                        self.params, ctx.cache, tok, pos, k, chunk_n
                    )
                    self._inj("device_step")
                    toks_np = self._sync(toks)  # one sync per chunk
                except InjectedFault as e:
                    # discard the uncommitted step and retry it — exact,
                    # because committed host state never advanced
                    self._on_step_fault(
                        sched, e,
                        lambda v: self._pool_fault_slot(sched, alloc, ctx, v),
                    )
                    continue
                self._clear_fault_streak()
                ctx.cache, tok, pos = cache2, tok2, pos2
                for s in sched.absorb_chunk(toks_np):
                    self._pool_retire(sched, alloc, ctx, s)
                refill()
                self.peak_active = sched.note_peak()
        except Exception:
            self._recover_paged(sched, alloc, ctx)
            raise
        self._paged_cache = ctx.cache
        self._finish_serve(sched)
        return sched.results_list()

    # ---- mixed varlen continuous batching (DESIGN.md §3.5 + §3.6) ----
    def _serve_mixed(self, requests, max_new_tokens: int,
                     priorities=None, deadlines=None) -> List[np.ndarray]:
        """Chunked-prefill continuous batching: ONE jitted packed varlen
        step per iteration, carrying every decoding slot's pending token
        and the next prefill chunks of admitted prompts.

        vs. the sequential loops: a newly admitted long prompt no longer
        runs a whole-prompt prefill dispatch that stalls every decoding
        sequence — its prompt drips in `prefill_chunk`-token pieces
        interleaved with decode rows, so time-to-first-token of everything
        behind it drops (BENCH_serve.json tracks this). Iterations with NO
        prefill in flight take the decode fast path instead: the same
        jitted `decode_chunk`-token loop as the sequential engines (one
        dispatch + one sync per chunk, not per token), so steady-state
        decode throughput is the sequential engine's — the packed step
        only pays its per-step sync while it is actually buying prefill
        interleaving. Admission is by free pages like `_serve_paged`, and
        the radix prefix cache applies here too: chunked prefill starts at
        the first UNCACHED token (`fed0`), so a warm shared prefix skips
        its chunks entirely."""
        from repro.kernels.tuning import (
            bucket_pow2, choose_varlen_blocks, padded_rows,
        )
        from repro.runtime.kvcache import PageError, pages_for

        lay = self._page_layout
        page = lay.page_size
        b = self.sc.max_batch
        sched = self._make_sched(requests, max_new_tokens, priorities,
                                 deadlines)
        alloc, cache0 = self._paged_state()
        ctx = _PoolCtx(cache0)
        budget = self.sc.token_budget or (b + self.sc.prefill_chunk)
        if self._spec is not None and not self.sc.token_budget:
            # default budget must fund every slot's K+1-row verify chain
            # on top of a prefill chunk, or drafts would never be planned
            budget = b * (self._spec.k + 1) + self.sc.prefill_chunk
        pchunk = max(1, min(self.sc.prefill_chunk, budget))
        chunk_n = max(1, min(self.sc.decode_chunk, max_new_tokens))
        hd = self.mc.head_dim_
        # segment hint: with >1 slot the pack mixes 1-token decode rows
        # into every prefill step, and each pads to block_q — keep the
        # tile at the sublane minimum; a lone slot packs one prefill
        # chunk per step, so the chunk itself is the segment. With
        # speculation on, decode segments are (K+1)-row verify chains.
        seg_hint = (self._spec.k + 1 if self._spec is not None
                    else (1 if b > 1 else pchunk))
        block_q = choose_varlen_blocks(
            bucket_pow2(budget, lo=8), hd, hd,
            group=self.mc.n_heads // self.mc.n_kv_heads,
            kv_heads=self.mc.n_kv_heads, page=page,
            segment_hint=seg_hint,
        ).block_q

        def try_admit():
            while (req := sched.head()) is not None:
                slot = sched.free_slot()
                if slot is None:
                    if self.sc.preemption and (
                        v := sched.victim_slot(below=req.priority)
                    ) is not None:
                        self._pool_preempt(sched, alloc, ctx, v)
                        slot = v
                    else:
                        return
                toks = req.tokens
                n = len(toks)
                self._check_len(req.rid, len(req.prompt), max_new_tokens)
                reserve = self._pool_reserve(req, max_new_tokens, chunk_n)
                cached = self._pool_match(alloc, toks)
                if not alloc.can_admit(reserve, cached=cached):
                    if self.sc.preemption and self._preempting_could_admit(
                        sched, alloc, ctx, req, reserve, cached
                    ) and (
                        v := sched.victim_slot(below=req.priority)
                    ) is not None:
                        self._pool_preempt(sched, alloc, ctx, v)
                        continue  # re-match: donation may extend the prefix
                    if sched.has_active():
                        return  # live sequences will free pages
                    raise PageError(
                        f"request {req.rid} needs {pages_for(reserve, page)}"
                        f" pages but the pool holds {lay.n_pages - 1}"
                    )
                sched.take_head()
                try:
                    self._inj("page_alloc", req.rid)
                except InjectedFault as e:
                    # fault isolation: only the request in hand rolls back
                    self._note_fault(e)
                    sched.retry_request(req)
                    continue
                seq = self._seq_base
                self._seq_base += 1
                alloc.admit(seq, prompt_len=n, reserve_tokens=reserve,
                            cached=cached)
                self._note_admission(toks, cached)
                fed0 = cached.n_tokens if cached is not None else 0
                ctx.cache = self._set_tbl_row(ctx.cache, slot, alloc.table(seq))
                sched.admit_request_prefilling(slot, req, fed0=fed0)
                ctx.seq_of[slot] = seq

        def note_prefilled():
            """Index prompts whose prefill just completed (their pages
            hold valid KV from here on) so concurrent admissions match."""
            if not self._cache_on:
                return
            for s, sl in enumerate(sched.slots):
                if sl.live and not sl.prefilling and s not in ctx.inserted:
                    alloc.insert(ctx.seq_of[s], sl.prompt)
                    ctx.inserted.add(s)

        def pack(plan: StepPlan):
            """Pack the plan into flat block_q-aligned device arrays
            (bucketed to a power of two); also returns the packed row
            count and how many of those rows hold a token."""
            off = 0
            spans = []
            for seg in plan.segments:
                spans.append(off)
                off += padded_rows(len(seg.tokens), block_q)
            total = bucket_pow2(max(off, 1), lo=block_q)
            tokens = np.zeros((total,), np.int32)
            seq_ids = np.full((total,), -1, np.int32)
            positions = np.full((total,), -1, np.int32)
            kv_len = np.zeros((b,), np.int32)
            last_rows = np.full((b,), -1, np.int32)
            for seg, o in zip(plan.segments, spans):
                n = len(seg.tokens)
                tokens[o:o + n] = seg.tokens
                seq_ids[o:o + n] = seg.slot
                positions[o:o + n] = np.arange(seg.start, seg.start + n)
                kv_len[seg.slot] = seg.start + n
                if seg.emits:
                    last_rows[seg.slot] = o + n - 1
            arrays = tuple(jnp.asarray(a) for a in
                           (tokens, seq_ids, positions, kv_len, last_rows))
            return arrays, total, plan.n_tokens

        def mixed_step(step) -> List[int]:
            """One packed varlen step: plan, pack, dispatch, sync, commit."""
            with TraceAnnotation("repro.serve.plan"):
                plan = self._plan_grown(sched, alloc, ctx, budget, pchunk)
            if not plan.segments:
                return []
            with TraceAnnotation("repro.serve.pack"):
                arrays, rows, real = pack(plan)
            step.set_metadata(rows=rows, real_rows=real)
            self._call_stats["packed_rows"] += rows
            self._call_stats["real_rows"] += real
            with TraceAnnotation("repro.serve.dispatch"):
                self._key, k = jax.random.split(self._key)
                self._inj("kernel_dispatch")
                cache2, toks = self._mixed(self.params, ctx.cache, *arrays,
                                           k, block_q)
                self._inj("device_step")
            with TraceAnnotation("repro.serve.sync"):
                toks_np = self._sync(toks)  # one sync per mixed step
            # commit the device cache only past the sync: a step fault
            # above discards the step entirely, so its retry is exact
            ctx.cache = cache2
            with TraceAnnotation("repro.serve.commit"):
                return sched.commit(plan, toks_np)

        def decode_chunk_phase(step) -> List[int]:
            """No prefill in flight: the sequential engines' jitted
            multi-token decode loop (one dispatch + one sync per
            `decode_chunk` tokens). Device tok/pos are rebuilt from the
            scheduler's host state, so packed steps and chunk phases
            interleave freely; dead slots carry zeroed table rows, so
            their lockstep writes land on the garbage page. Growth faults
            are rid-scoped (only that slot rolls back; a batch they empty
            has nothing to step); dispatch/sync faults are step-wide and
            propagate to the caller's retry handler."""
            with TraceAnnotation("repro.serve.plan"):
                for s in range(b):
                    sl = sched.slots[s]
                    if not sl.live:
                        continue
                    try:
                        self._inj("page_alloc", sl.rid)
                        self._pool_grow(sched, alloc, ctx, s,
                                        min(sl.kv + chunk_n, self.sc.max_len))
                    except InjectedFault as e:
                        self._note_fault(e)
                        self._pool_fault_slot(sched, alloc, ctx, s)
            if not sched.has_active():
                return []
            with TraceAnnotation("repro.serve.pack"):
                tok = jnp.asarray([sl.pending for sl in sched.slots], jnp.int32)
                pos = jnp.asarray([sl.kv for sl in sched.slots], jnp.int32)
            # the chunk computes every slot, live or not, chunk_n times
            step.set_metadata(rows=b * chunk_n,
                              real_rows=sched.active_count() * chunk_n)
            with TraceAnnotation("repro.serve.dispatch"):
                self._key, k = jax.random.split(self._key)
                self._inj("kernel_dispatch")
                cache2, _, _, toks = self._chunk(
                    self.params, ctx.cache, tok, pos, k, chunk_n
                )
                self._inj("device_step")
            with TraceAnnotation("repro.serve.sync"):
                toks_np = self._sync(toks)  # one sync per chunk
            ctx.cache = cache2  # commit past the sync (see mixed_step)
            with TraceAnnotation("repro.serve.commit"):
                return sched.absorb_chunk(toks_np)

        def admit(finished=()):
            """Index finished prefills, retire finished slots, admit."""
            with TraceAnnotation("repro.serve.admit"):
                note_prefilled()
                for s in finished:
                    self._pool_retire(sched, alloc, ctx, s)
                try_admit()
                self.peak_active = sched.note_peak()

        try:
            admit()
            while sched.has_active() or sched.queue:
                for s in sched.expire_overdue():
                    self._pool_retire(sched, alloc, ctx, s)
                if not sched.has_active():
                    with TraceAnnotation("repro.serve.idle"):
                        more = self._await_backoff(sched)
                    if not more:
                        break
                    admit()
                    continue
                # speculative rounds subsume both phases: verify chains
                # AND prefill chunks ride one packed step
                kind = ("spec" if self._spec is not None else
                        "mixed" if any(sl.prefilling for sl in sched.slots)
                        else "chunk")
                steps = self._call_stats["steps"]
                steps[kind] = steps.get(kind, 0) + 1
                with TraceAnnotation("repro.serve.step", kind=kind,
                                     live=sched.active_count()) as step:
                    try:
                        if kind == "spec":
                            finished = self._spec_round(
                                sched, alloc, ctx, budget=budget,
                                pchunk=pchunk, block_q=block_q,
                            )
                        elif kind == "chunk":
                            finished = decode_chunk_phase(step)
                        else:
                            finished = mixed_step(step)
                    except InjectedFault as e:
                        # discard the uncommitted step and retry it — exact,
                        # because committed host state never advanced
                        self._on_step_fault(
                            sched, e,
                            lambda v: self._pool_fault_slot(sched, alloc, ctx, v),
                        )
                        continue
                    self._clear_fault_streak()
                    admit(finished)
        except Exception:
            self._recover_paged(sched, alloc, ctx)
            raise
        self._paged_cache = ctx.cache
        self._finish_serve(sched)
        return sched.results_list()

    # ---- crash recovery: serve-state snapshot / restore (DESIGN.md §3.7) ----
    def snapshot(self, ckpt_dir: str, *, step: int = 0) -> str:
        """Serialize the last serve() call's surviving state as a
        metadata-only checkpoint (runtime/checkpoint.py, `tree=None`): the
        scheduler's unfinished requests (live slots fold in exactly like a
        preemption — prompt + tokens generated so far), finished results
        and statuses, the radix cache's content as token chains, and the
        pool geometry. No KV arrays are saved: FLASH-D's (O, Λ) carry
        makes KV a pure function of the token stream, so `restore()`
        recomputes it exactly. Call after a crash (serve() folds live
        slots into the queue before re-raising) or between serves."""
        from repro.runtime import checkpoint as ckpt

        sched = self._sched
        if sched is None:
            raise RuntimeError("snapshot() before any serve()")
        now = sched.now()
        pending = [
            Request(rid=sl.rid, prompt=np.asarray(sl.orig_prompt),
                    out=list(sl.out), priority=sl.priority,
                    deadline=sl.deadline, retries=sl.retries)
            for sl in sched.slots if sl.live
        ] + list(sched.queue)
        state = {
            "pending": sorted((
                {"rid": int(r.rid),
                 "prompt": np.asarray(r.prompt).astype(int).tolist(),
                 "out": [int(t) for t in r.out],
                 "priority": int(r.priority),
                 # deadlines persist as REMAINING seconds: the restored
                 # scheduler's clock starts at zero
                 "deadline": (max(0.0, float(r.deadline) - now)
                              if r.deadline is not None else None),
                 # retry-backoff gates rebase the same way — a snapshot
                 # taken mid-backoff restores with the REMAINING backoff,
                 # not a stale absolute clock value
                 "not_before": max(0.0, float(r.not_before) - now),
                 "retries": int(r.retries)}
                for r in pending), key=lambda d: d["rid"]),
            "done": {str(i): np.asarray(r).astype(int).tolist()
                     for i, r in enumerate(sched.results) if r is not None},
            "status": {str(k): v for k, v in sched.status.items()},
            "max_new_tokens": int(sched.max_new_tokens),
            "seq_base": int(self._seq_base),
            "chains": (self._alloc.cached_chains()
                       if self._alloc is not None and self._cache_on else []),
            "pool": ({"page_size": self._page_layout.page_size,
                      "n_pages": self._page_layout.n_pages}
                     if self._page_layout is not None else None),
        }
        return ckpt.save(ckpt_dir, step, None, extra={"engine_serve": state})

    def restore(self, ckpt_dir: str, *, step: Optional[int] = None) -> dict:
        """Load a `snapshot()` into THIS engine (typically a fresh one
        after a crash): stashes the pending requests for `resume()` and
        re-warms the radix prefix cache by replaying the snapshot's token
        chains through prefill — recompute, not array restore, so the
        rebuilt pages are exact. Chains are only replayed onto a matching
        pool geometry (same page_size). Returns the raw state dict."""
        from repro.runtime import checkpoint as ckpt

        _, extra = ckpt.restore(ckpt_dir, None, step=step)
        state = extra["engine_serve"]
        self._seq_base = max(self._seq_base, int(state.get("seq_base", 0)))
        pool = state.get("pool")
        chains = state.get("chains") or []
        if (chains and self._cache_on and self._page_layout is not None
                and pool is not None
                and int(pool["page_size"]) == self._page_layout.page_size):
            with self._scope():
                self._rewarm(chains)
        self._resume_state = state
        return state

    def resume(self) -> Dict[int, np.ndarray]:
        """Finish the restored snapshot's pending requests (one serve()
        call, deadlines/retry budgets carried over) and return ALL results
        keyed by the ORIGINAL request ids — already-finished requests come
        straight from the snapshot. Token-identical to the uninterrupted
        run: resumed requests re-enter through recompute-on-resume."""
        state = self._resume_state
        if state is None:
            raise RuntimeError("resume() before restore()")
        self._resume_state = None
        done = {int(k): np.asarray(v, np.int32)
                for k, v in state["done"].items()}
        pending = state["pending"]
        if pending:
            reqs = [Request(rid=i, prompt=np.asarray(p["prompt"], np.int32),
                            out=list(p["out"]), priority=int(p["priority"]),
                            deadline=p["deadline"], retries=int(p["retries"]),
                            not_before=float(p.get("not_before", 0.0)))
                    for i, p in enumerate(pending)]
            outs = self.serve(reqs, int(state["max_new_tokens"]),
                              deadlines=[p["deadline"] for p in pending])
            for p, o in zip(pending, outs):
                done[int(p["rid"])] = o
        return done

    def _rewarm(self, chains: List[List[int]]) -> None:
        """Replay radix-tree token chains into the (fresh) page pool:
        admit a scratch sequence over batch slot 0, prefill the chain's
        full pages, donate them back to the tree. Longest chains first so
        shorter ones ride their cached prefixes; chains that no longer fit
        (smaller pool) are skipped — the cache is a performance artifact,
        not correctness state."""
        alloc, cache = self._paged_state()
        page = self._page_layout.page_size
        cap = (self.sc.max_len // page) * page
        for chain in sorted(chains, key=len, reverse=True):
            toks = np.asarray(chain[:cap], np.int32)
            n = (len(toks) // page) * page
            if n < page:
                continue
            toks = toks[:n]
            m = alloc.match_prefix(toks)
            if m.n_tokens >= n:
                continue  # covered by a longer chain's replay
            cached = m if m.n_tokens > 0 else None
            if not alloc.can_admit(n, cached=cached):
                continue
            seq = self._seq_base
            self._seq_base += 1
            alloc.admit(seq, prompt_len=n, reserve_tokens=n, cached=cached)
            start = cached.n_tokens if cached is not None else 0
            cache = self._set_tbl_row(cache, 0, alloc.table(seq))
            view = _map_paged(cache, batch=lambda x: x[:, 0:1])
            _, view = self._prefill_bucketed(toks, view, start_pos=start)
            cache = _map_paged(
                cache, view,
                pool=lambda x, o: o,
                batch=lambda x, o: x.at[:, 0].set(o[:, 0]),
            )
            alloc.donate(seq, toks)
            cache = self._set_tbl_row(cache, 0, [])
        self._paged_cache = cache
