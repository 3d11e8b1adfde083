"""Serving launcher: load (or init) weights, run the batched engine.

    PYTHONPATH=src python -m repro.launch.serve --arch paper-llama \
        --requests 6 --max-new-tokens 16

Per-request time-to-first-token is printed for EVERY step mode (the
scheduler tracks it per request id from enqueue to first token, so a
priority-swapped or preempted request reports the waiting time it really
accrued), and the paged engines print the prefix-cache / preemption
counters from `Engine.stats()` (DESIGN.md §3.6).

Fault tolerance (DESIGN.md §3.7): `--fault-rate`/`--fault-seed` turn on
deterministic chaos injection, `--deadline-ms`/`--max-retries` set the
per-request lifecycle budgets, and every request's terminal status
(done / failed / expired) is printed with the retry/downgrade counters.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro import configs
from repro.launch.cache import enable_compile_cache
from repro.models import get_model
from repro.runtime import checkpoint as ckpt
from repro.serve import Engine, ServeConfig


def _parse_priorities(spec, n_requests):
    """--priorities "2,0,1,..." (1:1 with requests) or "mixed" (alternate
    two classes — a quick way to see preemptive scheduling act)."""
    if spec is None:
        return None
    if spec == "mixed":
        return [i % 2 for i in range(n_requests)]
    prios = [int(x) for x in spec.split(",")]
    if len(prios) != n_requests:
        raise SystemExit(
            f"--priorities lists {len(prios)} values for {n_requests} requests"
        )
    return prios


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="paper-llama")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--attn-impl", default=None)
    p.add_argument("--ckpt-dir", default=None, help="restore trained weights")
    p.add_argument("--requests", type=int, default=6)
    p.add_argument("--prompt-len", type=int, default=12)
    p.add_argument("--max-new-tokens", type=int, default=16)
    p.add_argument("--max-batch", type=int, default=4)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--kv-layout", choices=("contiguous", "paged"),
                   default="contiguous",
                   help="paged: page-pool KV, admission by free pages "
                        "(DESIGN.md §3.4)")
    p.add_argument("--page-size", type=int, default=0,
                   help="tokens per KV page (0 → tuned)")
    p.add_argument("--kv-pool-tokens", type=int, default=0,
                   help="paged pool size in tokens (0 → max_batch·max_len)")
    p.add_argument("--kv-dtype", default="",
                   help='quantize the paged KV pool: "int8" (or "fp8") '
                        'stores pages at 1 B/elem with a per-page scale '
                        'side-band — ~4x '
                        'the tokens per byte of HBM (DESIGN.md §3.8); '
                        'requires --kv-layout paged; "" → native dtype')
    p.add_argument("--step-mode", choices=("sequential", "mixed"),
                   default="sequential",
                   help="mixed: chunked-prefill continuous batching — one "
                        "packed varlen step per iteration (DESIGN.md §3.5)")
    p.add_argument("--token-budget", type=int, default=0,
                   help="packed tokens per mixed step (0 → heuristic)")
    p.add_argument("--prefill-chunk", type=int, default=16,
                   help="max prompt tokens one sequence feeds per mixed step")
    p.add_argument("--shared-prefix-len", type=int, default=0,
                   help="prepend a shared system prompt of this many tokens "
                        "to every request (exercises the radix prefix "
                        "cache, DESIGN.md §3.6)")
    p.add_argument("--priorities", default=None,
                   help='comma-separated ints (1:1 with requests) or '
                        '"mixed" — higher value is served first and may '
                        'preempt lower (DESIGN.md §3.6)')
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable the radix prefix cache")
    p.add_argument("--no-preemption", action="store_true",
                   help="worst-case reservation admission instead of "
                        "optimistic allocation + preemption")
    p.add_argument("--deadline-ms", type=float, default=0.0,
                   help="per-request deadline in milliseconds (0 → none); "
                        "overdue requests are cancelled like EOS with "
                        "status 'expired' (DESIGN.md §3.7)")
    p.add_argument("--max-retries", type=int, default=3,
                   help="fault-retry budget per request before it goes "
                        "terminal-FAILED (DESIGN.md §3.7)")
    p.add_argument("--fault-rate", type=float, default=0.0,
                   help="chaos injection: probability each fault-site "
                        "check fires (0 → no injection)")
    p.add_argument("--fault-seed", type=int, default=0,
                   help="seed for the deterministic fault injector")
    p.add_argument("--spec-tokens", type=int, default=0,
                   help="speculative decoding: draft tokens verified per "
                        "round through one packed varlen dispatch "
                        "(DESIGN.md §3.9); 0 → off. Greedy only; needs "
                        "--kv-layout paged or --step-mode mixed")
    p.add_argument("--draft-config", default="qwen3-0.6b",
                   help="architecture of the draft model proposing spec "
                        "tokens (smoke config under --smoke; randomly "
                        "initialized unless the checkpoint provides it)")
    p.add_argument("--no-spec", action="store_true",
                   help="force speculation off even if --spec-tokens is "
                        "set (quick A/B against the same command line)")
    args = p.parse_args(argv)

    enable_compile_cache()
    cfg = configs.get_smoke_config(args.arch) if args.smoke else configs.get_config(args.arch)
    if args.attn_impl:
        cfg = dataclasses.replace(cfg, attn_impl=args.attn_impl)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(args.seed), cfg)
    if args.ckpt_dir:
        from repro.train.train_step import TrainConfig, init_train_state

        template = init_train_state(jax.random.PRNGKey(args.seed), cfg, TrainConfig())
        state, _ = ckpt.restore(args.ckpt_dir, template)
        params = state.params
        print(f"restored weights from {args.ckpt_dir}")

    spec_tokens = 0 if args.no_spec else args.spec_tokens
    draft = None
    if spec_tokens > 0:
        # a randomly initialized draft still exercises the whole verify /
        # rollback path (its proposals mostly get rejected — output stays
        # token-identical by construction); real deployments restore
        # trained draft weights here
        dcfg = (configs.get_smoke_config(args.draft_config) if args.smoke
                else configs.get_config(args.draft_config))
        dparams = get_model(dcfg).init(jax.random.PRNGKey(args.seed + 1), dcfg)
        draft = (dparams, dcfg)
        print(f"speculative decoding: draft={args.draft_config} "
              f"k={spec_tokens}")

    eng = Engine(params, cfg, ServeConfig(
        max_batch=args.max_batch,
        max_len=args.shared_prefix_len + args.prompt_len + args.max_new_tokens + 8,
        temperature=args.temperature,
        seed=args.seed,
        kv_layout=args.kv_layout,
        page_size=args.page_size,
        kv_pool_tokens=args.kv_pool_tokens,
        kv_dtype=args.kv_dtype,
        step_mode=args.step_mode,
        token_budget=args.token_budget,
        prefill_chunk=args.prefill_chunk,
        prefix_cache=not args.no_prefix_cache,
        preemption=not args.no_preemption,
        max_retries=args.max_retries,
        deadline_s=args.deadline_ms / 1e3,
        fault_rate=args.fault_rate,
        fault_seed=args.fault_seed,
        spec_tokens=spec_tokens,
    ), draft=draft)
    rng = np.random.default_rng(args.seed)
    shared = rng.integers(
        0, cfg.vocab_size, (args.shared_prefix_len,)
    ).astype(np.int32)
    reqs = [
        np.concatenate([
            shared,
            rng.integers(0, cfg.vocab_size, (args.prompt_len,)).astype(np.int32),
        ])
        for _ in range(args.requests)
    ]
    priorities = _parse_priorities(args.priorities, len(reqs))
    t0 = time.time()
    outs = eng.serve(reqs, max_new_tokens=args.max_new_tokens,
                     priorities=priorities)
    dt = time.time() - t0
    total_tokens = sum(len(o) for o in outs)
    status = eng.stats()["request_status"]
    for i, o in enumerate(outs):
        print(f"request {i} [{status.get(i, '?'):>7}]: {o.tolist()}")
    layout = "paged pool" if eng._page_layout is not None else "contiguous slots"
    mode = "mixed varlen steps" if eng._mixed_ok else "sequential chunks"
    print(f"{total_tokens} tokens in {dt:.2f}s → {total_tokens/dt:.1f} tok/s "
          f"(batched decode over {args.max_batch} slots, {layout}, {mode}, "
          f"peak {eng.peak_active} concurrent)")
    if eng.ttft:  # every step mode reports per-request TTFT
        print("time-to-first-token (enqueue → first token, per request):")
        for rid in sorted(eng.ttft):
            prio = f" prio={priorities[rid]}" if priorities is not None else ""
            print(f"  request {rid}:{prio} {eng.ttft[rid]*1e3:8.1f} ms")
        ttft = [eng.ttft[r] for r in sorted(eng.ttft)]
        print(f"  mean {np.mean(ttft)*1e3:.1f} ms, max {np.max(ttft)*1e3:.1f} ms")
    st = eng.stats()
    if "kv_pool_bytes" in st:
        print(f"kv pool: {st['kv_dtype']}, "
              f"{st['kv_pool_bytes'] / 1024:.1f} KiB "
              f"({st['kv_bytes_per_token']:.0f} B/token)")
    if st["prefix_cache_enabled"] or st["preemption_enabled"]:
        print(f"serving core: prefix-cache hit rate "
              f"{100 * st['hit_rate']:.1f}% "
              f"({st['hit_tokens']}/{st['prompt_tokens']} prompt tokens, "
              f"{st.get('cached_pages', 0)} pages retained), "
              f"{st['preemptions']} preemptions, "
              f"{st.get('evictions', 0)} evictions")
    if args.fault_rate > 0 or args.deadline_ms > 0 or st["retried"]:
        n_done = sum(s == "done" for s in status.values())
        print(f"fault tolerance: {n_done}/{len(outs)} done, "
              f"{st['failed']} failed, {st['expired']} expired, "
              f"{st['retried']} retries, {st['downgrades']} downgrades "
              f"(impl now {st['attn_impl']}), "
              f"faults fired {st.get('injected_faults', {})}")
    if st.get("spec_enabled"):
        print(f"speculation: acceptance {100 * st['spec_acceptance_rate']:.1f}% "
              f"({st['spec_accepted']}/{st['spec_drafted']} drafts, "
              f"{st['spec_rejected']} rejected), "
              f"{st['spec_mean_accepted']:.2f} accepted/round "
              f"over {st['spec_rounds']} rounds")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
