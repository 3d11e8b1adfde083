"""Kernel micro-benchmarks — the paper's "same performance" claim.

CPU-only. Every number here is a host wall-time or a count on the CPU
backend (Pallas in interpret mode); none is a device metric. Do not run it
on a TPU: the ring bench starts a child process after this one has
initialised JAX, and a child cannot reach a chip its parent holds. The
on-chip check of the same paths is `chip_smoke.py`.

The paper's hardware comparison holds THROUGHPUT EQUAL (same pipelined
latency, same dataflow) and wins on area/power. The software analogues
measured here:

  1. wall-time of the tiled FLASH-D vs FA2 vs naive softmax attention
     (jit-compiled jnp on this host — same asymptotic work is the claim;
     Pallas interpret mode is a Python emulator, so TPU wall-times are
     out of scope for this container and come from the roofline instead);
  2. compiled HLO flops/bytes of each impl at equal shapes (XLA's view of
     the datapath — FLASH-D must not add work);
  3. skip-mode wall-time effect at a concentration-heavy input;
  4. the decode fast path: fused vs unfused split-K kernel and the jitted
     scan engine vs the per-token host loop (the seed serving path).

  5. context parallelism on a simulated 8-device host mesh (subprocess —
     this process must keep its single device): ring prefill vs the
     replicated single-device baseline, and cp_decode, in tokens/sec.

Besides the CSV `report` contract, this module emits machine-readable
``BENCH_prefill.json`` / ``BENCH_decode.json`` / ``BENCH_ring.json`` (into
$BENCH_DIR, default cwd) so the perf trajectory is tracked across PRs. Set
BENCH_SMOKE=1 for CI-sized shapes.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.attention import MaskSpec, flash_attention


def _bench(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _emit_json(filename: str, payload: dict) -> None:
    path = os.path.join(os.environ.get("BENCH_DIR", "."), filename)
    payload = {
        "backend": jax.devices()[0].platform,
        "smoke": bool(os.environ.get("BENCH_SMOKE")),
        **payload,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)


def run(report):
    smoke = bool(os.environ.get("BENCH_SMOKE"))
    prefill_rows = []
    shapes = [
        ("train-ish", 2, 512, 8, 64),
        ("prefill-ish", 1, 2048, 4, 64),
    ]
    if smoke:
        shapes = [("train-ish", 1, 128, 2, 32)]
    for name, b, s, h, d in shapes:
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)

        results = {}
        for impl in ("flashd", "fa2", "naive"):
            f = jax.jit(
                lambda q, k, v, impl=impl: flash_attention(
                    q, k, v, mask=MaskSpec("causal"), impl=impl,
                    block_q=128, block_k=128,
                )
            )
            us = _bench(f, q, k, v)
            c = f.lower(q, k, v).compile()
            ca = c.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            results[impl] = (us, float(ca.get("flops", 0)))
            report(f"kernel_{name}_{impl}", us, f"hlo_flops={results[impl][1]:.3e}")
            prefill_rows.append({
                "name": name, "impl": impl, "batch": b, "seq": s,
                "heads": h, "head_dim": d, "us_per_call": us,
                "hlo_flops": results[impl][1],
            })
        ratio = results["flashd"][0] / results["fa2"][0]
        report(
            f"kernel_{name}_flashd_vs_fa2", ratio,
            f"wall-time ratio (paper: parity; <1 is a win) "
            f"flop_ratio={results['flashd'][1]/max(results['fa2'][1],1):.3f}",
        )

    # skip-mode effect on a concentration-heavy input (post-trained attn is
    # concentrated; emulate with scaled scores)
    b, s, h, d = 1, 1024, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32) * 4.0
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
    for skip in (False, True):
        f = jax.jit(
            lambda q, k, v, skip=skip: flash_attention(
                q, k, v, mask=MaskSpec("causal"), impl="flashd",
                block_q=64, block_k=64, skip=skip,
            )
        )
        us = _bench(f, q, k, v)
        report(f"kernel_skip_{'on' if skip else 'off'}", us,
               "jnp path computes the predicate only; true FLOP skip is the "
               "Pallas @pl.when path (TPU)")

    _emit_json("BENCH_prefill.json", {"rows": prefill_rows})
    _emit_json("BENCH_decode.json", _bench_decode(report, smoke))
    _emit_json("BENCH_paged.json", _bench_paged(report, smoke))
    _emit_json("BENCH_serve.json", _bench_serve(report, smoke))
    _emit_json("BENCH_spec.json", _bench_spec(report, smoke))
    _emit_json("BENCH_prefix.json", _bench_prefix(report, smoke))
    _emit_json("BENCH_chaos.json", _bench_chaos(report, smoke))
    _emit_json("BENCH_train.json", _bench_train(report, smoke))
    _emit_json("BENCH_quant.json", _bench_quant(report, smoke))
    _emit_json("BENCH_ring.json", _bench_ring(report, smoke))


def _bench_train(report, smoke: bool) -> dict:
    """Training with the FLASH-D fwd+bwd pair (DESIGN.md §6).

    Two asserted bars:

    1. **throughput** — full jitted train step (value_and_grad + AdamW)
       with `attn_impl="flashd"` (the tiled custom_vjp pair, algorithmic
       mirror of the Pallas kernels) vs `"xla"` (plain softmax attention
       with no custom_vjp — XLA saves the [S,S] probs for the backward,
       the seed-era baseline). At S where the [S,S] residuals hurt, the
       recompute-from-(q,k,Λ) backward must win: flashd tokens/s ≥ xla.
       (Pallas interpret mode is a Python emulator — TPU wall-times are
       out of scope for this container, so the fused pair's own bar is
       the jnp mirror, same policy as the kernel bars above.)

    2. **goodput under chaos** — `train_resilient` at 0% / 10% train-site
       fault injection: goodput = committed steps / total step executions
       (replays after a restart are the waste). Asserted: 1.0 at rate 0,
       ≥ 0.5 at 10%, and the final loss BITWISE identical across rates —
       chaos costs throughput, never correctness.
    """
    import dataclasses as _dc
    import tempfile as _tf

    from repro.configs import paper_llama
    from repro.data import DataConfig, SyntheticLM
    from repro.runtime.resilience import FaultInjector
    from repro.train import (
        ResilienceConfig, TrainConfig, init_train_state, make_train_step,
        train_resilient,
    )

    out: dict = {"throughput": {}, "goodput": {}}

    # ---- 1. train-step throughput: flashd pair vs xla baseline ----
    S = 512 if smoke else 1024
    B = 2

    def tok_per_s(impl):
        cfg = _dc.replace(
            paper_llama.CONFIG, n_layers=2, d_model=128, n_heads=4,
            n_kv_heads=2, d_ff=256, head_dim=32, vocab_size=256,
            vocab_pad_multiple=64, attn_impl=impl,
        )
        tc = TrainConfig(warmup_steps=2, total_steps=100)
        data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=S,
                                      global_batch=B))
        state = init_train_state(jax.random.PRNGKey(0), cfg, tc)
        step = jax.jit(make_train_step(cfg, tc))
        batch = jax.tree.map(jnp.asarray, data.batch(0))
        state, m = step(state, batch)
        jax.block_until_ready(m["loss"])  # compile + warm
        best = float("inf")
        for i in range(5):
            batch = jax.tree.map(jnp.asarray, data.batch(i + 1))
            t0 = time.perf_counter()
            state, m = step(state, batch)
            jax.block_until_ready(m["loss"])
            best = min(best, time.perf_counter() - t0)
        return B * S / best

    tok = {impl: tok_per_s(impl) for impl in ("flashd", "xla")}
    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:  # the fused pair's real wall-time bar — TPU only
        tok["flashd_pallas"] = tok_per_s("flashd_pallas")
    out["throughput"] = {
        "shape": {"batch": B, "seq_len": S, "d_model": 128, "n_layers": 2},
        "tokens_per_sec": tok,
        "flashd_over_xla": tok["flashd"] / tok["xla"],
        "pallas_measured": on_tpu,
    }
    for impl, t in tok.items():
        report(f"train_step_{impl}_tok_per_s", t, f"B={B} S={S}")
    report("train_flashd_over_xla", tok["flashd"] / tok["xla"],
           "fused-pair mirror vs [S,S]-residual baseline (≥1 target)")
    floor = 0.9 if smoke else 1.0  # smoke shape's margin is thin on CPU
    assert tok["flashd"] >= floor * tok["xla"], tok

    # ---- 2. goodput under train-site fault injection ----
    cfg = _dc.replace(
        paper_llama.CONFIG, n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
        d_ff=64, head_dim=16, vocab_size=64, vocab_pad_multiple=64,
    )
    tc = TrainConfig(warmup_steps=2, total_steps=50)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                  global_batch=4))
    total = 12 if smoke else 24
    final_loss = {}
    for rate in (0.0, 0.10):
        inj = (FaultInjector(rate, seed=7, sites=FaultInjector.TRAIN_SITES)
               if rate > 0 else None)
        executions = [0]
        with _tf.TemporaryDirectory() as d:
            t0 = time.perf_counter()
            state, hist, ctr = train_resilient(
                ckpt_dir=d, model_cfg=cfg, train_cfg=tc, data=data,
                total_steps=total,
                res=ResilienceConfig(ckpt_every=3, max_restarts=1000),
                injector=inj,
                on_step=lambda s, m, c: executions.__setitem__(0, executions[0] + 1),
            )
            wall = time.perf_counter() - t0
        goodput = total / max(executions[0], total)
        final_loss[rate] = hist[-1]["loss"]
        out["goodput"][f"{rate:.2f}"] = {
            "goodput": goodput,
            "committed_steps": total,
            "step_executions": executions[0],
            "restarts": ctr["restarts"],
            "faults": ctr["faults"],
            "wall_s": wall,
            "final_loss": final_loss[rate],
        }
        report(f"train_chaos_rate{int(rate * 100):02d}_goodput", goodput,
               f"{ctr['restarts']} restarts, {ctr['faults']} faults")
    assert out["goodput"]["0.00"]["goodput"] == 1.0
    assert out["goodput"]["0.10"]["goodput"] >= 0.5, out["goodput"]
    assert final_loss[0.0] == final_loss[0.10], final_loss  # bitwise
    return out


_RING_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys, time
import jax, jax.numpy as jnp, numpy as np

smoke = bool(int(sys.argv[1]))
from repro.core.attention import MaskSpec, flash_attention
from repro.distributed.context import cp_decode, ring_prefill
from repro.kernels.tuning import choose_ring_schedule
from repro.launch.mesh import make_mesh

def bench(fn, iters=3):
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best

mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
b, s, h, d = (1, 256, 2, 32) if smoke else (1, 2048, 4, 64)
q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
out = {"n_devices": 8, "prefill": [], "decode": {}}
for kind, window in [("causal", 0), ("local", s // 4)]:
    mask = MaskSpec(kind, window=window)
    ring = jax.jit(lambda q, k, v, m=mask: ring_prefill(
        q, k, v, axis="data", mesh=mesh, mask=m, impl="flashd"))
    base = jax.jit(lambda q, k, v, m=mask: flash_attention(
        q, k, v, mask=m, impl="flashd"))
    t_ring = bench(lambda: ring(q, k, v))
    t_base = bench(lambda: base(q, k, v))
    sched = choose_ring_schedule(s // 8, s // 8, d, d, n_devices=8, mask=mask)
    out["prefill"].append({
        "mask": kind, "window": window, "batch": b, "seq": s, "heads": h,
        "head_dim": d, "live_hops": sched.n_hops,
        "tokens_per_sec_ring": b * s / t_ring,
        "tokens_per_sec_replicated": b * s / t_base,
    })

bd, S = (2, 256) if smoke else (2, 4096)
qd = jnp.asarray(rng.normal(size=(bd, h, d)), jnp.float32)
kc = jnp.asarray(rng.normal(size=(bd, S, h, d)), jnp.float32)
vc = jnp.asarray(rng.normal(size=(bd, S, h, d)), jnp.float32)
cl = jnp.full((bd,), S, jnp.int32)
cpd = jax.jit(lambda q, k, v, c: cp_decode(
    q, k, v, c, axis="data", mesh=mesh, use_kernel=False))
t_cp = bench(lambda: cpd(qd, kc, vc, cl))
out["decode"] = {"batch": bd, "cache_len": S, "heads": h, "head_dim": d,
                 "tokens_per_sec_cp": bd / t_cp}
print(json.dumps(out))
"""


def _bench_ring(report, smoke: bool) -> dict:
    """Ring context-parallel prefill/decode on a simulated 8-device mesh.

    Runs in a subprocess (XLA device count is fixed at first jax use, so
    this process cannot re-host 8 devices itself). Numbers are CPU-host
    relative — the tracked signal is ring-vs-replicated on equal shapes
    and the live-hop count, not absolute throughput."""
    res = subprocess.run(
        [sys.executable, "-c", _RING_PROG, "1" if smoke else "0"],
        capture_output=True, text=True, timeout=1200,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
             "PYTHONPATH": os.environ.get("PYTHONPATH", "src")},
    )
    if res.returncode != 0:
        # fail the job like the in-process benches do — a silent error blob
        # in BENCH_ring.json would erase the tracked perf signal unnoticed
        raise RuntimeError(f"ring bench subprocess failed:\n{res.stderr}")
    out = json.loads(res.stdout.strip().splitlines()[-1])
    for row in out["prefill"]:
        report(
            f"ring_prefill_{row['mask']}_tok_per_s", row["tokens_per_sec_ring"],
            f"replicated={row['tokens_per_sec_replicated']:.1f} "
            f"live_hops={row['live_hops']}/8 seq={row['seq']}",
        )
    report("cp_decode_tok_per_s", out["decode"]["tokens_per_sec_cp"],
           f"cache={out['decode']['cache_len']} b={out['decode']['batch']}")
    return out


def _bench_paged(report, smoke: bool) -> dict:
    """Paged KV cache (DESIGN.md §3.4): kernel overhead of the block-table
    indirection, and the serving-density win — peak concurrent sequences of
    the paged engine vs the contiguous engine at EQUAL KV memory budget.

    The contiguous engine commits max_len tokens per slot up front, so its
    concurrency is budget / max_len regardless of actual lengths; the paged
    engine admits by free pages, so short sequences pack the same budget
    ~(max_len / actual_len)× denser. The tracked signal is that ratio
    (≥ 1.5× is the acceptance bar; short-request workloads sit well above)."""
    from repro.kernels.flashd_decode import (
        flashd_decode_paged_pallas, flashd_decode_pallas,
    )

    out: dict = {"kernel": [], "engine": {}}
    interp = jax.devices()[0].platform != "tpu"

    # --- kernel: paged (block-table DMA gather) vs contiguous fused
    b, hq, hkv, d = (1, 2, 1, 16) if smoke else (2, 8, 2, 64)
    page, n_tbl = (16, 4) if smoke else (64, 8)
    s = page * n_tbl
    rng = np.random.default_rng(0)
    n_pool = b * n_tbl + 2
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pool, page, hkv, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pool, page, hkv, d)), jnp.float32)
    tbl = jnp.asarray(
        rng.permutation(np.arange(1, n_pool))[: b * n_tbl].reshape(b, n_tbl),
        jnp.int32,
    )
    cl = jnp.full((b,), s, jnp.int32)
    kc = jnp.moveaxis(kp[tbl], 3, 1).reshape(b, hkv, s, d)
    vc = jnp.moveaxis(vp[tbl], 3, 1).reshape(b, hkv, s, d)

    f_paged = jax.jit(lambda q, kp, vp, t, c: flashd_decode_paged_pallas(
        q, kp, vp, t, c, interpret=interp))
    f_cont = jax.jit(lambda q, k, v, c: flashd_decode_pallas(
        q, k, v, c, n_splits=n_tbl, fused=True, interpret=interp))
    us_paged = _bench(f_paged, q, kp, vp, tbl, cl)
    us_cont = _bench(f_cont, q, kc, vc, cl)
    report("decode_kernel_paged", us_paged, f"page={page} n_tbl={n_tbl}")
    report("decode_kernel_paged_vs_contiguous", us_paged / us_cont,
           "ratio (block-table indirection overhead; ~1 is the goal)")
    out["kernel"] = [
        {"variant": "paged", "batch": b, "heads": hq, "kv_heads": hkv,
         "cache_len": s, "head_dim": d, "page_size": page,
         "us_per_call": us_paged},
        {"variant": "contiguous_fused", "batch": b, "heads": hq,
         "kv_heads": hkv, "cache_len": s, "head_dim": d,
         "n_splits": n_tbl, "us_per_call": us_cont},
    ]

    # --- engine: concurrent sequences at equal KV memory budget
    from repro.configs import paper_llama
    from repro.models import get_model
    from repro.serve import Engine, ServeConfig

    cfg = dataclasses.replace(
        paper_llama.CONFIG, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, head_dim=16, vocab_size=128, vocab_pad_multiple=64,
    )
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    slots, max_len = (2, 64) if smoke else (4, 256)
    budget_tokens = slots * max_len  # what the contiguous engine commits
    n_req, p_len, n_new = (8, 4, 6) if smoke else (16, 8, 16)
    reqs = [np.random.default_rng(i).integers(0, cfg.vocab_size, (p_len,))
            .astype(np.int32) for i in range(n_req)]

    eng_c = Engine(params, cfg, ServeConfig(
        max_batch=slots, max_len=max_len, temperature=0.0))
    t0 = time.perf_counter()
    outs_c = eng_c.serve(reqs, n_new)
    t_cont = time.perf_counter() - t0

    eng_p = Engine(params, cfg, ServeConfig(
        max_batch=4 * slots, max_len=max_len, temperature=0.0,
        kv_layout="paged", page_size=16, kv_pool_tokens=budget_tokens))
    t0 = time.perf_counter()
    outs_p = eng_p.serve(reqs, n_new)
    t_paged = time.perf_counter() - t0
    assert all(np.array_equal(a, c) for a, c in zip(outs_c, outs_p))

    ratio = eng_p.peak_active / max(eng_c.peak_active, 1)
    report("serve_concurrency_contiguous", eng_c.peak_active,
           f"budget={budget_tokens} tokens, max_len={max_len}")
    report("serve_concurrency_paged", eng_p.peak_active,
           f"same budget, page=16, reqs of ~{p_len}+{n_new} tokens")
    report("serve_concurrency_ratio", ratio, "paged/contiguous (≥1.5 target)")
    out["engine"] = {
        "kv_budget_tokens": budget_tokens, "max_len": max_len,
        "request_prompt_len": p_len, "new_tokens": n_new,
        "n_requests": n_req,
        "concurrent_contiguous": eng_c.peak_active,
        "concurrent_paged": eng_p.peak_active,
        "concurrency_ratio": ratio,
        "wall_s_contiguous": t_cont, "wall_s_paged": t_paged,
    }
    return out


def _bench_quant(report, smoke: bool) -> dict:
    """Quantized paged KV pool (DESIGN.md §3.8): serving density at EQUAL
    KV HBM budget — the int8 pool stores ~4x the tokens per byte (pages at
    1 B/elem plus a small f32 per-page scale side-band), so the same
    memory admits proportionally more concurrent sequences. The tracked
    signals are the peak-concurrency ratio (≥ 1.5x is the acceptance bar)
    and the accuracy cost as max logprob drift on a teacher-forced paged
    decode (int8 vs native pool)."""
    import jax.numpy as jnp
    from jax import tree_util as jtu

    from repro.configs import paper_llama
    from repro.models import get_model
    from repro.models.transformer import (
        decode_step_lm, init_decode_cache, prefill_lm,
    )
    from repro.serve import Engine, ServeConfig

    cfg = dataclasses.replace(
        paper_llama.CONFIG, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, head_dim=16, vocab_size=128, vocab_pad_multiple=64,
    )
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    page = 4 if smoke else 8
    budget_tokens, max_len = (48, 32) if smoke else (192, 64)
    n_req, p_len, n_new = (16, 6, 6) if smoke else (32, 12, 16)
    reqs = [np.random.default_rng(i).integers(0, cfg.vocab_size, (p_len,))
            .astype(np.int32) for i in range(n_req)]

    def make(kv_dtype, pool_tokens):
        return Engine(params, cfg, ServeConfig(
            max_batch=n_req, max_len=max_len, temperature=0.0,
            kv_layout="paged", page_size=page, kv_pool_tokens=pool_tokens,
            kv_dtype=kv_dtype))

    eng_f = make("", budget_tokens)
    t0 = time.perf_counter()
    eng_f.serve(reqs, n_new)
    t_native = time.perf_counter() - t0
    bpt_f = eng_f.stats()["kv_bytes_per_token"]
    budget_bytes = bpt_f * budget_tokens

    # size the int8 pool to the SAME byte budget (scale side-band included)
    probe_eng = make("int8", budget_tokens)
    probe_eng._paged_state()  # pool is lazy; stats() needs it materialized
    bpt_q = probe_eng.stats()["kv_bytes_per_token"]
    pool_q = int(budget_bytes // bpt_q) // page * page
    eng_q = make("int8", pool_q)
    t0 = time.perf_counter()
    eng_q.serve(reqs, n_new)
    t_int8 = time.perf_counter() - t0

    ratio = eng_q.peak_active / max(eng_f.peak_active, 1)
    report("quant_pool_tokens_native", budget_tokens,
           f"{budget_bytes / 1024:.1f} KiB @ {bpt_f:.0f} B/token")
    report("quant_pool_tokens_int8", pool_q,
           f"same bytes @ {bpt_q:.0f} B/token (pages + scale side-band)")
    report("quant_concurrency_ratio", ratio,
           "int8/native peak sequences at equal KV HBM (≥1.5 target)")
    assert ratio >= 1.5, (
        f"int8 equal-memory concurrency {ratio:.2f}x below the 1.5x bar "
        f"({eng_q.peak_active} vs {eng_f.peak_active} peak sequences)")

    # --- accuracy: teacher-forced paged decode, int8 vs native pool
    B, plen, steps, max_len = 2, 8, 6, 32
    n_per = max_len // page  # the cache's table width
    toks_in = jnp.asarray(
        np.random.default_rng(99).integers(1, cfg.vocab_size, (B, plen)),
        jnp.int32)
    tbl = jnp.asarray([[1 + b * n_per + i for i in range(n_per)]
                       for b in range(B)], jnp.int32)

    def probe(kv_dtype, forced):
        cache = init_decode_cache(B, max_len, cfg, layout="paged", page_size=page,
                                  n_pages=1 + B * n_per, kv_dtype=kv_dtype)

        def set_tbl(path, x):
            name = next((e.key for e in reversed(path)
                         if isinstance(e, jtu.DictKey)), None)
            return jnp.broadcast_to(tbl, x.shape) if name == "tbl" else x

        cache = jtu.tree_map_with_path(set_tbl, cache)
        logits, cache = prefill_lm(params, toks_in, cache, cfg)
        lps, toks = [jax.nn.log_softmax(logits[:, :cfg.vocab_size])], []
        for t in range(steps):
            tok = (jnp.argmax(logits, -1).astype(jnp.int32)
                   if forced is None else forced[t])
            toks.append(tok)
            logits, cache = decode_step_lm(
                params, cache, tok, jnp.full((B,), plen + t), cfg)
            lps.append(jax.nn.log_softmax(logits[:, :cfg.vocab_size]))
        return jnp.stack(lps), toks

    lp_f, forced = probe("", None)
    lp_q, _ = probe("int8", forced)
    drift = float(jnp.max(jnp.abs(lp_q - lp_f)))
    report("quant_max_logprob_drift", drift,
           f"teacher-forced, {steps} decode steps")

    return {
        "kv_budget_bytes": int(budget_bytes),
        "bytes_per_token_native": float(bpt_f),
        "bytes_per_token_int8": float(bpt_q),
        "pool_tokens_native": budget_tokens, "pool_tokens_int8": pool_q,
        "page_size": page, "n_requests": n_req,
        "request_prompt_len": p_len, "new_tokens": n_new,
        "concurrent_native": eng_f.peak_active,
        "concurrent_int8": eng_q.peak_active,
        "concurrency_ratio": ratio,
        "wall_s_native": t_native, "wall_s_int8": t_int8,
        "max_logprob_drift": drift,
    }


def _bench_serve(report, smoke: bool) -> dict:
    """Mixed varlen step vs sequential prefill-then-decode (DESIGN.md §3.5).

    The tracked workload is decode-heavy with a LONG-PROMPT ARRIVAL: a
    queue of short prompts (which decode for a while) with one long prompt
    in the middle. The sequential engines run the long prompt's whole
    prefill as one blocking dispatch when a slot frees — every decoding
    sequence stalls and everything queued behind it waits; the mixed
    engine drips the prompt in `prefill_chunk`-token pieces interleaved
    with decode rows. Tracked signals: per-request time-to-first-token
    (engine.ttft, recorded by the shared Scheduler) and total tokens/s,
    for the contiguous and paged sequential engines vs the mixed engine.
    All three must be token-identical (asserted here, greedy)."""
    import dataclasses as _dc

    from repro.configs import paper_llama
    from repro.models import get_model
    from repro.serve import Engine, ServeConfig

    cfg = _dc.replace(
        paper_llama.CONFIG, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, head_dim=16, vocab_size=128, vocab_pad_multiple=64,
    )
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    if smoke:
        short_len, long_len, n_short, n_new = 6, 256, 5, 16
        slots, max_len = 2, 288
        pchunk = 64
    else:
        short_len, long_len, n_short, n_new = 8, 512, 7, 32
        slots, max_len = 2, 576
        pchunk = 64
    rng = np.random.default_rng(0)
    shorts = [rng.integers(0, cfg.vocab_size, (short_len,)).astype(np.int32)
              for _ in range(n_short)]
    long_p = rng.integers(0, cfg.vocab_size, (long_len,)).astype(np.int32)
    mid = n_short // 2
    reqs = shorts[:mid] + [long_p] + shorts[mid:]
    long_rid = mid

    # prefix_cache off: this bench re-serves the same queue for jit
    # warm-up, and the tracked signal is chunked-prefill interleaving on
    # COLD prompts — warm-hit prefill skipping is BENCH_prefix.json's job
    common = dict(max_batch=slots, max_len=max_len, temperature=0.0,
                  prefix_cache=False)
    engines = {
        "contiguous_sequential": ServeConfig(**common),
        "paged_sequential": ServeConfig(**common, kv_layout="paged"),
        "mixed": ServeConfig(
            **common, step_mode="mixed",
            prefill_chunk=pchunk, token_budget=slots + pchunk,
        ),
    }
    out: dict = {"workload": {
        "n_short": n_short, "short_len": short_len, "long_len": long_len,
        "long_rid": long_rid, "new_tokens": n_new, "slots": slots,
        "max_len": max_len,
    }, "engines": {}}
    tokens_ref = None
    for name, sc in engines.items():
        # jit caches live on the Engine instance, so the warm-up and the
        # timed call must share one engine: serve() rebuilds its scheduler
        # state per call, making a re-serve of the same queue valid
        eng2 = Engine(params, cfg, sc)
        eng2.serve(reqs, n_new)  # warm-up: compile every bucket
        t0 = time.perf_counter()
        outs = eng2.serve(reqs, n_new)
        wall = time.perf_counter() - t0
        if tokens_ref is None:
            tokens_ref = outs
        else:  # the acceptance contract: all three token-identical
            assert all(np.array_equal(a, b) for a, b in zip(tokens_ref, outs))
        ttft = [eng2.ttft[r] for r in sorted(eng2.ttft)]
        after_long = [eng2.ttft[r] for r in range(long_rid + 1, len(reqs))]
        row = {
            "wall_s": wall,
            "tokens_per_sec": sum(map(len, outs)) / wall,
            "ttft_mean_s": float(np.mean(ttft)),
            "ttft_max_s": float(np.max(ttft)),
            "ttft_long_prompt_s": eng2.ttft[long_rid],
            "ttft_after_long_mean_s": float(np.mean(after_long)),
            "ttft_s": ttft,
        }
        out["engines"][name] = row
        report(f"serve_{name}_tok_per_s", row["tokens_per_sec"], f"T={n_new}")
        report(f"serve_{name}_ttft_mean_s", row["ttft_mean_s"],
               f"after_long={row['ttft_after_long_mean_s']:.3f}s "
               f"max={row['ttft_max_s']:.3f}s")
    ratio = (out["engines"]["mixed"]["ttft_mean_s"]
             / out["engines"]["paged_sequential"]["ttft_mean_s"])
    report("serve_mixed_vs_sequential_ttft", ratio,
           "mean-TTFT ratio under long-prompt arrival (<1 is the win)")
    return out


def _bench_spec(report, smoke: bool) -> dict:
    """Speculative decoding through the packed verify step (DESIGN.md §3.9).

    Decode-heavy workload (short prompts, long generations — the regime
    speculation targets): a non-speculative mixed engine is the baseline,
    then the same queue runs with spec_tokens=K drafts verified per round.
    `OracleDraft` dials acceptance exactly (it corrupts the known greedy
    continuation per-token with a seeded rate), so the sweep shows decode
    tokens/s as a function of acceptance — the top of the sweep is the
    tracked ≥2× signal, the bottom bounds the rejection-rollback overhead.
    Token identity vs the non-speculative output is ASSERTED at every
    acceptance point (greedy: speculation must never change the stream),
    and a self-draft row (the target as its own draft, acceptance 1.0 by
    construction) pins the end-to-end DraftModel device path."""
    import dataclasses as _dc

    from repro.configs import paper_llama
    from repro.models import get_model
    from repro.serve import Engine, OracleDraft, ServeConfig

    cfg = _dc.replace(
        paper_llama.CONFIG, n_layers=4, d_model=256, n_heads=8,
        n_kv_heads=4, d_ff=512, head_dim=32, vocab_size=128,
        vocab_pad_multiple=64,
    )
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    # the TRACKED point is single-stream (max_batch=1): the latency-bound
    # regime speculation exists for — a lone sequence leaves the hardware
    # idle between sequential decode steps, and a verify round turns K+1
    # of those steps into one parallel dispatch. A batched point rides
    # along (reported, ungated): batching already fills the device, so
    # the margin there is structurally thinner.
    spec_k = 15
    if smoke:
        n_reqs, plen, n_new = 2, 8, 48
    else:
        n_reqs, plen, n_new = 3, 8, 64
    max_len = plen + n_new + spec_k + 2
    rng = np.random.default_rng(0)
    reqs = [rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32)
            for _ in range(n_reqs)]
    out: dict = {
        "workload": {"n_reqs": n_reqs, "prompt_len": plen,
                     "new_tokens": n_new, "spec_tokens": spec_k},
        "points": {},
    }

    def timed(eng):
        eng.serve(reqs, n_new)  # warm-up: compile every bucket
        t0 = time.perf_counter()
        outs = eng.serve(reqs, n_new)
        wall = time.perf_counter() - t0
        return outs, wall, sum(map(len, outs)) / wall

    top_row = None
    for slots in (1, 2):
        common = dict(max_batch=slots, max_len=max_len, temperature=0.0,
                      step_mode="mixed", prefix_cache=False)
        ref, base_wall, base_tps = timed(
            Engine(params, cfg, ServeConfig(**common))
        )
        point = {"baseline_tokens_per_sec": base_tps,
                 "baseline_wall_s": base_wall, "sweep": []}
        out["points"][f"slots_{slots}"] = point
        report(f"spec_b{slots}_baseline_tok_per_s", base_tps,
               f"T={n_new} no speculation")

        def spec_row(label, draft):
            eng = Engine(params, cfg,
                         ServeConfig(**common, spec_tokens=spec_k),
                         draft=draft)
            outs, wall, tps = timed(eng)
            for i, (a, b) in enumerate(zip(ref, outs)):  # identity contract
                assert np.array_equal(a, b), f"{label}: req {i} diverged"
            s = eng.stats()
            row = {
                "draft": label,
                "wall_s": wall,
                "tokens_per_sec": tps,
                "speedup": tps / base_tps,
                "acceptance_rate": s["spec_acceptance_rate"],
                "mean_accepted_per_round": s["spec_mean_accepted"],
                "rounds": s["spec_rounds"],
                "token_identical": True,
            }
            point["sweep"].append(row)
            report(f"spec_b{slots}_{label}_tok_per_s", tps,
                   f"acc={row['acceptance_rate']:.2f} "
                   f"speedup={row['speedup']:.2f}x")
            return row

        top = spec_row("oracle_acc_1.00",
                       OracleDraft(reqs, ref, cfg.vocab_size, accuracy=1.0))
        for acc in (0.75, 0.5):
            spec_row(f"oracle_acc_{acc:.2f}",
                     OracleDraft(reqs, ref, cfg.vocab_size,
                                 accuracy=acc, seed=1))
        if slots == 1:
            top_row = top
            spec_row("self_draft", (params, cfg))
    # the tracked acceptance bar, on the single-stream point: a
    # fully-accepted K-chain commits K+1 tokens per dispatch where the
    # baseline pays K+1 sequential steps — ≥2× decode throughput,
    # token-identical (measured margin is ~5-10×; 2 is the alarm line)
    assert top_row["speedup"] >= 2.0, (
        f"speculative decode speedup {top_row['speedup']:.2f}x < 2x at "
        f"acceptance {top_row['acceptance_rate']:.2f}"
    )
    report("spec_top_speedup", top_row["speedup"],
           ">=2x required at full acceptance, single stream, "
           "token-identical")
    return out


def _bench_chaos(report, smoke: bool) -> dict:
    """Serving under chaos injection (DESIGN.md §3.7).

    One request batch served at fault rates 0% / 5% / 20% (fresh engine
    per rate, same seed → deterministic). Tracked signals per rate:
    goodput (fraction of requests ending DONE), retries charged, wall
    time, and p99 TTFT. The lifecycle contract is ASSERTED, not just
    reported: every request terminal at every rate, survivors
    token-identical to the fault-free run, and goodput degrades
    gracefully (1.0 at rate 0, ≥ 0.5 at rate 0.2) instead of collapsing.
    """
    import dataclasses as _dc

    from repro.configs import paper_llama
    from repro.models import get_model
    from repro.serve import Engine, FaultInjector, ServeConfig

    cfg = _dc.replace(
        paper_llama.CONFIG, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, head_dim=16, vocab_size=128, vocab_pad_multiple=64,
    )
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    if smoke:
        n_req, p_len, n_new, slots = 6, 8, 8, 2
    else:
        n_req, p_len, n_new, slots = 8, 12, 16, 2
    rng = np.random.default_rng(0)
    reqs = [rng.integers(0, cfg.vocab_size, (p_len,)).astype(np.int32)
            for _ in range(n_req)]
    sc = ServeConfig(max_batch=slots, max_len=p_len + n_new + 8,
                     kv_layout="paged", page_size=8, max_retries=5)

    out: dict = {"workload": {"n_requests": n_req, "prompt_len": p_len,
                              "new_tokens": n_new, "slots": slots,
                              "max_retries": sc.max_retries},
                 "rates": {}}
    baseline = None
    for rate in (0.0, 0.05, 0.20):
        inj = FaultInjector(rate=rate, seed=0) if rate > 0 else None
        eng = Engine(params, cfg, sc, fault_injector=inj)
        t0 = time.perf_counter()
        outs = eng.serve(reqs, n_new)
        wall = time.perf_counter() - t0
        st = eng.stats()
        status = st["request_status"]
        assert all(s in ("done", "failed", "expired")
                   for s in status.values()), status  # all terminal
        if baseline is None:
            baseline = outs
        for i, o in enumerate(outs):  # survivors token-identical
            if status[i] == "done":
                assert np.array_equal(baseline[i], o), (rate, i)
        eng._alloc.check()
        goodput = sum(s == "done" for s in status.values()) / n_req
        ttft = sorted(eng.ttft.values())
        p99 = float(ttft[min(len(ttft) - 1,
                             int(np.ceil(0.99 * len(ttft))) - 1)]) if ttft else 0.0
        row = {
            "goodput": goodput,
            "done": sum(s == "done" for s in status.values()),
            "failed": st["failed"], "expired": st["expired"],
            "retries": st["retried"],
            "faults_fired": st.get("injected_faults", {}),
            "wall_s": wall,
            "tokens_per_sec": sum(map(len, outs)) / wall,
            "ttft_p99_s": p99,
        }
        out["rates"][f"{rate:.2f}"] = row
        report(f"chaos_rate{int(rate * 100):02d}_goodput", goodput,
               f"{row['done']}/{n_req} done, {row['retries']} retries, "
               f"p99 TTFT {p99:.3f}s")
    assert out["rates"]["0.00"]["goodput"] == 1.0
    assert out["rates"]["0.20"]["goodput"] >= 0.5, out["rates"]["0.20"]
    return out


def _bench_prefix(report, smoke: bool) -> dict:
    """Radix prefix cache + preemptive scheduling (DESIGN.md §3.6).

    Two tracked signals on a multi-turn chat workload (every request
    replays a shared system prompt):

      1. warm-hit TTFT — the engine's radix tree persists across serve()
         calls, so the second turn's prefill starts at the first uncached
         token. Acceptance bar: warm TTFT ≤ 0.5 × cold TTFT (asserted —
         on real shapes the ratio is prompt_len / tail_len, far below).
      2. oversubscription — a pool SMALLER than the worst-case demand of
         a mixed-priority burst completes via victim preemption with
         tokens IDENTICAL to the unconstrained engine (asserted), at the
         reported tokens/s and preemption count.
    """
    import dataclasses as _dc

    from repro.configs import paper_llama
    from repro.models import get_model
    from repro.serve import Engine, ServeConfig

    cfg = _dc.replace(
        paper_llama.CONFIG, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, head_dim=16, vocab_size=128, vocab_pad_multiple=64,
    )
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    if smoke:
        sys_len, user_len, n_new, page = 96, 8, 8, 8
    else:
        sys_len, user_len, n_new, page = 512, 16, 16, 16
    max_len = sys_len + 3 * (user_len + n_new) + 2 * page
    rng = np.random.default_rng(0)
    system = rng.integers(0, cfg.vocab_size, (sys_len,)).astype(np.int32)

    def user():
        return rng.integers(0, cfg.vocab_size, (user_len,)).astype(np.int32)

    sc = ServeConfig(max_batch=2, max_len=max_len, temperature=0.0,
                     kv_layout="paged", page_size=page)
    eng = Engine(params, cfg, sc)
    # compile warm-up on same-shape, different-content traffic (its pages
    # land in the cache but can never match the measured system prompt)
    wsys = rng.integers(0, cfg.vocab_size, (sys_len,)).astype(np.int32)
    wturn1 = np.concatenate([wsys, user()])
    wout = eng.serve([wturn1], n_new)
    eng.serve([np.concatenate([wsys, user()])], n_new)
    # a warm-up CONVERSATION turn, so the measured turn-2 tail bucket
    # (cached prior turn + fresh user message) is compiled too
    eng.serve([np.concatenate([wturn1, wout[0], user()])], n_new)

    turn1 = np.concatenate([system, user()])
    out1 = eng.serve([turn1], n_new)
    t_cold = eng.ttft[0]
    warm_prompt = np.concatenate([system, user()])
    out_warm = eng.serve([warm_prompt], n_new)
    t_warm = eng.ttft[0]
    # multi-turn: the follow-up replays the ENTIRE first conversation
    turn2 = np.concatenate([turn1, out1[0], user()])
    eng.serve([turn2], n_new)
    t_turn2 = eng.ttft[0]
    st = eng.stats()
    assert st["hit_tokens"] > 0, "the warm turns must hit the cache"
    assert t_warm <= 0.5 * t_cold, (
        f"warm-hit TTFT {t_warm:.4f}s not ≤ 0.5× cold {t_cold:.4f}s"
    )
    # warm tokens must equal a cold (cache-off) engine's for the same prompt
    cold_eng = Engine(params, cfg, _dc.replace(sc, prefix_cache=False))
    out_cold = cold_eng.serve([warm_prompt], n_new)
    assert np.array_equal(out_warm[0], out_cold[0]), (
        "warm-hit tokens must match the cold engine"
    )
    report("prefix_cold_ttft_s", t_cold, f"system={sys_len} user={user_len}")
    report("prefix_warm_ttft_s", t_warm,
           f"ratio={t_warm / t_cold:.3f} (≤0.5 bar) "
           f"hit_rate={st['hit_rate']:.2f}")
    report("prefix_turn2_ttft_s", t_turn2,
           "full prior conversation replayed from cache")

    # --- oversubscription: mixed priorities, pool < worst-case demand.
    # Every request is admitted at once (optimistic per-chunk allocation),
    # the shared system prompt is cached once, and the pool is sized so
    # concurrent tail GROWTH still overflows it — page pressure that only
    # victim preemption can resolve.
    n_req = slots = 6
    reqs = [np.concatenate([system, user()]) for _ in range(n_req)]
    prios = [i % 2 for i in range(n_req)]
    ample = Engine(params, cfg, _dc.replace(sc, max_batch=slots))
    t0 = time.perf_counter()
    want = ample.serve(reqs, n_new, priorities=prios)
    t_ample = time.perf_counter() - t0
    # worst case: n_req × ⌈(sys+user+new)/page⌉ pages; grant the shared
    # system prompt once plus one page of headroom per request
    shared_pages = sys_len // page
    tight_pages = shared_pages + n_req + 1
    worst_pages = n_req * (-(-(sys_len + user_len + n_new) // page))
    assert tight_pages < worst_pages
    tight = Engine(params, cfg, _dc.replace(
        sc, max_batch=slots, kv_pool_tokens=tight_pages * page))
    t0 = time.perf_counter()
    got = tight.serve(reqs, n_new, priorities=prios)
    t_tight = time.perf_counter() - t0
    assert all(np.array_equal(a, b) for a, b in zip(want, got)), (
        "oversubscribed run must stay token-identical"
    )
    stt = tight.stats()
    toks = sum(map(len, got))
    report("prefix_oversub_tok_per_s", toks / t_tight,
           f"pool={tight_pages}p vs worst-case {worst_pages}p, "
           f"preemptions={stt['preemptions']}, "
           f"ample={toks / t_ample:.1f} tok/s")
    return {
        "workload": {
            "system_len": sys_len, "user_len": user_len,
            "new_tokens": n_new, "page_size": page,
        },
        "cold_ttft_s": t_cold,
        "warm_ttft_s": t_warm,
        "warm_over_cold": t_warm / t_cold,
        "turn2_ttft_s": t_turn2,
        "hit_rate": st["hit_rate"],
        "hit_tokens": st["hit_tokens"],
        "oversubscription": {
            "n_requests": n_req, "slots": slots,
            "pool_pages": tight_pages, "worst_case_pages": worst_pages,
            "priorities": prios,
            "tokens_per_sec_tight": toks / t_tight,
            "tokens_per_sec_ample": toks / t_ample,
            "preemptions": stt["preemptions"],
            "evictions": stt["evictions"],
            "token_identical": True,
        },
    }


def _bench_decode(report, smoke: bool) -> dict:
    """Decode fast path: fused vs unfused split-K kernel, and the jitted
    scan engine vs the seed-style per-token host loop."""
    from repro.kernels.flashd_decode import flashd_decode_pallas

    out: dict = {"kernel": [], "engine": {}}

    # --- kernel: fused (in-VMEM merge) vs unfused (HBM partials + host merge)
    b, hq, hkv, s, d = (1, 2, 1, 64, 16) if smoke else (2, 8, 2, 512, 64)
    n_splits = 2 if smoke else 8
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    cl = jnp.full((b,), s, jnp.int32)
    for fused in (True, False):
        f = jax.jit(
            lambda q, k, v, c, fused=fused: flashd_decode_pallas(
                q, k, v, c, n_splits=n_splits, fused=fused,
                interpret=jax.devices()[0].platform != "tpu",
            )
        )
        us = _bench(f, q, kc, vc, cl)
        tag = "fused" if fused else "unfused"
        report(f"decode_kernel_{tag}", us, f"b={b} s={s} splits={n_splits}")
        out["kernel"].append({
            "variant": tag, "batch": b, "heads": hq, "kv_heads": hkv,
            "cache_len": s, "head_dim": d, "n_splits": n_splits,
            "us_per_call": us,
        })

    # --- engine: jitted scan loop vs per-token host loop (the seed path)
    from repro.configs import paper_llama
    from repro.models import get_model
    from repro.models.transformer import prefill_lm
    from repro.serve import Engine, ServeConfig, sample_token

    cfg = dataclasses.replace(
        paper_llama.CONFIG, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, head_dim=16, vocab_size=128, vocab_pad_multiple=64,
    )
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    bsz, n_new = (2, 8) if smoke else (4, 32)
    sc = ServeConfig(max_len=64, temperature=0.0)
    eng = Engine(params, cfg, sc)
    prompts = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (bsz, 8)
    ).astype(np.int32)

    scan_s = _bench(lambda: eng.generate(prompts, n_new), iters=3) * 1e-6

    prefill_j = jax.jit(lambda p, t, c: prefill_lm(p, t, c, cfg))

    def legacy_generate():
        """The seed engine's loop: one dispatch + one blocking np.asarray
        host sync per token."""
        cache = api.init_cache(bsz, sc.max_len, cfg)
        logits, cache = prefill_j(params, jnp.asarray(prompts, jnp.int32), cache)
        pos = jnp.full((bsz,), prompts.shape[1], jnp.int32)
        key = jax.random.PRNGKey(0)
        tok = sample_token(logits, key, sc)
        outs = []
        for _ in range(n_new):
            outs.append(np.asarray(tok))  # per-token host sync
            logits, cache = eng._decode(params, cache, tok, pos)
            pos = pos + 1
            key, k = jax.random.split(key)
            tok = sample_token(logits, k, sc)
        return np.stack(outs, axis=1)

    loop_s = _bench(legacy_generate, iters=3) * 1e-6

    tok_scan = bsz * n_new / scan_s
    tok_loop = bsz * n_new / loop_s
    report("decode_engine_scan_tok_per_s", tok_scan, f"b={bsz} T={n_new}")
    report("decode_engine_loop_tok_per_s", tok_loop, "seed per-token path")
    report("decode_engine_speedup", tok_scan / tok_loop,
           "jitted scan vs per-token host loop (>1 is a win)")
    out["engine"] = {
        "batch": bsz, "new_tokens": n_new,
        "tokens_per_sec_scan": tok_scan,
        "tokens_per_sec_seed_loop": tok_loop,
        "speedup": tok_scan / tok_loop,
    }
    return out
