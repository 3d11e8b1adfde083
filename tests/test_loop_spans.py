"""Host spans of the serving and training loops, and the mixed serving
loop's per-call counters.

A tiny paged mixed-mode `Engine.serve` and a 3-step `train_resilient`
run under `jax.profiler.trace`; their `repro.*` spans are read back from
the `.xplane.pb` the trace wrote. The serving schedule is small enough
to check by hand: two slots, prompt chunks of 4 tokens, a packed query
block of 8 rows.
"""

import dataclasses
import glob

import jax
import numpy as np
import pytest

from repro.configs import paper_llama
from repro.data import DataConfig, SyntheticLM
from repro.kernels.tuning import bucket_pow2, choose_varlen_blocks, padded_rows
from repro.models import get_model
from repro.optim import AdamWConfig
from repro.runtime.resilience import FaultInjector
from repro.serve import Engine, ServeConfig
from repro.serve.scheduler import Request, Scheduler
from repro.train import ResilienceConfig, TrainConfig, train_resilient

STEP_CHILDREN = ("plan", "pack", "dispatch", "sync", "commit", "admit")
TRAIN_CHILDREN = ["batch", "dispatch", "sync", "commit"]
MAX_NEW = 3
SC = dict(max_batch=2, max_len=32, kv_layout="paged", step_mode="mixed",
          prefill_chunk=4, page_size=8, decode_chunk=8)


def _cfg():
    return dataclasses.replace(
        paper_llama.CONFIG, n_layers=2, d_model=48, n_heads=4, n_kv_heads=2,
        d_ff=96, head_dim=12, vocab_size=64, vocab_pad_multiple=64,
    )


def _engine(injector=None):
    cfg = _cfg()
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    return Engine(params, cfg, ServeConfig(**SC), fault_injector=injector)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 64, n).astype(np.int32) for n in lengths]


def _spans(trace_dir):
    """Every `repro.*` host event: (name, thread, start_ns, end_ns, args)."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith("repro."):
                    out.append((e.name, ln.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: s[2])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Serve A (6 tokens) and B (3 tokens) at t = 0, then C (5 tokens)
    once both have finished, under the profiler."""
    eng = _engine()
    eng.serve(_prompts([6, 3, 5], seed=9), MAX_NEW)  # compile outside the trace
    reqs = [Request(rid=i, prompt=p, not_before=nb) for i, (p, nb) in
            enumerate(zip(_prompts([6, 3, 5]), [0.0, 0.0, 0.5]))]
    d = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(d):
        outs = eng.serve(reqs, MAX_NEW)
    return eng, eng.stats(), _spans(d), outs


def test_hand_checked_schedule_counts_its_rows(traced):
    eng, st, _, outs = traced
    assert [len(o) for o in outs] == [MAX_NEW] * 3
    # two slots: decode rows share the packs, so the tile is the minimum
    hd = _cfg().head_dim_
    block_q = choose_varlen_blocks(bucket_pow2(2 + 4, lo=8), hd, hd, group=2,
                                   kv_heads=2, page=8, segment_hint=1).block_q
    assert block_q == 8
    # step 1: A[0:4], B[0:2]; step 2: A[4:6], B[2:3] (both emit); then one
    # 3-token decode chunk; C: C[0:4], then C[4:5] (emits), then a chunk
    mixed = [[4, 2], [2, 1], [4], [1]]
    packed = sum(bucket_pow2(sum(padded_rows(n, block_q) for n in step), lo=block_q)
                 for step in mixed)
    assert (packed, sum(map(sum, mixed))) == (16 + 16 + 8 + 8, 14)
    assert st["packed_rows"] == packed
    assert st["real_rows"] == 14
    assert st["steps"] == {"mixed": 4, "chunk": 2}


def test_step_spans_and_their_children(traced):
    _, st, spans, _ = traced
    names = {s[0] for s in spans}
    assert names == {"repro.serve.step", "repro.serve.idle"} | {
        f"repro.serve.{c}" for c in STEP_CHILDREN}
    steps = [s for s in spans if s[0] == "repro.serve.step"]
    assert len({s[1] for s in spans}) == 1  # the serving thread
    kinds = [s[4]["kind"] for s in steps]
    assert kinds == ["mixed", "mixed", "chunk", "mixed", "mixed", "chunk"]
    for name, _, t0, t1, _ in spans:
        leaf = name.rsplit(".", 1)[1]
        if leaf in ("step", "idle"):
            continue
        inside = any(s[2] <= t0 and t1 <= s[3] for s in steps)
        # admission also runs outside a step: first, and after waiting
        assert inside or leaf == "admit", name
    # every step plans, packs, dispatches, syncs, commits and admits
    for s in steps:
        kids = [c[0].rsplit(".", 1)[1] for c in spans
                if c[0] != "repro.serve.step" and s[2] <= c[2] and c[3] <= s[3]]
        assert kids == list(STEP_CHILDREN)
    # C waits for its arrival with nothing live
    assert [s[0] for s in spans].count("repro.serve.idle") >= 1


def test_step_args_agree_with_stats(traced):
    _, st, spans, _ = traced
    steps = [s[4] for s in spans if s[0] == "repro.serve.step"]
    mixed = [a for a in steps if a["kind"] == "mixed"]
    assert sum(a["rows"] for a in mixed) == st["packed_rows"]
    assert sum(a["real_rows"] for a in mixed) == st["real_rows"]
    assert [a["live"] for a in steps] == [2, 2, 2, 1, 1, 1]
    # a decode chunk computes both slots for each of its 3 tokens
    chunks = [a for a in steps if a["kind"] == "chunk"]
    assert [(a["rows"], a["real_rows"]) for a in chunks] == [(6, 6), (6, 3)]


def test_admitted_at_follows_the_arrivals(traced):
    eng, st, _, _ = traced
    arrivals = [0.0, 0.0, 0.5]
    assert sorted(st["admitted_at"]) == [0, 1, 2]
    for rid, t in st["admitted_at"].items():
        assert arrivals[rid] <= t <= st["ttft"][rid]
    # another call starts its own counters
    eng.serve(_prompts([3]), 1)
    st2 = eng.stats()
    assert list(st2["admitted_at"]) == [0] and st2["steps"] == {"mixed": 1}


def test_admitted_at_survives_a_retry():
    # the 3rd page_alloc check is slot 0's growth before the first decode
    # chunk: A has its first token, is rolled back and admitted again
    eng = _engine(FaultInjector(schedule=[("page_alloc", 2)]))
    eng.serve(_prompts([6, 3]), MAX_NEW)
    st = eng.stats()
    assert st["retried"] == 1
    assert set(st["request_status"].values()) == {"done"}
    for rid, t in st["admitted_at"].items():
        assert t <= st["ttft"][rid]


def test_scheduler_keeps_the_first_admission():
    s = Scheduler([np.arange(4)], max_new_tokens=2, n_slots=1, eos_id=-1)
    req = s.take_head()
    s.admit_request_prefilling(0, req)
    first = s.admitted_at[0]
    assert s.fault_slot(0, backoff_s=0.0)
    s.admit_request_prefilling(0, s.take_head())
    assert s.admitted_at == {0: first}


def test_three_steps_three_step_spans_with_their_children(tmp_path):
    cfg = dataclasses.replace(
        paper_llama.CONFIG, n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
        d_ff=64, head_dim=16, vocab_size=64, vocab_pad_multiple=64,
    )
    tc = TrainConfig(optimizer=AdamWConfig(lr=1e-3), warmup_steps=2,
                     total_steps=50)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                  global_batch=4, seed=0))
    seen = []
    with jax.profiler.trace(str(tmp_path / "trace")):
        _, hist, _ = train_resilient(
            ckpt_dir=str(tmp_path / "ckpt"), model_cfg=cfg, train_cfg=tc,
            data=data, total_steps=3, res=ResilienceConfig(ckpt_every=2),
            on_step=lambda i, m, c: seen.append(i))
    assert seen == [0, 1, 2] and len(hist) == 3
    spans = _spans(str(tmp_path / "trace"))
    steps = [s for s in spans if s[0] == "repro.train.step"]
    assert [s[4]["step"] for s in steps] == [0, 1, 2]
    for _, _, t0, t1, _ in steps:
        kids = [s[0].rsplit(".", 1)[1] for s in spans
                if s[0] != "repro.train.step" and t0 <= s[2] and s[3] <= t1]
        assert kids == TRAIN_CHILDREN
    # saves after steps 2 and 3 (the last), each outside every step
    ckpts = [s for s in spans if s[0] == "repro.train.ckpt"]
    assert [s[4]["step"] for s in ckpts] == [2, 3]
    for _, _, t0, _, _ in ckpts:
        assert not any(s[2] <= t0 < s[3] for s in steps)
