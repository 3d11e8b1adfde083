"""A tiny benchmark tree for the CPU tests: one serving and one training
configuration at toy widths, their mixes and limits, and entries in a
`BENCHMARK.json` of its own, all written as files only, the way a later
change adds a cell. The metric readers are the real ones."""

from __future__ import annotations

import json
import os
import shutil
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab_size": 8192,
         "vocab_pad_multiple": 64, "qkv_bias": True, "qk_norm": True,
         "rope_theta": 10000.0, "norm_eps": 1e-6, "tie_embeddings": True,
         "dtype": "bfloat16", "attn_impl": "flashd_pallas"}
OPTIMIZER = {"lr": 0.0003, "b1": 0.9, "b2": 0.95, "eps": 1e-08,
             "weight_decay": 0.1, "clip_norm": 1.0, "warmup_steps": 100,
             "total_steps": 100000, "final_frac": 0.1,
             "no_decay_keywords": ["norm", "bias", "lam", "A_log", "D", "dt_bias"]}
# limits set from readings at this size on the CPU (seeds 1-4): sound
# runs read a logit gap of 0, loss gaps under 2e-4 and norm gaps under
# 3e-3; the float8 control reads logit gaps to 4e-3, loss gaps over 1e-3
# and gradient gaps over 0.02; half a batch reads gradient gaps over 0.04.
# The gradient's error by element (seeds 1-4, 9): sound runs 0.0108-0.0118,
# the float8 control 0.165-0.172, half a batch 0.77-0.94
SERVE_LIMIT = 0.001
TRAIN_LIMITS = {"loss_gap": 5e-4, "grad_norm_gap": 0.008, "update_norm_gap": 0.02,
                "grad_error": 0.04}


def _dump(obj, *parts):
    path = os.path.join(*parts)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make(root: str) -> str:
    b = os.path.join(root, "bench")
    shutil.copytree(os.path.join(BENCH_DIR, "metrics"), os.path.join(b, "metrics"))
    _dump({"name": "toy", "model": dict(MODEL, param_dtype="bfloat16"),
           "deployment": {"kind": "serve", "chips": 1, "engine": {
               "max_batch": 4, "kv_layout": "paged", "step_mode": "mixed",
               "prefill_chunk": 16, "kv_pool_tokens": 512, "temperature": 0.0}}},
          b, "configs", "toy.json")
    _dump({"name": "toyt", "model": dict(MODEL, param_dtype="float32"),
           "deployment": {"kind": "train", "chips": 1, "global_batch": 2,
                          "optimizer": OPTIMIZER}},
          b, "configs", "toyt.json")
    _dump({"kind": "open_loop", "rate_rps": 4.0, "max_new_tokens": 4,
           "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 48}},
          b, "traffic", "chat.json")
    _dump({"kind": "batch", "requests": 3, "max_new_tokens": 64,
           "deadline": "window",
           "prompt": {"median": 16, "sigma": 0.5, "min": 8, "max": 32}},
          b, "traffic", "gen.json")
    _dump({"kind": "train", "seq_len": 64}, b, "traffic", "tr.json")
    for c in ("toy.chat", "toy.gen"):
        _dump({"sample_requests": 2,
               "numbers": {"logit_gap": {"limit": SERVE_LIMIT}}}, b, "limits", c + ".json")
    _dump({"numbers": {k: {"limit": v} for k, v in TRAIN_LIMITS.items()}},
          b, "limits", "toyt.tr.json")

    def pl(name, moves, cell):
        return {"name": name, "unit": "%", "better": "lower",
                "source": "device_trace", "layer": "device", "moves": moves,
                "workloads": [cell]}

    def e2e(name, unit, cell=None):
        m = {"name": name, "unit": unit, "better": "lower", "bound": 0.1,
             "source": "host_clock"}
        if cell:
            m["workloads"] = [cell]
        return m

    _dump({"command": ["python3", "bench/run.py"], "paths": ["bench"],
           "run_seconds": 1,
           "configs": [
               {"name": "toy", "source": "toy", "file": "bench/configs/toy.json",
                "reduced": [], "why": "toy"},
               {"name": "toyt", "source": "toy", "file": "bench/configs/toyt.json",
                "reduced": [], "why": "toy"}],
           "workloads": [
               {"name": "toy.chat", "config": "toy", "traffic": "chat", "chips": 1, "why": "toy"},
               {"name": "toy.gen", "config": "toy", "traffic": "gen", "chips": 1, "why": "toy"},
               {"name": "toyt.tr", "config": "toyt", "traffic": "tr", "chips": 1, "why": "toy"}],
           "end_to_end": [e2e("ttft_p95_ms", "ms", "toy.chat"),
                          e2e("output_tok_s", "tokens/s", "toy.gen"),
                          e2e("train_tok_s", "tokens/s", "toyt.tr"),
                          e2e("setup_s", "s")],
           "per_layer": [pl("idle_share.prefill", "ttft_p95_ms", "toy.chat"),
                         pl("mfu.decode", "output_tok_s", "toy.gen"),
                         pl("idle_share.train", "train_tok_s", "toyt.tr")]},
          root, "BENCHMARK.json")
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    return root
