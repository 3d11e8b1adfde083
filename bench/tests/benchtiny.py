"""A tiny benchmark tree for the CPU tests: serving and training
configurations at toy widths, their mixes and limits, and entries in a
`BENCHMARK.json` of its own, all written as files only, the way a later
change adds a cell. The metric readers and the dense architecture module
are the real ones. A second architecture comes in the same way: `toyw`
names the module `arch/toy_local.py` that this file writes (every layer
attends to a sliding window of keys, the program's `attn_local`), and no
harness code changes. `toyt4` is the training configuration on four
chips."""

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
WINDOW = 8
TRAIN_CELLS = ("toyt.tr", "toyt4.tr", "toyw.tr")
# The architecture module of `toyw`: the dense reference with a windowed
# mask, and counts in which a query reads at most WINDOW keys.
TOY_LOCAL = '''"""Dense GQA whose every layer attends causally to the last
`attn_window` keys (the program's `attn_local`)."""

import dataclasses
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np


def _beside(name):
    """The module `<name>.py` of this file's directory (a dataclass in it
    needs its module in `sys.modules`)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location(f"{__name__}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


dense = _beside("dense_gqa")


@dataclasses.dataclass(frozen=True)
class Arch(dense.Arch):
    window: int = 0

    @classmethod
    def of(cls, model, lowp=None):
        return cls(**vars(dense.Arch.of(model, lowp)), window=model["attn_window"])

    def visible(self, rows, keys):
        # rows past the sequence (padding of the last block) keep its
        # last window, so that no row is left without a key
        last = jnp.minimum(rows, keys[-1])
        return ((keys[None, :] <= rows[:, None])
                & (keys[None, :] > last[:, None] - self.window))


hidden, logits, loss = dense.hidden, dense.logits, dense.loss
flops_per_token, head_flops = dense.flops_per_token, dense.head_flops


def draw(name, shape, key):
    """The feed-forward norm's scale is this module's: uniform in +-0.1;
    every other leaf is the harness's draw."""
    if name.endswith("/norm2"):
        return jax.random.uniform(key, shape, jnp.float32, -0.1, 0.1)
    return None


def _ctx_sum(w, start, n):
    """Sum over positions p in [start, start + n) of min(p + 1, w)."""
    p = np.arange(int(start), int(start) + int(n))
    return float(np.minimum(p + 1, w).sum())


def mixer_work(m, start, n):
    """Per piece, the dense counts over the keys in reach: each query
    reads at most `attn_window` of them."""
    w = m["attn_window"]
    start, n = np.asarray(start, np.float64), np.asarray(n, np.float64)
    first = np.maximum(start + 1 - w, 0)  # the first key the piece reads
    ctx = np.array([_ctx_sum(w, s, k) for s, k in zip(start, n)])
    return dense.attn_flops(m, ctx), dense.mixer_work(m, start - first, n)[1]


def train_mixer_work(m, batch, seq_len):
    fwd = batch * dense.attn_flops(m, _ctx_sum(m["attn_window"], 0, seq_len))
    return np.array([3.0 * fwd]), dense.train_mixer_work(m, batch, seq_len)[1]
'''


def _dump(obj, *parts):
    path = os.path.join(*parts)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make(root: str) -> str:
    b = os.path.join(root, "bench")
    for d in ("metrics", "arch"):
        shutil.copytree(os.path.join(BENCH_DIR, d), os.path.join(b, d),
                        ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(b, "arch", "toy_local.py"), "w") as f:
        f.write(TOY_LOCAL)
    _dump({"name": "toy", "arch": "dense_gqa", "model": dict(MODEL, param_dtype="bfloat16"),
           "deployment": {"kind": "serve", "chips": 1, "engine": {
               "max_batch": 4, "kv_layout": "paged", "step_mode": "mixed",
               "prefill_chunk": 16, "kv_pool_tokens": 512, "temperature": 0.0}}},
          b, "configs", "toy.json")
    for name, chips, arch, extra in (
            ("toyt", 1, "dense_gqa", {}),
            ("toyt4", 4, "dense_gqa", {}),
            ("toyw", 1, "toy_local", {"pattern": [["attn_local", "swiglu"]],
                                      "attn_window": WINDOW})):
        _dump({"name": name, "arch": arch,
               "model": dict(MODEL, param_dtype="float32", **extra),
               "deployment": {"kind": "train", "chips": chips, "global_batch": 2,
                              "optimizer": OPTIMIZER}},
              b, "configs", name + ".json")
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
    for c in TRAIN_CELLS:
        _dump({"numbers": {k: {"limit": v} for k, v in TRAIN_LIMITS.items()}},
              b, "limits", c + ".json")

    def pl(name, moves, *cells):
        return {"name": name, "unit": "%", "better": "lower",
                "source": "device_trace", "layer": "device", "moves": moves,
                "workloads": list(cells)}

    def e2e(name, unit, *cells):
        m = {"name": name, "unit": unit, "better": "lower", "bound": 0.1,
             "source": "host_clock"}
        if cells:
            m["workloads"] = list(cells)
        return m

    def conf(name):
        return {"name": name, "source": "toy", "file": f"bench/configs/{name}.json",
                "reduced": [], "why": "toy"}

    def cell(name, chips=1):
        config, traffic = name.split(".")
        return {"name": name, "config": config, "traffic": traffic,
                "chips": chips, "why": "toy"}

    _dump({"command": ["python3", "bench/run.py"], "paths": ["bench"],
           "run_seconds": 1,
           "configs": [conf(c) for c in ("toy", "toyt", "toyt4", "toyw")],
           "workloads": [cell("toy.chat"), cell("toy.gen"), cell("toyt.tr"),
                         cell("toyt4.tr", 4), cell("toyw.tr")],
           "end_to_end": [e2e("ttft_p95_ms", "ms", "toy.chat"),
                          e2e("output_tok_s", "tokens/s", "toy.gen"),
                          e2e("train_tok_s", "tokens/s", *TRAIN_CELLS),
                          e2e("setup_s", "s")],
           "per_layer": [pl("idle_share.prefill", "ttft_p95_ms", "toy.chat"),
                         pl("mfu.decode", "output_tok_s", "toy.gen"),
                         pl("idle_share.train", "train_tok_s", *TRAIN_CELLS)]},
          root, "BENCHMARK.json")
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    return root
