"""Serving cells: `Engine.serve` over one seeded traffic mix.

Set-up makes the weights, builds the engine from the configuration's
`deployment.engine` settings (a mix's `engine` entries override them, so
that a mix such as an int8 pool is a data file too), warms every
packed-step bucket and the decode chunk with short requests, then
replays a few seconds of the mix. The window is ONE `serve()` call:
open-loop arrivals ride on `Request.not_before` (honoured on the
scheduler's clock from its construction), and a mix with
`"deadline": "window"` ends every request at `--seconds` through
`serve(deadlines=...)`.

In a traced run a host thread also samples the page pool five times a
second (the allocator's own counts): the peak of pages that running
requests hold and of pages that hold any data, cached prefixes included.

Afterwards `correct` compares a seeded sample of the served requests,
the longest among them, with the plain reference (`check.serve_gap`).
"""

from __future__ import annotations

import dataclasses
import gc
import threading
import time
from typing import List

import numpy as np

from harness import check, traffic, weights
from harness.work import ServeWork

WARM_REPLAY_S = 5.0
POOL_SAMPLE_S = 0.2


def model_config(config: dict):
    """The program's ModelConfig of a configuration's `model` (a layer
    `pattern` comes from JSON as lists and is made tuples)."""
    from repro.models.config import ModelConfig

    model = dict(config["model"])
    if "pattern" in model:
        model["pattern"] = tuple(tuple(spec) for spec in model["pattern"])
    return ModelConfig(name=config["name"], **model)


def engine_settings(config: dict, mix: dict, max_len: int, seed: int) -> dict:
    s = dict(config["deployment"]["engine"])
    s.update(mix.get("engine", {}))
    s["max_len"] = max_len
    s["seed"] = int(seed) % (2**31)
    return s


def warm_lengths(prefill_chunk: int, max_prompt: int) -> List[List[int]]:
    """Request sets that between them fill every power-of-two packed-step
    bucket the window can reach: one lone prompt per bucket up to the
    prefill chunk, and two prompts that together overflow it. The first
    set runs twice: the engine's first step reads the freshly made pool,
    which jit keys apart from the pools that later steps return."""
    out, n = [[8]], 8
    while n <= min(prefill_chunk, max_prompt):
        out.append([n])
        n *= 2
    out.append([min(prefill_chunk, max_prompt)] * 2)
    return out


@dataclasses.dataclass
class ServeRun:
    """What one window served, for the end-to-end metrics, the metric
    readers and the reference check."""

    prompts: List[np.ndarray]
    arrivals: np.ndarray
    outputs: List[np.ndarray]
    first_token: dict  # rid -> scheduler seconds of its first token
    status: dict  # rid -> terminal status
    window_s: float
    stats: dict

    def ttft_s(self) -> np.ndarray:
        """Per request, seconds from its scheduled arrival to its first
        token; inf for a request that failed or never emitted one."""
        out = np.full(len(self.prompts), np.inf)
        for rid, t in self.first_token.items():
            if self.status.get(rid) != "failed":
                out[rid] = t - self.arrivals[rid]
        return out

    def output_tokens(self) -> int:
        return int(sum(len(o) for o in self.outputs))

    def failed(self) -> int:
        return sum(1 for s in self.status.values() if s == "failed")


class PoolSampler:
    """Peak page-pool occupancy over a window, read from the engine's
    allocator on a host thread: `live` counts the distinct pages that
    running requests hold or have reserved, `held` every page that holds
    data, the radix cache's retained prefixes included."""

    def __init__(self, engine):
        self.alloc = getattr(engine, "_alloc", None)
        self.peak_live = self.peak_held = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def __enter__(self):
        if self.alloc is not None:
            self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join()
        self._sample()
        return False

    def _loop(self):
        while not self._stop.wait(POOL_SAMPLE_S):
            self._sample()

    def _sample(self):
        a = self.alloc
        if a is None:
            return
        try:
            live = set()
            for seq in a.live_seqs:
                live.update(a.table(seq))
            n_live = len(live) + a.reserved_pages
            n_held = a.pages_in_use
        except (KeyError, RuntimeError):  # a request ended mid-read
            return
        self.peak_live = max(self.peak_live, n_live)
        self.peak_held = max(self.peak_held, n_held)

    def summary(self) -> str:
        a = self.alloc
        if a is None:
            return "pool: no paged pool"
        n, page = a.n_pages, a.page_size
        return (f"pool: peak {self.peak_live} of {n} pages held by running "
                f"requests ({100 * self.peak_live / n:.1f}%, "
                f"{self.peak_live * page} tokens); peak {self.peak_held} "
                f"pages with data, cached prefixes included "
                f"({100 * self.peak_held / n:.1f}%)")


class ServeCell:
    def __init__(self, cell, seed: int, seconds: float, phases, clock):
        import jax
        from repro.serve import Engine, ServeConfig

        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.mc = model_config(cell.config)
        vocab = self.mc.vocab_size
        self.traffic = traffic.serve_traffic(cell.mix, seed, seconds, vocab)
        self.shapes = weights.param_shapes(self.mc)
        params = weights.make_params(self.shapes, seed, self.mc.param_dtype,
                                     arch=cell.arch)
        jax.block_until_ready(params)
        phases.lap("weights")
        p = cell.mix["prompt"]
        max_new = self.traffic.max_new_tokens
        settings = engine_settings(cell.config, cell.mix, p["max"] + max_new, seed)
        self.engine_cfg = settings
        self.engine = Engine(params, self.mc, ServeConfig(**settings))
        del params
        for lengths in warm_lengths(settings["prefill_chunk"], p["max"]):
            self.engine.serve(traffic.warmup_prompts(seed, vocab, lengths),
                              max_new_tokens=min(16, max_new))
        # then a few seconds of the mix itself, from a stream the window
        # never draws: the buckets above alone left one packed-step
        # program to compile inside every docqa window (TPU v5e)
        self._serve(traffic.serve_traffic(cell.mix, seed, WARM_REPLAY_S, vocab,
                                          stream=1), WARM_REPLAY_S)
        phases.lap("warm")

    def _serve(self, t: traffic.ServeTraffic, seconds: float):
        """One `serve()` call over `t`; returns (outputs, wall seconds)."""
        import jax
        from repro.serve.scheduler import Request

        reqs = [Request(rid=i, prompt=p, not_before=float(a))
                for i, (p, a) in enumerate(zip(t.prompts, t.arrivals))]
        deadlines = None
        if self.cell.mix.get("deadline") == "window":
            deadlines = [float(seconds)] * len(reqs)
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.serve"):
            outs = self.engine.serve(reqs, max_new_tokens=t.max_new_tokens,
                                     deadlines=deadlines)
        return outs, time.monotonic() - t0

    def window(self, sample_pool: bool = False) -> ServeRun:
        t = self.traffic
        if sample_pool:
            with PoolSampler(self.engine) as pool:
                outs, wall = self._serve(t, self.seconds)
            self.pool_summary = pool.summary()
        else:
            outs, wall = self._serve(t, self.seconds)
        st = self.engine.stats()
        return ServeRun(t.prompts, t.arrivals,
                        [np.asarray(o) for o in outs], dict(st["ttft"]),
                        dict(st["request_status"]), wall, st)

    def work(self, run: ServeRun) -> ServeWork:
        return ServeWork.of(self.cell.arch, self.cell.config["model"], run.prompts,
                            run.outputs, self.engine_cfg["prefill_chunk"])

    def free(self) -> None:
        self.engine = None
        gc.collect()

    def check(self, run: ServeRun, lowp=None) -> dict:
        """The widest gap of the sample (and of the control, with
        `lowp`), after the program's state is freed."""
        params = weights.make_params(self.shapes, self.seed, self.mc.param_dtype,
                                     arch=self.cell.arch)
        sample = check.serve_sample(run.prompts, run.outputs, self.seed,
                                    self.cell.limits["sample_requests"])
        return check.serve_gap(self.cell.arch, self.cell.config["model"], params,
                               [(run.prompts[i], run.outputs[i]) for i in sample],
                               lowp=lowp)
