"""The one traffic generator: every mix is a data file of parameters.

Every seed gets the same sizes and gaps in the same order; the seed
draws the token ids (and, elsewhere, the weights). Sizes are the
quantiles of the mix's distribution at (i + 0.5) / n, i < n, in one fixed
shuffled order. A seed that reordered them changed the work: at 0.8 x
the knee the TTFT tail depends on which requests queue behind the
longest prompts, and reordered seeds moved docqa's p95 between 2.2 and
5.6 s while two runs of one seed agreed within 5% (TPU v5e).

Kinds:

- `open_loop`: requests due at a fixed `rate_rps` (Poisson: exponential
  gaps) for the run's `--seconds`; `prompt` lengths from a lognormal
  (`median`, `sigma`) clipped to [`min`, `max`]; `max_new_tokens` each.
- `batch`: `requests` requests, all due at 0, lengths as above.
- `train`: batches of `seq_len` tokens from `synthetic.SyntheticLM`.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class ServeTraffic:
    arrivals: np.ndarray  # [n] seconds from the start of the window
    prompts: List[np.ndarray]  # int32 token ids
    max_new_tokens: int

    @property
    def max_prompt(self) -> int:
        return max(len(p) for p in self.prompts)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream per (seed, purpose); any seed size."""
    return np.random.default_rng([int(seed), int(stream)])


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(np.int64)


def exponential_quantiles(n: int, rate: float) -> np.ndarray:
    return np.array([-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)])


def request_count(mix: dict, seconds: float) -> int:
    if mix["kind"] == "open_loop":
        return max(1, int(round(mix["rate_rps"] * seconds)))
    return int(mix["requests"])


def serve_traffic(mix: dict, seed: int, seconds: float, vocab: int,
                  stream: int = 0) -> ServeTraffic:
    """The mix's requests for a window of `seconds`; `stream` 1 draws the
    token ids of a warm-up replay, which the window never sees."""
    n = request_count(mix, seconds)
    p = mix["prompt"]
    order = rng_for(0, 5)  # the same order for every seed
    lengths = order.permutation(
        lognormal_quantiles(n, p["median"], p["sigma"], p["min"], p["max"]))
    rng = rng_for(seed, 2 * stream)
    if mix["kind"] == "open_loop":
        gaps = order.permutation(exponential_quantiles(n, mix["rate_rps"]))
        arrivals = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    elif mix["kind"] == "batch":
        arrivals = np.zeros(n)
    else:
        raise ValueError(f"not a serving mix: {mix['kind']!r}")
    prompts = [rng.integers(0, vocab, int(m)).astype(np.int32) for m in lengths]
    return ServeTraffic(arrivals, prompts, int(mix["max_new_tokens"]))


def warmup_prompts(seed: int, vocab: int, lengths) -> List[np.ndarray]:
    """Prompts for warming up, from a stream the window never draws."""
    rng = rng_for(seed, 3)
    return [rng.integers(0, vocab, int(m)).astype(np.int32) for m in lengths]
