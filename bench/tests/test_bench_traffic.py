"""The traffic generator and the training stream: deterministic by seed,
inside the mix's bounds, and the same work for every seed."""

import json
import os

import numpy as np

import benchtiny
from harness import traffic
from harness.synthetic import SyntheticLM

MIXES = os.path.join(benchtiny.BENCH_DIR, "traffic")
BIG_SEED = 2**31 + 12345


def _mix(name):
    with open(os.path.join(MIXES, name + ".json")) as f:
        return json.load(f)


def test_every_serving_mix_is_seeded_bounded_and_same_work():
    for name in sorted(os.listdir(MIXES)):
        mix = _mix(name[:-5])
        if mix["kind"] == "train":
            continue
        a = traffic.serve_traffic(mix, BIG_SEED, 3.0, 1000)
        b = traffic.serve_traffic(mix, BIG_SEED, 3.0, 1000)
        c = traffic.serve_traffic(mix, 7, 3.0, 1000)
        assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))
        assert np.array_equal(a.arrivals, b.arrivals)
        # every seed: the same sizes and arrivals in the same order, other ids
        assert [len(p) for p in a.prompts] == [len(p) for p in c.prompts]
        assert np.array_equal(a.arrivals, c.arrivals)
        assert not np.array_equal(a.prompts[0], c.prompts[0])
        lens = sorted(len(p) for p in a.prompts)
        p = mix["prompt"]
        assert p["min"] <= lens[0] and lens[-1] <= p["max"]
        assert all(0 <= t.min() and t.max() < 1000 for t in a.prompts)
        assert a.arrivals[0] == 0 and np.all(np.diff(a.arrivals) >= 0)
        assert len(a.prompts) == traffic.request_count(mix, 3.0)


def test_open_loop_arrivals_span_the_window_at_the_rate():
    mix = {"kind": "open_loop", "rate_rps": 8.0, "max_new_tokens": 4,
           "prompt": {"median": 100, "sigma": 1.0, "min": 10, "max": 1000}}
    t = traffic.serve_traffic(mix, 3, 30.0, 50)
    assert len(t.prompts) == 240
    assert 27.0 < t.arrivals[-1] < 31.0
    lens = np.array([len(p) for p in t.prompts])
    assert 90 <= np.median(lens) <= 110


def test_synthetic_stream_is_a_pure_function_of_seed_and_step():
    a = SyntheticLM(500, 64, 2, BIG_SEED)
    b = SyntheticLM(500, 64, 2, BIG_SEED)
    x, y = a.batch(3), b.batch(3)
    assert np.array_equal(x["tokens"], y["tokens"])
    assert np.array_equal(x["tokens"][:, 1:], x["labels"][:, :-1])
    assert not np.array_equal(a.batch(4)["tokens"], x["tokens"])
    assert x["tokens"].shape == (2, 64) and x["tokens"].max() < 500
    # the Markov table makes some successors far more likely than chance
    nxt = a._succ[x["tokens"][:, :-1]]
    hit = (nxt == x["labels"][:, :-1, None]).any(-1).mean()
    assert hit > 0.5


def test_synthetic_walk_is_the_plain_markov_walk():
    """The walk over Python ints draws the same tokens as the plain
    per-position numpy walk of the program's stream."""
    lm = SyntheticLM(3000, 256, 2, BIG_SEED)
    got = lm.batch(5)
    rng = np.random.default_rng([BIG_SEED, 3, 5])
    uni = np.minimum(np.searchsorted(lm._cdf, rng.random((2, 257))), 2999)
    use_markov = rng.random((2, 257)) < lm.markov_weight
    pick = rng.integers(0, 4, size=(2, 257))
    toks = np.empty((2, 257), np.int32)
    toks[:, 0] = uni[:, 0]
    for t in range(1, 257):
        succ = lm._succ[toks[:, t - 1], pick[:, t]]
        toks[:, t] = np.where(use_markov[:, t], succ, uni[:, t])
    assert np.array_equal(got["tokens"], toks[:, :-1])
    assert np.array_equal(got["labels"], toks[:, 1:])
