"""Trace reduction: interval arithmetic, and the whole reduction on a
small trace recorded on a TPU v5e (`data/`)."""

import glob
import os

import numpy as np
import pytest

import benchtiny
from harness import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_exclusive_time():
    iv = np.array([[0, 10], [5, 15], [20, 30]], float)
    assert T.union_ns(iv) == 25
    assert T.union_ns(np.zeros((0, 2))) == 0
    coll = np.array([[8, 22]], float)
    # 15..20 is covered by nothing else
    assert T.exclusive_ns(coll, iv) == 5
    assert T.sum_ns(iv) == 30


def _device(names, iv):
    iv = np.asarray(iv, float)
    return T.Device("/device:TPU:0", list(names), iv, ["m"], iv[:1])


def test_idle_share_top_ops_and_gaps():
    d = _device(["a", "b", "a"], [[0, 2e8], [3e8, 4e8], [9e8, 1e9]])
    tr = T.Trace([d], window_s=1.0)
    assert T.idle_share(tr) == pytest.approx(0.6)
    assert T.top_ops(tr)[0] == ["a", pytest.approx(0.3)]
    gaps = T.idle_gaps(tr, [("bench.data", 4e8, 9e8)])
    assert gaps[0] == ["bench.data", pytest.approx(0.5)]
    assert gaps[1] == ["untraced host", pytest.approx(0.1)]
    assert T.idle_share(T.Trace([], 1.0)) is None


def test_reduction_of_a_recorded_chip_trace():
    paths = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    assert paths, "the recorded trace is missing"
    tr = T.load(paths[0], window_s=1.0)
    assert len(tr.devices) == 1
    d = tr.devices[0]
    assert len(d.ops) > 0 and len(d.modules) > 0
    busy = T.busy_s(d)
    assert 0 < busy <= T.union_ns(np.array([[d.ops[:, 0].min(), d.ops[:, 1].max()]])) * 1e-9
    from harness.readers import is_pallas

    kernels = d.select(is_pallas)
    assert len(kernels) > 0
    assert T.sum_ns(kernels) < T.sum_ns(d.ops)
    assert T.union_ns(d.modules) >= T.union_ns(kernels)
