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


def _hlo(name, opcode):
    return f"%{name}.1 = bf16[8,1536]{{1,0}} {opcode}(%p.2), replica_groups={{{{0,1,2,3}}}}"


def test_collective_share_counts_exposed_time_on_the_worst_chip():
    from harness import cells
    from run import Context

    fusion = "%fusion.3 = bf16[8,1536]{1,0} fusion(%a.1), kind=kLoop, calls=%f"
    loop = "%while.4 = (s32[], bf16[8,1536]{1,0}) while(%t.1), condition=%c, body=%b"
    # chip 0: an all-reduce that a fusion overlaps for half its length,
    # both inside a loop whose own event spans them
    d0 = T.Device("/device:TPU:0", [loop, fusion, _hlo("all-reduce-start", "all-reduce-start")],
                  np.array([[0, 150], [0, 100], [50, 150]], float), ["m"],
                  np.array([[0, 150]], float))
    # chip 1: an all-gather alone, a collective-permute-done under a
    # fusion, and a fusion named after a collective, which is no collective
    d1 = T.Device("/device:TPU:1", [_hlo("all-gather", "all-gather"), fusion,
                                    _hlo("collective-permute-done", "collective-permute-done"),
                                    _hlo("all-reduce-scatter-fusion", "fusion")],
                  np.array([[0, 80], [100, 200], [120, 140], [300, 400]], float), ["m"],
                  np.array([[0, 400]], float))
    ctx = Context(trace=T.Trace([d0, d1], window_s=1e-6))
    reader = cells.load_reader(benchtiny.BENCH_DIR, "collective_share.train")
    # chip 0 exposes 50 of its 100 ns, chip 1 all 80 of its 80 (+20 hidden)
    assert reader.read(ctx) == pytest.approx(8.0)
    assert "worst /device:TPU:1" in ctx.notes[0]
    alone = Context(trace=T.Trace([d0], window_s=1e-6))
    assert reader.read(alone) == pytest.approx(5.0)
    none = Context(trace=T.Trace([_device(["a"], [[0, 10]])], window_s=1e-6))
    assert reader.read(none) is None
