"""The program's host spans and the split of device idle time
(`harness/spans.py`): on a hand-built trace whose split is worked out by
hand, and on a small serving trace recorded on a TPU v5e
(`data/v5e_serve_spans.xplane.pb`, made by `record_serve_spans.py`)."""

import os
import types

import numpy as np
import pytest

import benchtiny  # noqa: F401  puts bench/ on the path
from harness import spans as SP
from harness import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "v5e_serve_spans.xplane.pb")
KERNELS = ("flashd_varlen", "flashd_decode_paged", "flashd_decode",
           "flashd_decode_fused", "flashd_fwd", "flashd_bwd_dq",
           "flashd_bwd_dkv", "fa2_fwd")


def _span(name, start, end, **args):
    return SP.Span("repro.serve." + name, "python", float(start), float(end), args)


def _hand_built():
    """A 1000 ns window: four ops (idle 650 ns, 50 of it inside the first
    program execution) under a step with sync and admit children, a wait
    for arrivals, and a plan span outside any step."""
    ops = np.array([[0, 100], [150, 200], [300, 400], [600, 700]], float)
    mods = np.array([[0, 200], [300, 400], [600, 700]], float)
    dev = T.Device("/device:TPU:0", ["op"] * 4, ops, ["jit__mixed_fn"] * 3, mods)
    host = SP.HostTrace([
        _span("step", 180, 420, kind="mixed"),
        _span("sync", 190, 250),
        _span("admit", 250, 290),
        _span("idle", 450, 580),
        _span("plan", 700, 760),
    ], window=(0.0, 1000.0))
    return T.Trace([dev], window_s=1e-6, path="hand-built"), host


def test_merge_and_gaps():
    iv = np.array([[5, 9], [0, 3], [2, 4], [8, 12]], float)
    np.testing.assert_array_equal(SP.merge(iv), [[0, 4], [5, 12]])
    np.testing.assert_array_equal(SP.gaps(SP.merge(iv), -1, 20),
                                  [[-1, 0], [4, 5], [12, 20]])
    np.testing.assert_array_equal(SP.gaps(np.zeros((0, 2)), 0, 3), [[0, 3]])


def test_innermost_span_names_each_piece():
    pieces = SP.innermost([_span("step", 0, 10), _span("sync", 2, 5)])
    assert pieces == [(0, 2, "repro.serve.step"), (2, 5, "repro.serve.sync"),
                      (5, 10, "repro.serve.step")]


def test_hand_built_split():
    tr, host = _hand_built()
    split = SP.idle_split(tr.devices[0], host, tr.window_s, "repro.serve.")
    assert split == {
        "device": 50, "sync": 50, "idle": 130,
        "repro.serve.admit": 40,
        # 290..300 between the children, and 400..420 after the program
        "repro.serve.step": 30,
        "repro.serve.plan": 60,
        # 420..450, 580..600 and 760..1000
        "untraced": 290}
    assert sum(split.values()) == pytest.approx(1e9 * tr.window_s * T.idle_share(tr))


def test_hand_built_host_share_and_note(monkeypatch):
    tr, host = _hand_built()
    monkeypatch.setattr(SP, "load", lambda path: host)
    ctx = types.SimpleNamespace(trace=tr)
    # admit 40 + step 30 + plan 60 of 1000 ns
    assert SP.host_share(ctx, "repro.serve.") == pytest.approx(13.0)
    (note,) = ctx.notes
    assert "idle_share 65.0000% = inside programs 5.0000% + host work 13.0000%" in note
    assert "sync 5.0000% + idle 13.0000% + untraced 29.0000%" in note
    assert "parts sum to 65.0000%" in note
    # a trace without the program's spans reads nothing
    assert SP.host_share(ctx, "repro.train.") is None


@pytest.fixture(scope="module")
def recorded():
    host = SP.load(RECORDED)
    lo, hi = host.window
    return T.load(RECORDED, (hi - lo) * 1e-9), host


def test_recorded_kernel_events_carry_their_names(recorded):
    from harness.readers import is_pallas

    tr, _ = recorded
    (dev,) = tr.devices
    names = {T.short_name(n) for n in dev.op_names if is_pallas(n)}
    assert names == {"flashd_varlen (custom-call)",
                     "flashd_decode_paged (custom-call)"}
    assert {n.split(" ")[0] for n in names} <= set(KERNELS)


def test_recorded_split_adds_up_to_the_idle_share(recorded):
    tr, host = recorded
    assert {s.name for s in host.spans} == {
        "repro.serve." + n for n in
        ("step", "plan", "pack", "dispatch", "sync", "commit", "admit", "idle")}
    ctx = types.SimpleNamespace(trace=tr)
    share = SP.host_share(ctx, "repro.serve.")
    idle = 100.0 * T.idle_share(tr)
    assert 0 < share <= idle
    split = SP.idle_split(tr.devices[0], host, tr.window_s, "repro.serve.")
    total = 100.0 * sum(split.values()) / (1e9 * tr.window_s)
    assert total == pytest.approx(idle, abs=0.1)


def test_recorded_syncs_end_after_the_program_they_wait_for(recorded):
    """Host and device share one clock: the k-th packed step or decode
    chunk program starts after the k-th dispatch span starts, and ends
    before the k-th sync span ends. In the recording the programs start
    0.50-0.74 ms after their dispatch began and the syncs end 2.06-2.21 ms
    after their program: both margins must lie in [0, 5] ms, which a
    clock offset of more than 0.5 ms either way would break."""
    tr, host = recorded
    (dev,) = tr.devices
    progs = sorted((s, e) for n, (s, e) in zip(dev.module_names, dev.modules)
                   if n.startswith(("jit__mixed_fn(", "jit__chunk_fn(")))
    syncs = [s for s in host.spans if s.name == "repro.serve.sync"]
    disps = [s for s in host.spans if s.name == "repro.serve.dispatch"]
    assert len(progs) == len(syncs) == len(disps) == 5
    for (start, end), sync, disp in zip(progs, syncs, disps):
        assert 0 <= start - disp.start <= 5e6
        assert 0 <= sync.end - end <= 5e6
