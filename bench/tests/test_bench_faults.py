"""`correct` comes out false when the timed path is broken underneath,
and for the control: each fault is planted in the program (or the
control in the reference's place) and a whole toy run is driven below
the command's TPU check. The toy limits sit between the sound readings
and these (benchtiny)."""

import io
import json
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import pytest

import benchtiny
import run as bench_run
from harness import check


def _run(root, workload, seed=4):
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert bench_run.main(["--workload", workload, "--seed", str(seed),
                               "--seconds", "1", "--trace", "0"],
                              root=root, require_tpu=False) == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return benchtiny.make(str(tmp_path_factory.mktemp("toyfaults")))


def _wrap_trainer(monkeypatch, fault):
    import repro.launch.train as lt

    real = lt.build_trainer

    def build(*a, **kw):
        init, step = real(*a, **kw)
        return init, fault(step)

    monkeypatch.setattr(lt, "build_trainer", build)


def test_token_altered_where_produced(toy_root, monkeypatch):
    import repro.serve.engine as eng

    real = eng.sample_token
    monkeypatch.setattr(eng, "sample_token",
                        lambda logits, key, cfg: (real(logits, key, cfg) + 1) % 8192)
    assert _run(toy_root, "toy.chat")["correct"] is False


def test_step_that_returns_its_state_unchanged(toy_root, monkeypatch):
    def fault(step):
        def f(state, batch):
            _, metrics = step(jax.tree.map(jnp.copy, state), batch)
            return state, metrics
        return f

    _wrap_trainer(monkeypatch, fault)
    out = _run(toy_root, "toyt.tr")
    assert out["correct"] is False
    assert out["compared"]["grad_norm_gap"]["value"] == pytest.approx(1.0)
    assert out["compared"]["grad_error"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(toy_root, monkeypatch):
    def fault(step):
        def f(state, batch):
            labels = batch["labels"].at[batch["labels"].shape[0] // 2:].set(-1)
            return step(state, dict(batch, labels=labels))
        return f

    _wrap_trainer(monkeypatch, fault)
    out = _run(toy_root, "toyt.tr")
    assert out["correct"] is False
    assert out["compared"]["grad_norm_gap"]["value"] > benchtiny.TRAIN_LIMITS["grad_norm_gap"]


def test_float8_control_is_not_correct(toy_root):
    """The control: the reference in float8 products, read against the
    float32 reference, fails a limit on every seed tried: the gradient's
    error by element, which a leaf's norm averages away."""
    from harness import cells
    from harness.clock import CompileClock, Phases
    from harness.train_driver import TrainCell

    cell = cells.find_cell(toy_root, "toyt.tr")
    drv = TrainCell(cell, 9, 1.0, Phases(0.0), CompileClock(), toy_root)
    ref = drv.reference()
    ctrl = check.train_numbers(drv.reference(lowp="fp8"), ref)
    ok, _ = check.verdict(ctrl, cell.limits)
    assert ok is False
    assert ctrl["grad_error"] > benchtiny.TRAIN_LIMITS["grad_error"]


def test_float8_control_of_a_serving_cell_is_not_correct(toy_root):
    from harness import cells
    from harness.clock import CompileClock, Phases
    from harness.serve_driver import ServeCell

    cell = cells.find_cell(toy_root, "toy.gen")
    drv = ServeCell(cell, 1, 1.0, Phases(0.0), CompileClock())
    run = drv.window()
    drv.free()
    r = drv.check(run, lowp="fp8")
    assert r["logit_gap"] <= benchtiny.SERVE_LIMIT < r["logit_gap_control"]
