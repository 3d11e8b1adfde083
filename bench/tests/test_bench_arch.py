"""An architecture comes in by files alone, and the training reference is
laid over the cell's chips.

`toyw` (benchtiny) names its own module, `arch/toy_local.py`, which the
test tree holds beside the real `dense_gqa.py`; nothing of the harness
changes for it. `toyt4` is the dense training configuration on four
chips, run here on four virtual CPU devices in a child process."""

import io
import json
import os
import shutil
import subprocess
import sys
from contextlib import redirect_stdout

import pytest

import benchtiny
import run as bench_run
from harness import cells


def _run(root, workload, seed):
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert bench_run.main(["--workload", workload, "--seed", str(seed),
                               "--seconds", "1", "--trace", "0"],
                              root=root, require_tpu=False) == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return benchtiny.make(str(tmp_path_factory.mktemp("toyarch")))


def _with_config(toy_root, tmp_path, name, change):
    """A copy of the toy tree whose configuration `name` is changed."""
    root = str(tmp_path / "copy")
    shutil.copytree(toy_root, root, symlinks=True)
    path = os.path.join(root, "bench", "configs", name + ".json")
    with open(path) as f:
        conf = json.load(f)
    change(conf)
    with open(path, "w") as f:
        json.dump(conf, f)
    return root


def test_an_architecture_added_as_files_reads_correct(toy_root):
    cell = cells.find_cell(toy_root, "toyw.tr")
    assert cell.arch.__file__.endswith(os.path.join("arch", "toy_local.py"))
    out = _run(toy_root, "toyw.tr", 2**31 + 7)
    assert out["correct"] is True
    assert set(out["compared"]) == set(benchtiny.TRAIN_LIMITS)


def test_the_dense_module_fails_the_windowed_model(toy_root, tmp_path):
    root = _with_config(toy_root, tmp_path, "toyw",
                        lambda c: c.update(arch="dense_gqa"))
    out = _run(root, "toyw.tr", 2**31 + 7)
    assert out["correct"] is False
    assert out["compared"]["grad_error"]["value"] > 10 * benchtiny.TRAIN_LIMITS["grad_error"]


@pytest.mark.parametrize("change", [lambda c: c.pop("arch"),
                                    lambda c: c.update(arch="no_such_arch")],
                         ids=["missing", "unknown"])
def test_a_configuration_must_name_a_known_architecture(toy_root, tmp_path, change):
    root = _with_config(toy_root, tmp_path, "toyt", change)
    with pytest.raises(KeyError, match="arch"):
        cells.find_cell(root, "toyt.tr")


def test_a_module_draws_the_leaves_it_owns(toy_root):
    import jax
    import numpy as np
    from harness import weights

    local = cells.find_cell(toy_root, "toyw.tr").arch
    words = weights.seed_words(2**33 + 5)
    for name, owned in (("blocks/pos0/norm2", True), ("blocks/pos0/norm1", False)):
        mine = weights.init_leaf(name, (2, 64), words, np.float32, local)
        base = weights.init_leaf(name, (2, 64), words, np.float32)
        assert np.array_equal(mine, base) is not owned
    key = jax.random.fold_in(weights.base_key(words),
                             weights.zlib.crc32(b"blocks/pos0/norm2") & 0x7FFFFFFF)
    assert np.array_equal(weights.init_leaf("blocks/pos0/norm2", (2, 64), words,
                                            np.float32, local),
                          local.draw("blocks/pos0/norm2", (2, 64), key))


def test_the_windowed_module_counts_fewer_keys(toy_root):
    dense = cells.find_cell(toy_root, "toyt.tr")
    local = cells.find_cell(toy_root, "toyw.tr")
    m = local.config["model"]
    # 64 queries from 0: the dense count reads 1..64 keys, the window 1..8
    fl_d, by_d = dense.arch.mixer_work(m, [0.0], [64.0])
    fl_w, by_w = local.arch.mixer_work(m, [0.0], [64.0])
    assert fl_d[0] == dense.arch.attn_flops(m, 64 * 65 / 2)
    assert fl_w[0] == dense.arch.attn_flops(m, 36 + 8 * 56)
    # a decoded token at position 100 reads its last 8 positions' K/V
    assert local.arch.mixer_work(m, [100.0], [1.0])[1][0] == \
        dense.arch.mixer_work(m, [7.0], [1.0])[1][0]
    assert by_w[0] == by_d[0]


CHILD = r"""
import io, json, sys
from contextlib import redirect_stdout
sys.path[:0] = [sys.argv[2], sys.argv[3]]
import jax, numpy as np
import jax.numpy as jnp
import run as bench_run
from harness import cells, check, weights
from harness.clock import CompileClock, Phases
from harness.train_driver import CHECKED_STEPS, TrainCell

root = sys.argv[1]
buf = io.StringIO()
with redirect_stdout(buf):
    rc = bench_run.main(["--workload", "toyt4.tr", "--seed", "5", "--seconds", "1",
                         "--trace", "0"], root=root, require_tpu=False)
out = {"rc": rc, "run": json.loads(buf.getvalue().strip().splitlines()[-1])}

cell = cells.find_cell(root, "toyt4.tr")
drv = TrainCell(cell, 11, 1.0, Phases(0.0), CompileClock(), root)
laid = drv.reference()
layout = weights.spread(drv.param_shapes, jax.devices()[:4])
params = weights.make_params(drv.param_shapes, 11, jnp.float32, out_shardings=layout)
out["specs"] = sorted({str(s.spec) for s in jax.tree.leaves(
    jax.tree.map(lambda x: x.sharding, params))})
del params
one = check.train_reference(
    cell.arch, cell.config["model"], drv.opt,
    weights.make_params(drv.param_shapes, 11, jnp.float32, arch=cell.arch),
    [drv.data.data.batch(i) for i in range(CHECKED_STEPS)], CHECKED_STEPS,
    drv.delta_norms)
drv.data.close()
out["losses"] = [laid["losses"], one["losses"]]
for key in ("grad_norms", "delta_norms"):
    out[key] = max(abs(laid[key][k] - one[key][k]) / max(abs(one[key][k]), 1e-30)
                   for k in one[key])
out["moment"] = max(float(np.abs(laid["moment"][k] - one["moment"][k]).max()
                          / max(np.abs(one["moment"][k]).max(), 1e-30))
                    for k in one["moment"])
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_chips(toy_root):
    """A whole run of the four-chip toy cell, and its reference laid out
    and on one device, in a child with four virtual CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(benchtiny.REPO, "src"))
    p = subprocess.run(
        [sys.executable, "-c", CHILD, toy_root, benchtiny.BENCH_DIR,
         os.path.join(benchtiny.REPO, "src")],
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_chip_training_cell_runs_correct(four_chips):
    assert four_chips["rc"] == 0
    run = four_chips["run"]
    assert run["correct"] is True, run["compared"]
    assert run["device"]["count"] == 4


def test_reference_laid_over_four_chips_matches_one_device(four_chips):
    # leaves are split over the chips along one axis
    assert any("chips" in s for s in four_chips["specs"]), four_chips["specs"]
    laid, one = four_chips["losses"]
    assert laid == pytest.approx(one, rel=1e-6)
    # float32 round-off: the sharded sums add in another order
    assert four_chips["grad_norms"] < 1e-5
    assert four_chips["delta_norms"] < 1e-5
    assert four_chips["moment"] < 1e-5
