"""The benchmark's files, and a whole run of cells added as files only.

`toy.*` cells live in a tree of their own (`benchtiny.make`): a
configuration, a mix, limits and entries, and no code. The harness finds
them by name and runs set-up, window, reference check, metric readers
and the last line at toy size on the CPU, below the command's TPU check.
"""

import json
import os
import re
import subprocess
import sys

import pytest

import benchtiny
import run as bench_run
from harness import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def _bench():
    with open(os.path.join(benchtiny.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_entries_name_files_that_agree():
    b = _bench()
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    names += [w["name"] for w in b["workloads"]] + [c["name"] for c in b["configs"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for m in b["end_to_end"] + b["per_layer"])
    for w in b["workloads"]:
        cell = cells.find_cell(benchtiny.REPO, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.chips == w["chips"] == cell.config["deployment"]["chips"]
        assert {m.name for m in cell.end_to_end} > {"setup_s"}
        assert cell.per_layer, w["name"]
        assert cell.limits["numbers"]
    for m in b["per_layer"]:
        reader = cells.load_reader(benchtiny.BENCH_DIR, m["name"])
        assert (reader.LAYER, reader.MOVES) == (m["layer"], m["moves"])


def _run(root, workload, seed, trace=0, seconds=1.0):
    out = subprocess_free_main(root, workload, seed, trace, seconds)
    assert RESULT_KEYS <= set(out)
    assert list(out)[-1] == "compared"
    return out


def subprocess_free_main(root, workload, seed, trace, seconds, capsys=None):
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                             "--seconds", str(seconds), "--trace", str(trace)],
                            root=root, require_tpu=False)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return benchtiny.make(str(tmp_path_factory.mktemp("toybench")))


def test_serving_cell_added_as_files_runs_end_to_end(toy_root):
    out = _run(toy_root, "toy.chat", 2**31 + 99)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"ttft_p95_ms", "setup_s"}
    assert out["attempted"] == 4 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert out["compared"]["logit_gap"]["limit"] == benchtiny.SERVE_LIMIT


def test_batch_cell_ends_at_the_window(toy_root):
    out = _run(toy_root, "toy.gen", 5)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"output_tok_s", "setup_s"}
    assert out["metrics"]["output_tok_s"]["value"] > 0


def test_training_cell_added_as_files_runs_end_to_end(toy_root):
    out = _run(toy_root, "toyt.tr", 3)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"train_tok_s", "setup_s"}
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["compared"]) == set(benchtiny.TRAIN_LIMITS)


def test_metric_readers_run_on_a_trace(toy_root, monkeypatch):
    from harness import peaks

    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    out = _run(toy_root, "toy.gen", 6, trace=1)
    # the CPU trace has no TPU plane: the readers find nothing and say so
    assert out["metrics"] == {}
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_new_metric_is_a_file_and_an_entry(toy_root, tmp_path):
    import shutil

    root = str(tmp_path / "more")
    shutil.copytree(toy_root, root, symlinks=True)
    with open(os.path.join(root, "bench", "metrics", "requests_seen.py"), "w") as f:
        f.write('LAYER = "serving loop"\nMOVES = "ttft_p95_ms"\n\n\n'
                'def read(ctx):\n    return len(ctx.run.prompts)\n')
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        b = json.load(f)
    b["per_layer"].append({"name": "requests_seen", "unit": "requests",
                           "better": "higher", "source": "program_counter",
                           "layer": "serving loop", "moves": "ttft_p95_ms",
                           "workloads": ["toy.chat"]})
    with open(path, "w") as f:
        json.dump(b, f)
    cell = cells.find_cell(root, "toy.chat")
    assert [m.name for m in cell.per_layer] == ["idle_share.prefill", "requests_seen"]


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(benchtiny.BENCH_DIR, "run.py"),
         "--workload", "qwen2-1.5b.docqa-poisson", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=benchtiny.REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
