"""Find a cell's pieces by the names that `BENCHMARK.json` gives.

A cell (`workloads` entry) names a configuration and a traffic mix:

- `configs/<config>.json`: the model as it is run (the program's
  `ModelConfig` keys under `model`), its published config under
  `published`, the deployment (`deployment`), and under `arch` the name
  of its architecture's module.
- `arch/<arch>.py`: the architecture's plain float32 reference and the
  counts of its work (`bench/arch/dense_gqa.py` says what a module
  provides).
- `traffic/<mix>.json`: the mix's parameters, read by `traffic.py`.
- `limits/<cell>.json`: the numbers that decide `correct`, each with its
  limit and the readings it was set from.
- `metrics/<metric>.py`: one per-layer metric's reader (`read(ctx)`).

Adding a cell, a mix or a metric adds files and entries; adding a
configuration of another architecture adds `arch/<name>.py` and names
it; no code here changes.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
import sys
import zlib
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: str = ""
    moves: str = ""
    bound: float = 0.0


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    arch: object  # the module arch/<config["arch"]>.py
    mix: dict
    limits: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]
    bench_dir: str


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def find_cell(root: str, workload: str) -> Cell:
    """The cell `workload` of `<root>/BENCHMARK.json`, with its files
    read from the directory of its configuration's file."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    conf_path = os.path.join(root, conf["file"])
    bench_dir = os.path.dirname(os.path.dirname(conf_path))
    e2e = [Metric(m["name"], m["unit"], m["better"], m["source"],
                  bound=m.get("bound", 0.0))
           for m in bench["end_to_end"] if _reports(m, workload)]
    reported = {m.name for m in e2e}
    per_layer = [Metric(m["name"], m["unit"], m["better"], m["source"],
                        layer=m["layer"], moves=m["moves"])
                 for m in bench["per_layer"]
                 if m["moves"] in reported and _reports(m, workload)]
    config = _load_json(conf_path)
    if "arch" not in config:  # no default: each configuration names its own
        raise KeyError(f"configuration {config['name']!r} names no \"arch\" "
                       f"(a module under {bench_dir}/arch/)")
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        arch=load_arch(bench_dir, config["arch"]),
        mix=_load_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")),
        limits=_load_json(os.path.join(bench_dir, "limits", workload + ".json")),
        end_to_end=e2e,
        per_layer=per_layer,
        bench_dir=bench_dir,
    )


def _load_module(bench_dir: str, kind: str, name: str):
    """`<kind>/<name>.py` as a module, registered under a name of its
    path (a dataclass in it needs its module in `sys.modules`)."""
    path = os.path.abspath(os.path.join(bench_dir, kind, name + ".py"))
    tag = name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{tag}_{zlib.crc32(path.encode()):08x}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(bench_dir: str, metric: str):
    """The module `metrics/<metric>.py`: `LAYER`, `MOVES` and `read(ctx)`."""
    return _load_module(bench_dir, "metrics", metric)


@functools.lru_cache(maxsize=None)
def load_arch(bench_dir: str, name: str):
    """The module `arch/<name>.py`, loaded once per process."""
    if not os.path.isfile(os.path.join(bench_dir, "arch", name + ".py")):
        raise KeyError(f"no architecture module arch/{name}.py in {bench_dir}")
    return _load_module(bench_dir, "arch", name)


def read_metrics(cell: Cell, ctx) -> Dict[str, dict]:
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for m in cell.per_layer:
        value = load_reader(cell.bench_dir, m.name).read(ctx)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out
