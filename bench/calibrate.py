#!/usr/bin/env python3
"""Readings that the limits in `limits/<cell>.json` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, in one process, it runs the cell's set-up and window as
`run.py` does and prints one JSON line with the numbers `correct`
compares: the program's, and those of the control and of the planted
faults, each judged against the float32 reference in the same way:

- serving: the control is the reference with every product rounded to
  float8 (one precision below the configuration's bfloat16); it reads the
  gap of the token that the control puts first;
- training: the control is the reference trained in float8 products; the
  fault `half_batch` is the reference with the loss taken over half of
  each batch. A step that returns its state unchanged reads 1 on the
  gradient by construction and needs no run.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(ROOT, "src"))

from harness import cells, check  # noqa: E402
from harness.clock import CompileClock, Phases  # noqa: E402


def readings(cell, seed: int, seconds: float, clock, root: str) -> dict:
    phases = Phases(time.monotonic())
    if cell.config["deployment"]["kind"] == "serve":
        from harness.serve_driver import ServeCell

        drv = ServeCell(cell, seed, seconds, phases, clock)
        run = drv.window()
        drv.free()
        r = drv.check(run, lowp="fp8")
        return {"logit_gap": r["logit_gap"],
                "control.logit_gap": r["logit_gap_control"],
                "tokens_compared": r["tokens_compared"]}
    from harness.train_driver import TrainCell

    drv = TrainCell(cell, seed, seconds, phases, clock, root)
    prog = drv.run()["readings"]
    gc.collect()
    refr = drv.reference()
    out = check.train_numbers(prog, refr)
    for tag, kw in (("control", {"lowp": "fp8"}), ("half_batch", {"half_batch": True})):
        other = drv.reference(**kw)
        for k, v in check.train_numbers(other, refr).items():
            out[f"{tag}.{k}"] = v
    out["losses"] = prog["losses"]
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = cells.find_cell(ROOT, args.workload)
    clock = CompileClock()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        r = readings(cell, seed, args.seconds, clock, ROOT)
        r = {k: v for k, v in r.items() if not k.endswith("_left_out")}
        print(json.dumps({"seed": seed, "s": round(time.monotonic() - t0, 1), **r}),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
