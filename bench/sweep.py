#!/usr/bin/env python3
"""Knee sweep of an open-loop serving cell, on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 3,4,5

One process sets the cell up once and serves one window per rate, with
the mix's rate replaced. Per rate it prints the requests offered, the
time to first token (median, p95, and p95 of the first and the second
half of the arrivals) and the drain: seconds from the last arrival to the
end of the window. The rule that reads the knee from this table is in
PERF.md; the cell's mix file holds the rate chosen from it.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(ROOT, "src"))

from harness import cells, traffic  # noqa: E402
from harness.clock import CompileClock, Phases  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    args = p.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from harness.serve_driver import ServeCell

    cell = cells.find_cell(ROOT, args.workload)
    phases = Phases(T_START)
    drv = ServeCell(cell, args.seed, args.seconds, phases, CompileClock())
    print(f"setup {phases.total():.1f}s", flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_rps=rate)
        drv.cell = dataclasses.replace(cell, mix=mix)
        drv.traffic = traffic.serve_traffic(mix, args.seed, args.seconds,
                                            drv.mc.vocab_size)
        run = drv.window()
        t = run.ttft_s()
        n = len(t)
        half = n // 2

        def pct(v, q):
            v = sorted(v)
            return v[max(0, math.ceil(q * len(v)) - 1)] if v else math.inf

        row = {"rate": rate, "requests": n, "window_s": run.window_s,
               "drain_s": run.window_s - float(run.arrivals[-1]),
               "ttft_p50_ms": 1e3 * pct(t, 0.5), "ttft_p95_ms": 1e3 * pct(t, 0.95),
               "p95_first_half_ms": 1e3 * pct(t[:half], 0.95),
               "p95_second_half_ms": 1e3 * pct(t[half:], 0.95),
               "output_tok_s": run.output_tokens() / run.window_s,
               "prompt_tok_s": float(sum(len(q) for q in run.prompts)) / run.window_s}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
