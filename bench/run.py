#!/usr/bin/env python3
"""On-chip benchmark of the FLASH-D serving and training stack.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of `BENCHMARK.json` on the chips of this machine: sets it
up (weights from the seed, compile, warm-up), measures one window of
`--seconds`, decides `correct` against the plain reference, and prints
one JSON line last on standard output. `--trace 0` reports the cell's
end-to-end metrics, `--trace 1` its per-layer metrics from a profiler
trace of the same window. Without a TPU, or with fewer chips than the
cell asks for, it prints no result and exits 2.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

from harness import cells, check  # noqa: E402
from harness.clock import CompileClock, Phases  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def p95(values):
    """Nearest-rank 95th percentile (inf counts as the largest value)."""
    v = sorted(values)
    return v[max(0, math.ceil(0.95 * len(v)) - 1)] if v else math.inf


def device_info(jax, n_used: int) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs[:n_used]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class Context:
    """What a per-layer metric reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def run_serve(cell, args, phases, clock, jax, traced, root):
    from harness.serve_driver import ServeCell

    drv = ServeCell(cell, args.seed, args.seconds, phases, clock)
    setup_s = phases.total()
    mark = clock.mark()
    with traced() as tr:
        run = drv.window(sample_pool=bool(args.trace))
    window_compiles = clock.names_since(mark)
    if args.trace:
        log(f"{drv.pool_summary}; peak {run.stats['peak_active']} requests "
            f"running")
    work = drv.work(run)
    dev = device_info(jax, cell.chips)
    drv.free()
    t_ref = time.monotonic()
    numbers = drv.check(run)
    log(f"reference: {time.monotonic() - t_ref:.1f}s over "
        f"{numbers['tokens_compared']} served tokens")
    ttft = run.ttft_s()
    finite = sorted(t for t in ttft if math.isfinite(t))
    if finite:
        log(f"ttft over {len(ttft)} requests: median "
            f"{1e3 * finite[len(finite) // 2]:.1f} ms, p95 "
            f"{1e3 * p95(list(ttft)):.1f} ms; {len(ttft) - len(finite)} "
            f"without a first token; window {run.window_s:.2f}s, "
            f"{run.output_tokens()} output tokens")
    e2e = {"setup_s": setup_s,
           "ttft_p95_ms": 1e3 * p95(list(ttft)),
           "output_tok_s": run.output_tokens() / run.window_s}
    failed = run.failed() + sum(1 for t in ttft if not math.isfinite(t))
    if cell.mix.get("deadline") == "window":
        failed = run.failed()
    ctx = Context(kind="serve", run=run, work=work, trace=tr.get("trace"),
                  window_s=run.window_s)
    return dict(e2e=e2e, numbers=numbers, ctx=ctx, dev=dev,
                attempted=len(run.prompts), failed=failed,
                window_compiles=window_compiles)


def run_train(cell, args, phases, clock, jax, traced, root):
    from harness.train_driver import TrainCell

    drv = TrainCell(cell, args.seed, args.seconds, phases, clock, root)
    box = {}

    def start():
        box["setup_s"] = phases.total()
        box["cm"] = traced()
        box["tr"] = box["cm"].__enter__()

    try:
        steplog = drv.run(on_window_start=start)
    finally:
        if "cm" in box:
            box["cm"].__exit__(None, None, None)
    dev = device_info(jax, cell.chips)
    gc.collect()
    t_ref = time.monotonic()
    refr = drv.reference()
    numbers = check.train_numbers(steplog["readings"], refr)
    log(f"reference: {time.monotonic() - t_ref:.1f}s; losses program "
        f"{steplog['readings']['losses']} reference {refr['losses']}; "
        f"worst gradient leaf {numbers['_grad_leaf']}, worst change leaf "
        f"{numbers['_update_leaf']}, worst gradient error leaf "
        f"{numbers['_error_leaf']}, left out {numbers['_left_out']}")
    work = drv.work()
    log(f"window {steplog['window_s']:.2f}s, {steplog['steps']} steps; "
        f"{steplog['gc_n']} collections took {steplog['gc_s']:.3f}s of it")
    e2e = {"setup_s": box["setup_s"],
           "train_tok_s": steplog["steps"] * work.tokens() / steplog["window_s"]}
    ctx = Context(kind="train", steps=steplog["steps"], work=work,
                  trace=box["tr"].get("trace"), window_s=steplog["window_s"])
    return dict(e2e=e2e, numbers=numbers, ctx=ctx, dev=dev,
                attempted=steplog["steps"], failed=steplog["skipped"],
                window_compiles=steplog["window_compiles"])


def main(argv=None, root: str = ROOT, require_tpu: bool = True) -> int:
    args = parse(argv)
    cell = cells.find_cell(root, args.workload)
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell.chips):
        log(f"needs {cell.chips} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform} device(s)")
        return 2
    jax.config.update("jax_compilation_cache_dir", os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    sys.path.insert(0, os.path.join(root, "src"))
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        log(f"the program is not in this checkout: {e}")
        return 2
    phases = Phases(T_START)
    phases.lap("jax_init")
    clock = CompileClock()
    from harness import trace as tracing

    trace_dir = os.path.join(root, ".bench_run", "trace")

    class traced:
        """The window's profiler trace, when --trace 1."""

        def __enter__(self):
            self.info = {}
            if args.trace:
                self.cm = tracing.record(trace_dir)
                self.info = self.cm.__enter__()
            return self.info

        def __exit__(self, *exc):
            if args.trace:
                self.cm.__exit__(*exc)
                t0 = time.monotonic()
                self.info["trace"] = tracing.load(self.info["path"],
                                                  self.info["window_s"])
                log(f"trace {os.path.getsize(self.info['path']) / 2**20:.1f} MiB "
                    f"over {self.info['window_s']:.1f}s, read in "
                    f"{time.monotonic() - t0:.1f}s")
            return False

    kind = cell.config["deployment"]["kind"]
    res = (run_serve if kind == "serve" else run_train)(
        cell, args, phases, clock, jax, traced, root)
    split = ", ".join(f"{k} {v:.2f}s" for k, v in phases.split.items())
    log(f"setup {res['e2e']['setup_s']:.2f}s: {split}; compile "
        f"{clock.seconds:.2f}s over {clock.count} programs")
    if res["window_compiles"]:
        log(f"WARNING: compiled inside the measured window: {res['window_compiles']}")
    out = {"attempted": int(res["attempted"]), "failed": int(res["failed"]),
           "device": res["dev"]}
    if args.trace:
        ctx = res["ctx"]
        tr = ctx.trace
        from harness.peaks import peaks_for

        ctx.peaks = peaks_for(res["dev"]["kind"])
        out["metrics"] = cells.read_metrics(cell, ctx)
        for note in getattr(ctx, "notes", []):
            log(note)
        used = tr.devices[: cell.chips]
        out["device"]["busy_s"] = sum(tracing.busy_s(d) for d in used) / max(len(used), 1)
        out["device"]["window_s"] = tr.window_s
        out["breakdown"] = {
            "device_ops": tracing.top_ops(tr),
            "idle_gaps": tracing.idle_gaps(tr, tracing.host_spans(tr.path))}
    else:
        out["metrics"] = {m.name: {"value": float(res["e2e"][m.name]), "unit": m.unit}
                          for m in cell.end_to_end}
    ok, compared = check.verdict(res["numbers"], cell.limits)
    for name, c in compared.items():
        log(f"compared {name}: {c['value']} limit {c['limit']}")
    result = {"correct": bool(ok)}
    result.update(out)
    result["compared"] = compared
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
