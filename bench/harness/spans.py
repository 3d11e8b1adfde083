"""The program's own host spans in a window's trace, and the split of the
device's idle time by what the host was doing.

The program writes `repro.*` spans with `jax.profiler.TraceAnnotation`
(serving loop: `repro.serve.step` and its children `plan`, `pack`,
`dispatch`, `sync`, `commit`, `admit`, and `repro.serve.idle` while it
waits for arrivals; train loop: `repro.train.step` with `batch`,
`dispatch`, `sync`, `commit`, and `repro.train.ckpt`). They are host
events on the profiler's clock, the clock of the device events that
`trace.py` reads. `load(path)` reads them with their thread (the trace
line) and args; `idle_split` puts each nanosecond of the window in which
no device operation ran into one of five parts:

- `device`: inside a program execution (`XLA Modules`): device-side;
- a span name: between executions, under that innermost `repro.*` span
  other than `*.sync` and `*.idle` (its self time): host work;
- `sync`: between executions, under a `*.sync` span;
- `idle`: between executions, under a `*.idle` span;
- `untraced`: between executions, under no `repro.*` span.

The window on the trace's clock runs from the end of the profiler's
`start_trace` call to the start of its `stop_trace` call, which the
Python tracer records on the chip; without them it is [0, window_s).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from harness import trace as tracing

PREFIX = "repro."
_START = re.compile(r"^\$profiler\.py:\d+ start_trace$")
_STOP = re.compile(r"^\$profiler\.py:\d+ stop_trace$")
PARTS = ("device", "sync", "idle", "untraced")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    thread: str
    start: float  # ns, trace clock
    end: float
    args: dict


@dataclasses.dataclass
class HostTrace:
    spans: List[Span]
    window: Optional[Tuple[float, float]]  # (start, end) ns, if recorded


def load(path: str) -> HostTrace:
    """Every `repro.*` host span, and the window's bounds where the Python
    tracer recorded `start_trace`/`stop_trace`."""
    from jax.profiler import ProfileData

    spans, start, stop = [], None, None
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                name = e.name
                if name.startswith(PREFIX):
                    spans.append(Span(name, ln.name, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      dict(e.stats)))
                elif name.startswith("$profiler.py"):
                    if _START.match(name):
                        start = e.start_ns + e.duration_ns
                    elif _STOP.match(name):
                        stop = e.start_ns
    spans.sort(key=lambda s: (s.start, -s.end))
    window = (start, stop) if start is not None and stop is not None else None
    return HostTrace(spans, window)


def merge(iv: np.ndarray) -> np.ndarray:
    """The union of [start, end) intervals as sorted disjoint intervals."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > reach[:-1]
    starts = iv[new, 0]
    last = np.r_[np.nonzero(new)[0][1:] - 1, len(iv) - 1]
    return np.stack([starts, reach[last]], axis=1)


def gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The parts of [lo, hi) that the disjoint sorted `busy` leaves free."""
    b = busy[(busy[:, 1] > lo) & (busy[:, 0] < hi)] if len(busy) else busy
    if len(b) == 0:
        return np.array([[lo, hi]]) if hi > lo else np.zeros((0, 2))
    b = np.clip(b, lo, hi)
    s = np.r_[lo, b[:, 1]]
    e = np.r_[b[:, 0], hi]
    keep = e > s
    return np.stack([s[keep], e[keep]], axis=1)


def innermost(spans: List[Span]) -> List[Tuple[float, float, str]]:
    """The timeline as (start, end, name) pieces, each named by the
    innermost span that covers it (the latest started; spans on other
    threads count too)."""
    edges = sorted({t for s in spans for t in (s.start, s.end)})
    out, active, i = [], [], 0
    order = sorted(spans, key=lambda s: s.start)
    for a, b in zip(edges[:-1], edges[1:]):
        while i < len(order) and order[i].start <= a:
            active.append(order[i])
            i += 1
        active = [s for s in active if s.end > a]
        if active:
            top = max(active, key=lambda s: (s.start, -s.end))
            out.append((a, b, top.name))
    return out


def _part(name: str) -> str:
    leaf = name.rsplit(".", 1)[-1]
    return leaf if leaf in ("sync", "idle") else name


def idle_split(dev: tracing.Device, host: HostTrace, window_s: float,
               prefix: str) -> Dict[str, float]:
    """Nanoseconds of `dev`'s idle time in the window, by part (see the
    module docstring); span parts are keyed by span name."""
    lo, hi = host.window or (0.0, window_s * 1e9)
    ops = merge(dev.ops)
    idle = gaps(ops, lo, hi)
    out = {p: 0.0 for p in PARTS}
    between = gaps(merge(np.concatenate([dev.ops, dev.modules])), lo, hi)
    out["device"] = tracing.sum_ns(idle) - tracing.sum_ns(between)
    pieces = innermost([s for s in host.spans if s.name.startswith(prefix)])
    j = 0
    for gs, ge in between:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            c = min(ge, pieces[k][1]) - max(gs, pieces[k][0])
            if c > 0:
                part = _part(pieces[k][2])
                out[part] = out.get(part, 0.0) + c
                covered += c
            k += 1
        out["untraced"] += (ge - gs) - covered
    return out


def host_share(ctx, prefix: str) -> Optional[float]:
    """Percent of the window in which the device was idle between program
    executions while the host ran a `prefix` span other than sync or idle;
    None where the trace holds no such span. Adds the whole split of the
    idle share to `ctx.notes`."""
    if not ctx.trace.devices or ctx.trace.window_s <= 0:
        return None
    # the device whose idle share `trace.idle_share` reports
    dev = min(ctx.trace.devices, key=tracing.busy_s)
    host = load(ctx.trace.path)
    if not any(s.name.startswith(prefix) for s in host.spans):
        return None
    split = idle_split(dev, host, ctx.trace.window_s, prefix)
    pct = {k: 100.0 * v / (ctx.trace.window_s * 1e9) for k, v in split.items()}
    work = sum(v for k, v in pct.items() if k not in PARTS)
    spans = ", ".join(f"{k[len(prefix):]} {v:.4f}%" for k, v in
                      sorted(pct.items(), key=lambda kv: -kv[1]) if k not in PARTS)
    share = tracing.idle_share(ctx.trace)
    bounds = ("start_trace..stop_trace" if host.window else
              "[0, window_s) (no start_trace/stop_trace events)")
    ctx.notes = getattr(ctx, "notes", []) + [
        f"idle split ({prefix}*, window {bounds}): idle_share "
        f"{100.0 * share:.4f}% = inside programs {pct['device']:.4f}% + host "
        f"work {work:.4f}% ({spans}) + sync {pct['sync']:.4f}% + idle "
        f"{pct['idle']:.4f}% + untraced {pct['untraced']:.4f}%; parts sum to "
        f"{sum(pct.values()):.4f}%"]
    return work
