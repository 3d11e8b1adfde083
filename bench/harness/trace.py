"""Profiler trace of a window, reduced to device intervals.

`record(dir)` wraps the window in `jax.profiler` tracing; `load(path)`
reads the `.xplane.pb` it wrote with JAX alone. Each TPU device plane
(`/device:TPU:<i>`) gives its operations (line `XLA Ops`) and its program
executions (line `XLA Modules`) as [start, end) intervals in ns. Busy time
is the union of a device's operation intervals; a kernel's time is the
summed duration of its events; a collective's exposed time is the part of
its intervals that no other operation on that device covers.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil
import time
from typing import Callable, Dict, List, Optional

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# an XLA Ops event is named by its HLO text: "%<name>.<n> = <shape> <opcode>(..."
_HLO = re.compile(r"%([A-Za-z_][\w-]*?)(?:\.\d+)* = .*?\s([a-z][a-z0-9-]*)\(")


@dataclasses.dataclass
class Device:
    name: str
    op_names: List[str]
    ops: np.ndarray  # [n, 2] start, end ns
    module_names: List[str]
    modules: np.ndarray  # [m, 2]

    def select(self, pred: Callable[[str], bool]) -> np.ndarray:
        keep = [i for i, n in enumerate(self.op_names) if pred(n)]
        return self.ops[keep] if keep else np.zeros((0, 2))


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    window_s: float  # host-clock length of the traced window
    path: str = ""


def _intervals(events) -> tuple:
    names, iv = [], []
    for e in events:
        names.append(e.name)
        iv.append((e.start_ns, e.start_ns + e.duration_ns))
    return names, np.asarray(iv, np.float64).reshape(-1, 2)


def load(path: str, window_s: float) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            continue
        on, ops = _intervals(lines[OPS_LINE].events)
        mn, mods = (_intervals(lines[MODULES_LINE].events)
                    if MODULES_LINE in lines else ([], np.zeros((0, 2))))
        devices.append(Device(plane.name, on, ops, mn, mods))
    devices.sort(key=lambda d: d.name)
    return Trace(devices, window_s, path)


def union_ns(iv: np.ndarray) -> float:
    """Length of the union of [start, end) intervals."""
    if len(iv) == 0:
        return 0.0
    iv = iv[np.argsort(iv[:, 0])]
    total, cur_s, cur_e = 0.0, iv[0, 0], iv[0, 1]
    for s, e in iv[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return total + cur_e - cur_s


def exclusive_ns(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the part of union(a) that union(b) does not cover."""
    return union_ns(np.concatenate([a, b])) - union_ns(b)


def sum_ns(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def busy_s(dev: Device) -> float:
    return union_ns(dev.ops) * 1e-9


def idle_share(trace: Trace) -> Optional[float]:
    """Worst device's share of the window in which no operation ran."""
    if not trace.devices or trace.window_s <= 0:
        return None
    return max(1.0 - busy_s(d) / trace.window_s for d in trace.devices)


def short_name(name: str) -> str:
    """`<instruction name without its number> (<opcode>)` of an HLO event."""
    m = _HLO.match(name)
    return f"{m.group(1)} ({m.group(2)})" if m else name



def top_ops(trace: Trace, k: int = 10) -> List[list]:
    """The k operations, by short name, that took the most device time
    (averaged over devices)."""
    tot: Dict[str, float] = {}
    for d in trace.devices:
        dur = d.ops[:, 1] - d.ops[:, 0]
        for n, t in zip(d.op_names, dur):
            n = short_name(n)
            tot[n] = tot.get(n, 0.0) + t
    n_dev = max(len(trace.devices), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t * 1e-9 / n_dev] for n, t in best]


def idle_gaps(trace: Trace, spans, k: int = 10) -> List[list]:
    """The k longest idle gaps of the first device, each named by the host
    span (name, start_ns, end_ns on the trace's clock) that covers most of
    it, or "untraced host"."""
    if not trace.devices:
        return []
    iv = trace.devices[0].ops
    if len(iv) == 0:
        return []
    iv = iv[np.argsort(iv[:, 0])]
    ends = np.maximum.accumulate(iv[:, 1])
    gaps = [(iv[i + 1, 0] - ends[i], ends[i], iv[i + 1, 0])
            for i in range(len(iv) - 1) if iv[i + 1, 0] > ends[i]]
    gaps.sort(key=lambda g: -g[0])
    out = []
    for length, s, e in gaps[:k]:
        name, cover = "untraced host", 0.0
        for sn, ss, se in spans:
            c = min(e, se) - max(s, ss)
            if c > cover:
                name, cover = sn, c
        out.append([name, length * 1e-9])
    return out


def host_spans(path: str, prefix: str = "bench.") -> List[tuple]:
    """Host spans the benchmark wrote with `TraceAnnotation`."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(prefix):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


@contextlib.contextmanager
def record(out_dir: str):
    """Trace the enclosed block; yields a dict that gets `path` and
    `window_s` on exit."""
    import jax

    shutil.rmtree(out_dir, ignore_errors=True)
    info = {}
    jax.profiler.start_trace(out_dir)
    t0 = time.monotonic()
    try:
        yield info
    finally:
        info["window_s"] = time.monotonic() - t0
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"), recursive=True)
        info["path"] = found[0] if found else ""
