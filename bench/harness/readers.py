"""Reductions the per-layer metric readers share: trace time of the
attention kernels and of the window's programs, against the work the
window did (`work.py`) and the chip's peaks. Each returns None where the
trace holds nothing to divide by, never 0."""

from __future__ import annotations

import re

import numpy as np

from harness import trace as tracing
from harness import work as W

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)(-start|-done)?$")
# ops whose event spans the ops of their body (a scanned layer loop)
CONTAINERS = ("while", "conditional", "call")

def is_pallas(name: str) -> bool:
    """A Pallas (Mosaic) kernel event. The program names its kernels
    (`flashd_varlen`, `flashd_fwd`, ...), but every Pallas event still
    counts as attention: in the dense cells all of them are."""
    return 'custom_call_target="tpu_custom_call"' in name

def _opcode(name: str) -> str:
    m = tracing._HLO.match(name)
    return m.group(2) if m else ""

def collective_share(ctx):
    """Percent of the traced window in which a collective op (by its HLO
    opcode: all-reduce, all-gather, reduce-scatter, all-to-all,
    collective-permute, or the -start or -done half of one) ran on a chip
    and no other op did, on the chip where that is largest; None where
    the trace holds no collective. A loop's or call's own event spans its
    body and is no other op here. Notes each chip's exposed and total
    collective time."""
    tr = ctx.trace
    if tr.window_s <= 0:
        return None
    per_chip = []
    for d in tr.devices:
        ops = [_opcode(n) for n in d.op_names]
        coll = np.array([bool(COLLECTIVE.match(o)) for o in ops], bool)
        if not coll.any():
            continue
        other = ~coll & np.array([o not in CONTAINERS for o in ops], bool)
        exposed = tracing.exclusive_ns(d.ops[coll], d.ops[other])
        per_chip.append((exposed, tracing.sum_ns(d.ops[coll]), d.name))
    if not per_chip:
        return None
    worst = max(per_chip)
    ctx.notes = getattr(ctx, "notes", []) + [
        "collectives (exposed / total op time): " + ", ".join(
            f"{name} {e * 1e-9:.4f}s / {t * 1e-9:.4f}s" for e, t, name in per_chip)
        + f"; worst {worst[2]}"]
    return 100.0 * worst[0] / (tr.window_s * 1e9)

def kernel_s(ctx) -> float:
    return sum(tracing.sum_ns(d.select(is_pallas)) for d in ctx.trace.devices) * 1e-9

def program_s(ctx) -> float:
    """Device time of the window's program executions, summed over chips."""
    return sum(tracing.union_ns(d.modules) for d in ctx.trace.devices) * 1e-9

def _share(roof_s: float, time_s: float):
    return None if time_s <= 0 else 100.0 * roof_s / time_s

def serve_attn_roofline(ctx):
    fl, by = ctx.work.attn_pieces()
    rf = W.roofline(fl, by, ctx.peaks)
    ctx.notes = getattr(ctx, "notes", []) + [
        f"serve attention roofline {rf.seconds:.4f}s ({rf.bound()}-bound: "
        f"{rf.compute_s:.4f}s compute, {rf.memory_s:.4f}s memory) over kernel "
        f"time {kernel_s(ctx):.4f}s"]
    return _share(rf.seconds, kernel_s(ctx))

def train_attn_roofline(ctx):
    fl, by = ctx.work.attn_piece()
    rf = W.roofline(fl * ctx.steps, by * ctx.steps, ctx.peaks)
    ctx.notes = getattr(ctx, "notes", []) + [
        f"train attention roofline {rf.seconds:.4f}s ({rf.bound()}-bound) "
        f"over kernel time {kernel_s(ctx):.4f}s"]
    return _share(rf.seconds, kernel_s(ctx))

def mfu(ctx):
    flops = (ctx.work.model_flops() * ctx.steps if ctx.kind == "train"
             else ctx.work.model_flops())
    t = program_s(ctx)
    return None if t <= 0 else 100.0 * flops / (t * ctx.peaks.flops)
