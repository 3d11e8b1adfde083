"""Reductions the per-layer metric readers share: trace time of the
attention kernels and of the window's programs, against the work the
window did (`work.py`) and the chip's peaks. Each returns None where the
trace holds nothing to divide by, never 0."""

from __future__ import annotations

import numpy as np

from harness import trace as tracing
from harness import work as W

def is_pallas(name: str) -> bool:
    """A Pallas (Mosaic) kernel event. The program names no kernel yet,
    so every such event counts as attention in the dense cells."""
    return 'custom_call_target="tpu_custom_call"' in name

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
