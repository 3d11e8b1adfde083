"""Nearest-rank p95, over every request of the docqa window, of the wait
from its scheduled arrival to its first admission into a slot (the
engine's `stats()["admitted_at"]`, scheduler seconds, which a retry does
not move). A request never admitted waits until the window's end."""

import math

LAYER = "scheduler"
MOVES = "ttft_p95_ms"


def read(ctx):
    admitted = ctx.run.stats.get("admitted_at")
    if admitted is None or len(ctx.run.arrivals) == 0:
        return None
    waits = sorted(admitted.get(rid, ctx.run.window_s) - float(t)
                   for rid, t in enumerate(ctx.run.arrivals))
    return 1e3 * waits[max(0, math.ceil(0.95 * len(waits)) - 1)]
