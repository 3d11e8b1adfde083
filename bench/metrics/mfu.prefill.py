"""Model FLOPs of the docqa window's work (every fed token's matmuls, each
emitted token's LM head, causal attention) over the summed device time of
the window's programs times the chip's peak: a share of step time, not of
window time, since an open-loop window's length is set by its rate."""

from harness.readers import mfu

LAYER = "model step"
MOVES = "ttft_p95_ms"


def read(ctx):
    return mfu(ctx)
