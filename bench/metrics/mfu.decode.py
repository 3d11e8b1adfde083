"""Model FLOPs of the long-generation window's work (every fed token's
matmuls, each emitted token's LM head, causal attention) over the summed
device time of the window's programs times the chip's peak."""

from harness.readers import mfu

LAYER = "model step"
MOVES = "output_tok_s"


def read(ctx):
    return mfu(ctx)
