"""Model FLOPs per step (forward and backward, no recomputation) times the
window's steps, over the summed device time of the window's programs
times the chips used times the chip's peak."""

from harness.readers import mfu

LAYER = "model step"
MOVES = "train_tok_s"


def read(ctx):
    return mfu(ctx)
