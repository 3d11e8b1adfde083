"""Device idle share of the training window: 1 - (union of device
operation intervals) / traced window, on the worst chip."""

from harness.trace import idle_share

LAYER = "device"
MOVES = "train_tok_s"


def read(ctx):
    share = idle_share(ctx.trace)
    return None if share is None else 100.0 * share
