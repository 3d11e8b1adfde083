"""Device idle share of the long-generation window: 1 - (union of device
operation intervals) / traced window, on the worst chip."""

from harness.trace import idle_share

LAYER = "device"
MOVES = "output_tok_s"


def read(ctx):
    share = idle_share(ctx.trace)
    return None if share is None else 100.0 * share
