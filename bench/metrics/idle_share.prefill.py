"""Device idle share of the docqa window: 1 - (union of device operation
intervals) / traced window, on the worst chip."""

from harness.trace import idle_share

LAYER = "device"
MOVES = "ttft_p95_ms"


def read(ctx):
    share = idle_share(ctx.trace)
    return None if share is None else 100.0 * share
