"""Share of the rows that the docqa window's packed mixed steps dispatched
that held no token: 100 x (1 - real_rows / packed_rows), from the
engine's `stats()`. Rows pad each segment to the kernel's query block
and the pack to a power of two."""

LAYER = "serving loop"
MOVES = "ttft_p95_ms"


def read(ctx):
    packed = ctx.run.stats.get("packed_rows")
    if not packed:
        return None
    return 100.0 * (1.0 - ctx.run.stats["real_rows"] / packed)
