"""Share of the longgen window in which the device sat idle between
program executions while the serving loop's host did its own work: under
a `repro.serve.*` span other than `sync` and `idle`. The note splits the
whole idle share (harness/spans.py)."""

from harness.spans import host_share

LAYER = "serving loop"
MOVES = "output_tok_s"


def read(ctx):
    return host_share(ctx, "repro.serve.")
