"""Share of the docqa window in which the device sat idle between program
executions while the serving loop's host did its own work: under a
`repro.serve.*` span other than `sync` and `idle` (plan, pack, dispatch,
commit, admit, or a step's own code). The note splits the whole idle
share into that, idle inside programs, sync, waiting for arrivals and
untraced host time (harness/spans.py)."""

from harness.spans import host_share

LAYER = "serving loop"
MOVES = "ttft_p95_ms"


def read(ctx):
    return host_share(ctx, "repro.serve.")
