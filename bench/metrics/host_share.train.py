"""Share of the training window in which the device sat idle between
program executions while the train loop's host did its own work: under a
`repro.train.*` span other than `sync` (batch, dispatch, commit, ckpt, or
a step's own code). The note splits the whole idle share
(harness/spans.py)."""

from harness.spans import host_share

LAYER = "train loop"
MOVES = "train_tok_s"


def read(ctx):
    return host_share(ctx, "repro.train.")
