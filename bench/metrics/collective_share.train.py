"""Exposed collective time of the training window: on the worst chip, the
time in which a collective op (all-reduce, all-gather, reduce-scatter,
all-to-all, collective-permute, or a -start/-done half of one) runs and
no other op does, as a share of the traced window. The note gives each
chip's exposed and total collective time, so that hidden and exposed
communication can be told apart."""

from harness.readers import collective_share

LAYER = "collectives"
MOVES = "train_tok_s"


def read(ctx):
    return collective_share(ctx)
