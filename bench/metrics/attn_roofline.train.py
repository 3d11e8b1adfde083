"""Attention kernels' share of their roofline in the training window.

Causal attention forward and backward work per step (bench/harness/work.py)
times the steps of the window, over the summed device time of the Pallas
kernel events. The program names no kernel, so every Pallas (Mosaic) event
counts as attention: in a dense training cell those are flashd_fwd and
flashd_bwd."""

from harness.readers import train_attn_roofline

LAYER = "kernels"
MOVES = "train_tok_s"


def read(ctx):
    return train_attn_roofline(ctx)
