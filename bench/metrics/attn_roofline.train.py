"""Attention kernels' share of their roofline in the training window.

Causal attention forward and backward work per step (counted by the
configuration's architecture module, bench/arch/) times the steps of the
window, over the device time of the Pallas kernel events summed over the
chips. Every Pallas (Mosaic) event counts as attention: in a dense
training cell those are the kernels the program names flashd_fwd,
flashd_bwd_dq and flashd_bwd_dkv."""

from harness.readers import train_attn_roofline

LAYER = "kernels"
MOVES = "train_tok_s"


def read(ctx):
    return train_attn_roofline(ctx)
