"""Attention kernels' share of their roofline in the long-generation window.

Roofline time of the attention work the window served (each prefill chunk
and each decoded token against its context, counted by the configuration's
architecture module, bench/arch/), over the summed device time of the
Pallas kernel events. Every Pallas (Mosaic) event counts as attention:
in this dense cell the only Pallas kernels are those the program names
flashd_varlen and flashd_decode_paged."""

from harness.readers import serve_attn_roofline

LAYER = "kernels"
MOVES = "output_tok_s"


def read(ctx):
    return serve_attn_roofline(ctx)
