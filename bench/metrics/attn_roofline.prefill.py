"""Attention kernels' share of their roofline in the docqa window.

Roofline time of the attention work the window served (each prefill chunk
and each decoded token against its context, bench/harness/work.py), over
the summed device time of the Pallas kernel events. The program names no
kernel, so every Pallas (Mosaic) event counts as attention: in this dense
cell the only Pallas kernels are flashd_varlen and flashd_decode_paged."""

from harness.readers import serve_attn_roofline

LAYER = "kernels"
MOVES = "ttft_p95_ms"


def read(ctx):
    return serve_attn_roofline(ctx)
