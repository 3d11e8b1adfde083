"""Pallas TPU kernels for the FLASH-D attention hot spot.

flashd_fwd    — blockwise FLASH-D prefill/training forward (tile-skip capable)
fa2_fwd       — FlashAttention2 baseline (the paper's comparison point)
flashd_decode — split-K decode with FLASH-D sigmoid merging of partials
flashd_varlen — packed varlen prefill+decode over the paged cache (§3.5)
ops           — dispatch REGISTRY (TPU: compiled kernels; CPU: interpret
                mode); entry points register under stable op names and are
                re-exported here
ref           — pure-jnp oracles
"""

from repro.kernels.ops import (
    get_op,
    op_names,
    pallas_attention_fwd_batched,
    pallas_decode,
    pallas_decode_paged,
    pallas_varlen,
    register_op,
)

__all__ = [
    "get_op",
    "op_names",
    "pallas_attention_fwd_batched",
    "pallas_decode",
    "pallas_decode_paged",
    "pallas_varlen",
    "register_op",
]
