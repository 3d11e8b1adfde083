"""Work functions against hand counts at a toy configuration, through
the dense GQA architecture module (`arch/dense_gqa.py`)."""

import numpy as np
import pytest

import benchtiny
from harness import cells
from harness import work as W
from harness.peaks import Peaks

D = cells.load_arch(benchtiny.BENCH_DIR, "dense_gqa")

M = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 2, "d_ff": 16, "vocab_size": 10}


def test_dense_and_head_flops():
    # per layer: q 8*8 + k,v 2*(8*4) + o 8*8 + swiglu 3*8*16 = 576 params
    assert D.matmul_params_per_layer(M) == 576
    assert D.flops_per_token(M) == 2 * 2 * 576
    assert D.head_flops(M) == 2 * 8 * 10


def test_causal_context_sum():
    # positions 3, 4, 5 see 4, 5, 6 keys
    assert D.causal_ctx_sum(3, 3) == 15
    assert D.attn_flops(M, 15) == 4 * 2 * 4 * 2 * 15


def test_serve_work_counts_fed_tokens_and_pieces():
    w = W.ServeWork.of(D, M, [np.zeros(5), np.zeros(3)], [np.zeros(3), np.zeros(0)], 4)
    # request 0: 5 prompt tokens + 2 fed outputs; request 1 served nothing
    assert w.fed_tokens() == 7
    ctx = D.causal_ctx_sum(0, 7)
    assert w.model_flops() == pytest.approx(
        7 * D.flops_per_token(M) + 3 * D.head_flops(M) + D.attn_flops(M, ctx))
    fl, by = w.attn_pieces()
    # prefill chunks [0, 4) and [4, 5), then decode at contexts 6 and 7
    assert len(fl) == 4
    assert fl.sum() == pytest.approx(D.attn_flops(M, ctx))
    kv_row, q_row = 2 * 2 * 2 * 2, 2 * 4 * 2 * 2
    assert by[0] == 2 * (4 * kv_row + 4 * q_row)
    assert by[2] == 2 * (6 * kv_row + q_row)


def test_train_work():
    w = W.TrainWork(D, M, batch=2, seq_len=4)
    fwd_attn = 2 * D.attn_flops(M, 10)
    assert w.mixer_fwd_flops() == fwd_attn
    assert w.model_flops() == pytest.approx(
        3 * (8 * (D.flops_per_token(M) + D.head_flops(M)) + fwd_attn))
    fl, by = w.attn_piece()
    assert fl[0] == 3 * fwd_attn
    assert by[0] == 8 * 2 * 2 * 2 * ((8 + 4) + (12 + 4) + (4 + 4))


def test_roofline_sums_the_binding_bound_per_piece():
    p = Peaks(flops=10.0, hbm_bw=2.0, hbm_bytes=1.0)
    r = W.roofline(np.array([100.0, 10.0]), np.array([4.0, 8.0]), p)
    assert r.compute_s == 10.0 and r.memory_s == 4.0
    assert r.seconds == 14.0 and r.bound() == "compute"
