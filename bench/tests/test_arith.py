"""FLOP and byte counts against values worked out by hand."""
import pytest

from bench.harness import arith

PEAK = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
M = {"n_layers": 1, "d_model": 4, "n_heads": 2, "n_kv_heads": 1,
     "d_head": 2, "d_ff": 8, "vocab": 16, "act": "gelu"}


def test_one_gemm():
    assert arith.gemm_flops(3, 5, 7) == 210
    # compute bound: 210 / 100 = 2.1 s > 20 / 10
    assert arith.roofline_s(210, 20, PEAK) == pytest.approx(2.1)
    # memory bound
    assert arith.roofline_s(210, 300, PEAK) == pytest.approx(30.0)


def test_one_causal_attention():
    # 3 positions: 6 query-key pairs per head; 4 * d FLOPs per pair forward
    assert arith.causal_pairs(3) == 6
    assert arith.attn_fwd_flops(1, 3, M) == 4 * 2 * 6 * 2


def test_model_counts():
    # wq 4x4, wk 4x2, wv 4x2, wo 4x4, up 4x8, down 8x4, head 4x16
    assert arith.matmul_params(M) == 16 + 8 + 8 + 16 + 32 + 32 + 64
    per_tok = 2 * 176
    assert arith.decode_token_flops(M, 3) == per_tok + 4 * 2 * 3 * 2
    assert arith.prefill_flops(M, 3) == 3 * per_tok + 4 * 2 * 6 * 2


def test_decode_weights_are_credited_packed():
    one = {"n_layers": 0, "d_model": 64, "vocab": 32, "n_heads": 1,
           "n_kv_heads": 1, "d_head": 1, "d_ff": 1}
    peak = {"bf16_flops": 1e30, "hbm_bytes_per_s": 1.0}
    # only the head: 64 x 32 weights at 1 + 1/32 bytes, 1 row in and out
    assert arith.decode_gemm_ideal_s(one, 1, peak) == pytest.approx(
        64 * 32 * (1 + 1 / 32) + (64 + 32) * 2)

