"""The operation and byte counts of the dense reference model, against
counts made by hand."""
import pytest

from harness.arith import roofline_share
from harness.chip import PEAKS, NoChip, peaks
from reference.dense_gqa import Model, Spec

# 2 layers, d 8, 4 heads of 2 (kv 2), ffn 16, vocab 10
A = Model(Spec(layers=2, d_model=8, heads=4, kv_heads=2, head_dim=2, d_ff=16,
               vocab=10, rope_theta=1e4, eps=1e-6, qk_norm=False))


def test_layer_params_by_hand():
    # q 8x8, k 8x4, v 8x4, o 8x8, three 8x16 SwiGLU matrices
    assert A.layer_matmul_params == 64 + 32 + 32 + 64 + 3 * 128
    assert A.head_params == 80


def test_decode_token_flops_by_hand():
    # 2 per multiply-add over (2 layers x 576 + head 80) weights, and
    # q.k plus p.v: 2 x 2 x heads x head_dim per key per layer
    assert A.decode_token_flops(5) == 2 * (2 * 576 + 80) + 2 * 4 * 4 * 2 * 5


def test_train_step_flops_by_hand():
    B, S = 3, 4
    body = S * 2 * 2 * 576
    head = (S - 1) * 2 * 80
    attn = 4 * 2 * 4 * 2 * (1 + 2 + 3 + 4)       # causal: 1..S keys
    assert A.train_step_flops(B, S) == 3 * B * (body + head + attn)


def test_decode_attention_work_by_hand():
    flops, nbytes = A.decode_attention_work(7)
    assert flops == 2 * 2 * 4 * 2 * 7
    # K and V rows (7 x 2 heads x 2 dims, bf16) plus q and o (4 x 2, bf16)
    assert nbytes == 2 * 7 * 2 * 2 * 2 + 2 * 4 * 2 * 2


def test_flash_attention_work_by_hand():
    flops, nbytes = A.flash_attention_work(2, 3)
    assert flops == 4 * 2 * 4 * 2 * (1 + 2 + 3)
    assert nbytes == 2 * (2 * 3 * 4 * 2 * 2) + 2 * (2 * 3 * 2 * 2 * 2)


def test_roofline_share_takes_the_larger_bound():
    p = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline_share(200, 10, 4.0, p) == (50.0, "compute")
    assert roofline_share(10, 200, 40.0, p) == (50.0, "memory")


def test_peaks_are_published_v5e_numbers_and_unknown_kinds_fail():
    assert PEAKS["TPU v5 lite"]["bf16_flops"] == 197e12
    assert PEAKS["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    with pytest.raises(NoChip):
        peaks("TPU v9 imaginary")
