"""Required-work counts against hand-worked shapes, and the peak table."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import counts  # noqa: E402
import peaks  # noqa: E402


def test_kernel_counts_by_hand():
    # 2 heads over 1 kv head, width 4, 3 landmarks, 10 keys, bf16
    assert counts.landmark_summary(10, 2, 1, 4, 3) == (
        4 * 2 * 3 * 10 * 4, 2 * 1 * 10 * 4 * 2 + 2 * 2 * 3 * 4 * 4)
    assert counts.query_side(10, 2, 4, 3) == (
        4 * 2 * 10 * 3 * 4, 2 * 2 * 10 * 4 * 2 + 2 * 2 * 3 * 4 * 4)
    assert counts.paged_row_stats(7, 2, 1, 4) == (4 * 2 * 7 * 4,
                                                  2 * 1 * 7 * 4 * 2)
    f, b = counts.landmark_summary_bwd(10, 2, 1, 4, 3)
    assert f == 2 * counts.landmark_summary(10, 2, 1, 4, 3)[0]
    assert counts.core_flops(2, 1) == 2 * 8 * 7


def test_model_counts_by_hand():
    m = {"num_layers": 2, "d_model": 8, "num_heads": 2, "num_kv_heads": 1,
         "d_ff": 16, "vocab_size": 10, "num_landmarks": 2, "pinv_iters": 1}
    # q and o: 8*2*4 each; k and v: 8*1*4 each; SwiGLU 3*8*16
    assert counts.layer_matmul_params(m) == 64 * 2 + 32 * 2 + 384
    per_layer = 2 * 576 + 2 * (4 * 4 * 4 + 4 * 2 * 4 + 4 * 4 * 4 + 112)
    assert counts.decode_token_flops(m, 3, 16) == 2 * per_layer + 2 * 8 * 10
    n = 5
    attn = 2 * (12 * 2 * n * 4 + 4 * 4 * 4 + 112)
    assert counts.prefill_flops(m, n) == \
        2 * (2 * 576 * n + attn) + 2 * 8 * 10


def test_peak_table_and_roofline():
    chip = peaks.peak("TPU v5 lite")
    assert chip["flops_per_s"] == 197e12 and chip["bytes_per_s"] == 819e9
    # memory-bound: 819 MB in 2 ms is half the roofline
    assert peaks.roofline_share(1.0, 819e6, 2e-3, chip) == pytest.approx(50.0)
    assert peaks.roofline_share(197e9, 1.0, 1e-3, chip) == pytest.approx(100.0)
    assert peaks.roofline_share(1.0, 1.0, 0.0, chip) is None


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")
