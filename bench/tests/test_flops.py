"""Roofline and MFU arithmetic against hand-worked shapes."""

import pytest

from bench import flops, peaks

GPT2 = {"n_layers": 12, "d_model": 768, "n_heads": 12, "n_kv_heads": 12,
        "head_dim": 64, "d_ff": 3072, "vocab_size": 50257}


def test_forward_flops_of_gpt2_by_hand():
    per_layer = 4 * 768 * 768 + 3 * 768 * 3072          # q, k, v, o; GLU
    matmul = 12 * per_layer + 768 * 50257               # + LM head
    attn = 2 * 2 * 768 * (1024 + 1) / 2 * 12            # causal QK^T, AV
    want = 2 * matmul + attn
    assert flops.dense_forward_flops_per_token(GPT2, 1024) == want
    assert flops.dense_train_flops_per_token(GPT2, 1024) == 3 * want
    assert want == pytest.approx(3.226e8, rel=1e-3)


def test_mfu_by_hand():
    from bench.metrics._common import mfu
    peak = peaks.peaks("TPU v5 lite")
    rec = {"window": {"record": {"tokens": 70_000 * 10, "window_s": 10.0}},
           "peak": peak, "chips": 1}
    got = mfu(rec, 9.678e8)
    assert got == pytest.approx(100 * 9.678e8 * 70_000 / 197e12)
    rec["chips"] = 4
    assert mfu(rec, 9.678e8) == pytest.approx(got / 4)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_trace_kernel_costs_follow_the_padding():
    # 65,536 records, 300 objects: records pad to 2048 x 32, objects to 512
    ops, nbytes = flops.object_histogram_cost(65_536, 300)
    assert ops == 65_536 and nbytes == (65_536 + 3 * 512) * 4
    # 40,000 records pad to 1024 x 64 = 65,536; 1,500 blocks to 1,536;
    # 8 time bins stay 8
    ops, nbytes = flops.hotness_cost(40_000, 1_500, 8)
    assert ops == 65_536
    assert nbytes == (2 * 65_536 + 8 * 1_536) * 4
    assert flops.padded(2049, 2048) == 4096
    assert flops.padded(1, 128) == 128


def test_least_time_takes_the_binding_bound():
    peak = peaks.peaks("TPU v5 lite")
    # memory bound: 819 MB at 819 GB/s is 1 ms; 1 GFLOP is 5 us
    assert flops.least_time(1e9, 819e6, peak) == pytest.approx(1e-3)
    # compute bound: 197 TFLOP takes 1 s
    assert flops.least_time(197e12, 1.0, peak) == pytest.approx(1.0)
