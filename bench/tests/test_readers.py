"""Per-layer readers on hand-made records: each returns its metric, and
nothing where it has nothing to read."""

import pytest

from bench import flops, peaks
from bench.registry import Registry

PEAK = peaks.peaks("TPU v5 lite")
LM = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
      "head_dim": 16, "d_ff": 128, "vocab_size": 256}


def read(name, rec):
    return Registry().reader(name).read(rec)


def test_idle_share_and_nothing_without_a_trace():
    rec = {"trace": {"busy_s": 2.0, "window_s": 10.0}}
    assert read("device_idle_share.profile", rec) == pytest.approx(80.0)
    assert read("device_idle_share.train", {"trace": None}) is None


def test_profile_readers():
    hot = {"n_blocks": 1000, "n_tbins": 8}
    w = {"forwards": 4, "tokens": 4 * 4096, "window_s": 10.0,
         "analysis_s": 3.0, "plain_forward_s": 0.1, "hotness": hot,
         "buffers": [(65_536, 300, 0.01), (1_000, 100, 0.01)]}
    trace = {"busy_s": 2.0, "window_s": 10.0,
             "module_time": {"jit_object_histogram_pallas(1)": 1e-3,
                             "jit_hotness_histogram_pallas(2)": 1e-3},
             "module_count": {"jit_object_histogram_pallas(1)": 2,
                              "jit_hotness_histogram_pallas(2)": 2}}
    rec = {"window": {"record": w}, "trace": trace, "peak": PEAK,
           "config": {"program": LM}, "mix": {"seq": 1024}, "chips": 1}
    assert read("trace_analysis_share.profile", rec) == pytest.approx(30.0)
    assert read("overhead_x.profile", rec) == pytest.approx(25.0)
    least = sum(flops.least_time(*c, PEAK) for c in (
        flops.object_histogram_cost(65_536, 300),
        flops.hotness_cost(65_536, 1000, 8),
        flops.object_histogram_cost(1_000, 100),
        flops.hotness_cost(1_000, 1000, 8)))
    assert read("trace_kernel_roofline.profile", rec) == \
        pytest.approx(100 * least / 2e-3)
    # one of the two buffers fused: the one with fewer objects
    trace["module_time"] = {"jit_object_histogram_pallas(1)": 1e-3,
                            "jit_trace_aggregate_pallas(3)": 1e-3}
    trace["module_count"] = {"jit_object_histogram_pallas(1)": 1,
                             "jit_trace_aggregate_pallas(3)": 1}
    least = sum(flops.least_time(*c, PEAK) for c in (
        flops.object_histogram_cost(65_536, 300),
        flops.hotness_cost(65_536, 1000, 8),
        flops.trace_aggregate_cost(1_000, 100, 1000, 8)))
    assert read("trace_kernel_roofline.profile", rec) == \
        pytest.approx(100 * least / 2e-3)
    fwd = flops.dense_forward_flops_per_token(LM, 1024)
    assert read("mfu.profile", rec) == \
        pytest.approx(100 * fwd * 4 * 4096 / 10.0 / 197e12)


def test_serving_readers():
    ticks = [0.02, 0.03, 0.04]
    work = [([300, 400], 0, 0.02), ([301], 512, 0.03), ([], 0, 0.04)]
    w = {"ticks": ticks, "tick_work": work, "window_s": 1.0,
         "prefix_lookups": 4, "prefix_hits": 3}
    rec = {"window": {"record": w}, "peak": PEAK,
           "config": {"program": LM}, "chips": 1}
    assert read("tick_ms_p50.serve", rec) == pytest.approx(30.0)
    assert read("prefix_hit_rate.serve", rec) == pytest.approx(75.0)
    weights = 2 * flops.dense_matmul_params(LM)
    kv = flops.dense_kv_bytes_per_token(LM)
    want = (flops.least_time(flops.dense_decode_flops(LM, 300)
                             + flops.dense_decode_flops(LM, 400),
                             weights + kv * 700, PEAK)
            + flops.least_time(flops.dense_decode_flops(LM, 301)
                               + 512 * flops.dense_forward_flops_per_token(
                                   LM, 512), weights + kv * 301, PEAK)
            + flops.least_time(0.0, weights, PEAK))
    assert read("mfu.serve", rec) == pytest.approx(100 * want / 1.0)
    w["prefix_lookups"] = 0
    assert read("prefix_hit_rate.serve", rec) is None
