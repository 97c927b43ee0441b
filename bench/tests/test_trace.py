"""The trace reduction: interval arithmetic and labelling on a hand-made
trace, and the whole reduction on the small trace recorded on a v5e
(``record_small_trace.py``)."""

import os

import pytest

from bench import trace

SMALL = os.path.join(os.path.dirname(__file__), "data",
                     "small_trace.xplane.pb")


def test_union_and_subtract():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert trace.subtract([[0, 10]], [[2, 3], [5, 7]]) == \
        [[0, 2], [3, 5], [7, 10]]
    assert trace.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]
    assert trace.clip([[0, 5], [8, 12]], 2, 10) == [[2, 5], [8, 10]]


def _raw():
    # one device; window 0..100 ns; ops busy 10..30 and 50..60; an
    # all-reduce 25..45 of which 30..45 runs alone; host spans label gaps
    ops = [("fusion.1", 10, 30), ("all-reduce.2", 25, 45),
           ("fusion.3", 50, 60)]
    return {"devices": {0: {"ops": ops, "modules": [("jit_step(1)", 10, 60)]}},
            "spans": [("bench.window", 0, 100), ("bench.feed", 0, 10),
                      ("bench.wait", 60, 100)]}


def test_reduce_by_hand():
    r = trace.reduce(_raw(), n_devices=1)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(45e-9)          # 10..45 and 50..60
    assert r["collective_s"] == pytest.approx(20e-9)
    assert r["collective_exposed_s"] == pytest.approx(15e-9)
    assert r["module_count"] == {"jit_step(1)": 1}
    gaps = r["idle_gaps"]
    assert gaps[0] == ["bench.wait", pytest.approx(40e-9)]
    assert gaps[1] == ["bench.feed", pytest.approx(10e-9)]
    assert [g[0] for g in gaps] == ["bench.wait", "bench.feed",
                                    "outside bench spans"]


def test_no_device_operations_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {0: {"ops": [], "modules": []}},
                      "spans": []}, n_devices=1)


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_small_trace_from_the_chip():
    raw = trace.read(SMALL)
    spans = {n: (s, e) for n, s, e in raw["spans"]}
    assert {"bench.matmul", "bench.kernels", "bench.host_gap"} <= set(spans)
    # the recording opened no window span: give it one from the first
    # device op (the device clock runs about 1 ms ahead of the host's in
    # this trace) to the end of the host gap
    first = min(s for d in raw["devices"].values() for _, s, _ in d["ops"])
    raw["spans"].append(("bench.window", first, spans["bench.host_gap"][1]))
    r = trace.reduce(raw, n_devices=1)
    assert 0 < r["busy_s"] < r["window_s"]
    # three jitted matmuls and one call of each Pallas kernel
    count = {k.split("(")[0]: v for k, v in r["module_count"].items()}
    assert count["jit__lambda"] == 3
    assert count["jit_object_histogram_pallas"] == 1
    assert count["jit_hotness_histogram_pallas"] == 1
    ops = dict(r["top_ops"])
    assert "fusion bf16[2048,2048]" in ops
    assert "custom-call object_histogram_pallas f32[1,512]" in ops
    # the 50 ms host sleep is the longest idle gap, and is labelled by it
    label, secs = r["idle_gaps"][0]
    assert label == "bench.host_gap" and secs >= 0.045
