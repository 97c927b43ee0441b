"""PASTA's own spans as the benchmark reads them (``bench/spans.py``,
``bench/split.py`` and the readers of the program's split): the reduction
and every reader on a hand-made trace with exact values, nothing where the
trace holds no program span, the harness's own keys unchanged on the small
trace recorded on a v5e, and the split of a tiny profile window recorded on
the CPU."""

import glob
import io
import os

import pytest

import tiny
from bench import harness, spans, split, trace
from bench.registry import Registry

SMALL = os.path.join(os.path.dirname(__file__), "data",
                     "small_trace.xplane.pb")


def _raw():
    # one device, busy 10..30 and 50..60 of a 0..100 ns window; the
    # harness's forward holds 5..95
    ops = [("fusion.1", 10, 30), ("fusion.2", 50, 60)]
    return {"devices": {0: {"ops": ops, "modules": []}},
            "spans": [("bench.window", 0, 100), ("bench.forward", 5, 95)]}


EVENTS = [("pasta.op", 10, 40, {}),
          ("pasta.dispatch", 20, 40, {}),
          ("pasta.process.trace", 20, 39, {}),
          ("pasta.kernel.pad", 20, 25, {"records": 3, "padded": 8}),
          ("pasta.kernel.pad", 26, 28, {"records": 5, "padded": 8}),
          ("pasta.kernel.fetch", 30, 38, {}),
          ("pasta.free", 90, 110, {}),          # clipped to the window
          ("pasta.op", 150, 160, {})]           # after it


def _rec():
    return {"trace": spans.reduce(_raw(), EVENTS, n_devices=1)}


def test_reduce_by_hand():
    r = _rec()["trace"]
    assert r["window_ns"] == 100
    hs = r["host_spans"]
    assert hs["pasta.op"] == {"intervals": [[10, 40]], "count": 1,
                              "stats": {}}
    assert hs["pasta.kernel.pad"] == {"intervals": [[20, 25], [26, 28]],
                                      "count": 2,
                                      "stats": {"records": 8, "padded": 16}}
    assert hs["pasta.free"]["intervals"] == [[90, 100]]
    assert hs["bench.forward"]["intervals"] == [[5, 95]]
    assert r["idle_intervals"] == [[[0, 10], [30, 50], [60, 100]]]


@pytest.mark.parametrize("name,want", [
    ("instrument_share.profile", 20.0),       # 10..20, 90..100
    ("processor_host_share.profile", 12.0),   # 20..30, 38..40
    ("kernel_wait_share.profile", 8.0),       # 30..38
    ("model_host_share.profile", 55.0),       # 5..10, 40..90
    ("round_trips_per_buffer.profile", 1.0),
    ("trace_pad_share.profile", 50.0),        # 8 records of 16
    ("idle_in_pasta_share.profile", 100 * 20 / 70),   # 30..40, 90..100
])
def test_reader_by_hand(name, want):
    assert Registry().reader(name).read(_rec()) == pytest.approx(want)


def test_the_four_shares_add_up_to_the_covered_window():
    out = split.split(_rec()["trace"])
    assert out["split_sum"] == pytest.approx(95.0)
    assert out["covered"] == pytest.approx(95.0)       # 5..100
    assert out["process_trace"] == pytest.approx(19.0)
    assert out["spans"]["pasta.kernel.pad"] == {
        "share": pytest.approx(7.0), "count": 2}
    assert set(out["spans"]) == {e[0] for e in EVENTS}


@pytest.mark.parametrize("name", split.READERS)
def test_reader_reads_nothing_without_program_spans(name):
    reader = Registry().reader(name)
    bench_only = spans.reduce(_raw(), [], n_devices=1)
    assert bench_only["host_spans"]
    for rec in ({"trace": bench_only}, {"trace": None},
                {"trace": {"window_s": 1.0, "busy_s": 0.5}}):
        assert reader.read(rec) is None


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_small_trace_keeps_every_key_of_the_harness():
    want = trace.reduce_file(SMALL, n_devices=1)
    got = split.reduce_file(SMALL, n_devices=1)
    assert {k: got[k] for k in want} == want
    assert set(got) - set(want) == {"host_spans", "idle_intervals",
                                    "window_ns"}
    assert spans.read(SMALL) == []
    out = split.split(got)
    assert out.pop("spans") == {}
    assert all(v is None for v in out.values())


@pytest.fixture(scope="module")
def tiny_window(tmp_path_factory):
    """A tiny profile cell's window on the CPU under the profiler: the
    driver's record and the reduction with an idle device beside it (the
    CPU has no device plane)."""
    import jax
    reg = Registry(tiny.tiny_root(str(tmp_path_factory.mktemp("bench"))))
    cell = reg.cell("tiny-lm.profile")
    config = reg.config(cell["config"])
    mix = reg.traffic(cell["traffic"])
    ctx = harness.Context(cell=cell, config=config, mix=mix,
                          limits=reg.limits(cell["name"]), seed=5,
                          reference=reg.reference(config["reference"]),
                          devices=jax.devices()[:1], peak=None,
                          log=io.StringIO())
    driver = reg.driver(mix["kind"])
    state = driver.setup(ctx)
    tmp = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(tmp)
    try:
        win = driver.window(ctx, state, 0.3)
    finally:
        jax.profiler.stop_trace()
    state["session"].close()
    path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                      recursive=True)
    raw = trace.read(path)
    raw["devices"] = {0: {"ops": [], "modules": []}}
    reduced = spans.reduce(raw, spans.read(path), n_devices=1)
    return win["record"], reduced, reg


def test_split_of_a_profile_window_on_the_cpu(tiny_window):
    record, reduced, reg = tiny_window
    out = split.split(reduced, reg)
    assert all(out[n] is not None for n in split.READERS), out
    # the spans nest as designed: the four shares cover what the forwards
    # and the program's spans cover, no more and no less
    assert out["split_sum"] == pytest.approx(out["covered"], abs=1e-6)
    assert out["covered"] > 90.0
    assert out["round_trips_per_buffer.profile"] == 1.0   # the CPU fuses
    assert out["trace_pad_share.profile"] == 0.0          # and pads none
    # the device idles all through: its idle time in the program's spans
    # is the program's share of the window
    assert out["idle_in_pasta_share.profile"] == pytest.approx(
        spans.share({"trace": reduced}, ("pasta.*",)))


def test_program_counts_match_the_drivers_record(tiny_window):
    record, reduced, _ = tiny_window
    hs = reduced["host_spans"]
    assert hs["pasta.process.trace"]["count"] == len(record["buffers"])
    assert hs["pasta.kernel.pad"]["stats"]["records"] == \
        sum(b[0] for b in record["buffers"])
    # analysis_s (host clock) times the kernel calls inside each
    # pasta.process.trace span (trace clock)
    in_process = trace.total(hs["pasta.process.trace"]["intervals"])
    assert in_process >= 0.99 * record["analysis_s"] * 1e9


def test_split_main_reduces_what_the_harness_reduces(monkeypatch, capsys):
    """``split.main`` hands the harness its own reduction: whatever
    ``bench.trace.reduce_file`` the run calls returns the spans' keys too,
    and the split line follows the run's result."""
    import json
    monkeypatch.setattr(trace, "reduce_file", trace.reduce_file)

    def run_main(argv):
        assert argv[-2:] == ["--trace", "1"]
        assert "host_spans" in trace.reduce_file(SMALL, n_devices=1)
        return 0
    monkeypatch.setattr(split.run, "main", run_main)
    assert split.main(["--workload", "w", "--seed", "1",
                       "--seconds", "1"]) == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert set(json.loads(line)["split"]) == \
        {*split.READERS, "split_sum", "covered", "process_trace", "spans"}
