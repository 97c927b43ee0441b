"""Columnar event backbone: batch-vs-scalar golden equivalence.

Every ported tool must produce an *identical* ``finalize()`` report whether
the same logical event stream arrives via scalar ``emit``, via the buffered
SoA ring (at several flush boundaries, including capacity-1 and mid-stream
flushes), or as producer-built columnar batches — plus fused-kernel parity
against the separate kernels in interpret mode.
"""

import os

import numpy as np
import pytest

import repro.core as pasta
from repro.core.events import (Event, EventBatch, EventKind, EventRing,
                               reset_seq)
from repro.core.pool import CHUNK_ALIGN


HOT_CFG = {"base": CHUNK_ALIGN, "n_blocks": 64, "n_tbins": 4,
           "t_max": 1.0, "block_shift": 5}

KERNELS = [("fusion.1", 3, "train"), ("fusion.1", 2, "train"),
           ("dot.7", 5, ""), ("fusion.2", 1, "train"), ("copy", 4, ""),
           ("dot.7", 1, "eval"), ("fusion", 2, "")]


def _golden_tools():
    return [pasta.KernelFrequencyTool(), pasta.MemoryTimelineTool(),
            pasta.WorkingSetTool(), pasta.HotnessTool(n_tbins=4, n_blocks=64),
            pasta.RooflineTool()]


def _emit_kernels_scalar(handler):
    for name, count, label in KERNELS * 3:
        attrs = {"count": count, "bytes": 1 << 20}
        if label:
            attrs["label"] = label
        handler.emit(Event(EventKind.KERNEL_LAUNCH, name=name, attrs=attrs))


def _emit_kernels_batched(handler):
    rows = KERNELS * 3
    attrs = []
    for name, count, label in rows:
        a = {"count": count, "bytes": 1 << 20}
        if label:
            a["label"] = label
        attrs.append(a)
    handler.emit_batch(EventBatch.of(
        EventKind.KERNEL_LAUNCH, names=[r[0] for r in rows], attrs=attrs))


def _run_workload(emit_kernels, buffered_capacity=None):
    """One full coarse+fine workload; returns the tools' reports."""
    reset_seq()
    handler = pasta.EventHandler(
        buffer_capacity=buffered_capacity or 4096,
        buffered=buffered_capacity is not None)
    with pasta.EventProcessor(handler, tools=_golden_tools(),
                              hotness=HOT_CFG) as proc:
        handler.step_start(0)
        emit_kernels(handler)
        pool = pasta.MemoryPool(handler, chunk_size=1 << 20)
        ts = [pool.alloc((i + 1) << 12, f"t{i}") for i in range(6)]
        handler.operator_start(
            "op0", tensors=[(t.addr, t.size) for t in ts[:3]])
        handler.emit(Event(EventKind.COLLECTIVE, name="all-reduce.1",
                           size=1 << 16, attrs={"mult": 2}))
        handler.memcpy(4096, "h2d")
        objs = sorted(t.addr_range() for t in pool.live_tensors())
        rng = np.random.default_rng(7)
        starts = np.asarray([s for s, _ in objs])
        sizes = np.asarray([e - s for s, e in objs])
        pick = rng.integers(0, len(objs), size=400)
        addrs = starts[pick] + rng.integers(0, sizes[pick])
        handler.trace_buffer(addrs, name="k0", kernel="k0", objects=objs,
                             object_sizes=sizes.tolist(), time=0.3)
        for t in ts[::2]:
            pool.free(t)
        if buffered_capacity is not None and buffered_capacity > 16:
            handler.flush()          # mid-stream explicit flush boundary
        for t in ts[1::2]:
            pool.free(t)
        handler.step_end(0)
        return proc.finalize()


def test_batched_emit_matches_scalar():
    want = _run_workload(_emit_kernels_scalar)
    got = _run_workload(_emit_kernels_batched)
    assert got == want


@pytest.mark.parametrize("capacity", [1, 3, 7, 64, 4096])
def test_buffered_ring_matches_scalar(capacity):
    """Ring flushes at capacity / step boundaries / explicit flush must not
    change any report, for pathological and comfortable capacities alike."""
    want = _run_workload(_emit_kernels_scalar)
    got = _run_workload(_emit_kernels_scalar, buffered_capacity=capacity)
    assert got == want


def test_batched_and_buffered_match():
    got = _run_workload(_emit_kernels_batched, buffered_capacity=5)
    want = _run_workload(_emit_kernels_scalar)
    assert got == want


def test_template_fallback_subclass_sees_batches():
    """A legacy-style subclass overriding only on_<kind> hooks must behave
    identically under scalar and batched emission (loop-over-rows default)."""

    class CountingTool(pasta.PastaTool):
        EVENTS = (EventKind.KERNEL_LAUNCH,)

        def __init__(self):
            super().__init__()
            self.total = 0
            self.names = []

        def on_kernel_launch(self, ev):
            self.total += int(ev.attrs.get("count", 1))
            self.names.append(ev.name)

        def finalize(self):
            return {"total": self.total, "names": self.names}

    reps = []
    for emit in (_emit_kernels_scalar, _emit_kernels_batched):
        handler = pasta.EventHandler()
        with pasta.EventProcessor(handler, tools=[CountingTool()]) as proc:
            emit(handler)
            reps.append(proc.finalize()["CountingTool"])
    assert reps[0] == reps[1]
    assert reps[0]["total"] == sum(c for _n, c, _l in KERNELS) * 3


def test_normalize_batch_masked_negation():
    from repro.core.events import KIND_CODE
    codes = np.asarray([KIND_CODE[EventKind.TENSOR_FREE],
                        KIND_CODE[EventKind.ALLOC],
                        KIND_CODE[EventKind.TENSOR_FREE]], dtype=np.int16)
    b = EventBatch.of(codes, sizes=[-512, -128, 1024])
    pasta.EventProcessor.normalize_batch(b)
    assert b.sizes.tolist() == [512, -128, 1024]   # ALLOC keeps raw sign
    assert b.normalized


def test_scalar_subscribers_see_normalized_rows(handler):
    seen = []
    pasta.EventProcessor(handler)
    handler.subscribe(lambda e: seen.append(e),
                      kinds=(EventKind.TENSOR_FREE,))
    pool = pasta.MemoryPool(handler)
    t = pool.alloc(4096)
    with handler.buffering():
        pool.free(t)
    assert seen and seen[0].normalized and seen[0].size == t.size > 0


def test_processor_close_stops_double_dispatch(handler):
    t1 = pasta.KernelFrequencyTool()
    t2 = pasta.KernelFrequencyTool()
    p1 = pasta.EventProcessor(handler, tools=[t1])
    handler.emit(Event(EventKind.KERNEL_LAUNCH, name="a", attrs={"count": 1}))
    p1.close()
    p2 = pasta.EventProcessor(handler, tools=[t2])
    handler.emit(Event(EventKind.KERNEL_LAUNCH, name="a", attrs={"count": 1}))
    assert t1.counts["a"] == 1        # p1 detached before the second event
    assert t2.counts["a"] == 1
    p2.close()


def test_unsubscribe_targeted(handler):
    a, b = [], []
    fa, fb = a.append, b.append
    handler.subscribe(fa, kinds=(EventKind.SYNC,))
    handler.subscribe(fb, kinds=(EventKind.SYNC,))
    handler.sync()
    handler.unsubscribe(fa)
    handler.sync()
    assert len(a) == 1 and len(b) == 2


def test_trace_buffer_bypasses_ring(handler):
    """Heavy TRACE_BUFFER rows must dispatch (and be reduced to aggregates)
    immediately even under buffering — the ring must never pin raw
    access-record arrays until the next flush boundary."""
    proc = pasta.EventProcessor(handler)
    seen = []
    handler.subscribe(lambda e: seen.append(e), kinds=("trace_buffer",))
    with handler.buffering():
        handler.sync("before")                 # stays in the ring...
        handler.trace_buffer(np.arange(64), name="k")
        assert seen, "trace row was buffered instead of dispatched"
        assert "records" not in seen[0].attrs  # ...but the trace reduced
    proc.close()


def test_pool_handles_stamped_before_dispatch(handler):
    """Subscribers running during TENSOR_FREE dispatch must observe the
    freed handle as dead (free_seq stamped before emit)."""
    pool = pasta.MemoryPool(handler)
    live_during_dispatch = []
    handler.subscribe(
        lambda e: live_during_dispatch.append(
            pool.tensors[e.attrs["tensor_id"]].live),
        kinds=(EventKind.TENSOR_FREE,))
    t = pool.alloc(4096)
    assert t.alloc_seq > 0
    pool.free(t)
    assert live_during_dispatch == [False]


def test_ring_capacity_flush():
    ring = EventRing(capacity=2)
    from repro.core.events import KIND_CODE
    code = KIND_CODE[EventKind.SYNC]
    assert not ring.append(code, "s", 0, 0.0, 0, 0, 1, None, (), ())
    assert ring.append(code, "s2", 0, 0.0, 0, 0, 2, None, (), ())
    batch = ring.flush()
    assert len(batch) == 2 and len(ring) == 0
    assert batch.name_of(0) == "s" and batch.name_of(1) == "s2"
    assert ring.flush() is None


# ------------------------------------------------------- fused kernel parity
def _mk_trace(rng, k=17, n=5000):
    sizes = rng.integers(512, 4 << 20, size=k) // 512 * 512
    starts = np.zeros(k, dtype=np.int64)
    addr = 2 << 20
    for i in range(k):
        starts[i] = addr
        addr += sizes[i] + (2 << 20)
    ends = starts + sizes
    hits = rng.integers(0, k, size=n)
    addrs = starts[hits] + rng.integers(0, sizes[hits])
    addrs[::11] = ends[-1] + 12345           # out-of-object misses
    times = rng.random(n)
    return addrs, times, starts, ends


@pytest.mark.parametrize("n,nb,tb", [(100, 64, 4), (5000, 256, 8),
                                     (20000, 512, 16)])
def test_fused_matches_separate_kernels_interpret(rng, n, nb, tb):
    os.environ["REPRO_PALLAS_INTERPRET"] = "1"
    try:
        from repro.kernels import ops
        addrs, times, starts, ends = _mk_trace(rng, n=n)
        base = 2 << 20
        c_sep = ops.object_histogram(addrs, starts, ends)
        h_sep = ops.hotness_histogram(addrs, times, base, nb, tb, 1.0)
        c_fused, h_fused = ops.trace_aggregate(addrs, times, starts, ends,
                                               base, nb, tb, 1.0)
        np.testing.assert_array_equal(c_fused, c_sep)
        np.testing.assert_array_equal(h_fused, h_sep)
    finally:
        os.environ["REPRO_PALLAS_INTERPRET"] = "0"


def test_fused_ref_backend_matches_separate(rng):
    from repro.kernels import ops
    addrs, times, starts, ends = _mk_trace(rng)
    base = 2 << 20
    c_sep = ops.object_histogram(addrs, starts, ends)
    h_sep = ops.hotness_histogram(addrs, times, base, 128, 8, 1.0)
    c_f, h_f = ops.trace_aggregate(addrs, times, starts, ends, base,
                                   128, 8, 1.0)
    np.testing.assert_array_equal(c_f, c_sep)
    np.testing.assert_array_equal(h_f, h_sep)


def test_fused_fallback_beyond_vmem_ceilings(handler, rng):
    """Problems larger than the fused kernel's resident-accumulator limits
    (object table > FUSE_MAX_K, hist cells > FUSE_MAX_HIST) must route to
    the tiled two-pass kernels instead of tripping the kernel asserts."""
    os.environ["REPRO_PALLAS_INTERPRET"] = "1"   # pallas limits apply
    try:
        from repro.kernels import ops
        assert not ops.can_fuse(5000, 1024, 64)
        assert not ops.can_fuse(100, 32768, 64)
        assert ops.can_fuse(100, 1024, 64)
        hp = {"base": 2 << 20, "n_blocks": 32768, "n_tbins": 64,
              "t_max": 1.0}
        proc = pasta.EventProcessor(handler, hotness=hp)
        seen = []
        handler.subscribe(lambda e: seen.append(e), kinds=("trace_buffer",))
        starts = np.array([2 << 20, 64 << 20])
        ends = starts + (1 << 20)
        addrs = np.concatenate([rng.integers(starts[0], ends[0], 300),
                                rng.integers(starts[1], ends[1], 100)])
        handler.trace_buffer(addrs, name="k",
                             objects=list(zip(starts, ends)),
                             object_sizes=[1 << 20, 1 << 20], time=0.5)
        proc.close()
        ev = seen[0]
        assert ev.attrs["object_counts"].tolist() == [300, 100]
        assert int(ev.attrs["hotness_map"].sum()) == 400
    finally:
        os.environ["REPRO_PALLAS_INTERPRET"] = "0"


#: the fine-grained profile's hotness shape, which the fused kernel's VMEM
#: budget refuses at any object count: 1,048 blocks in 8 time bins
WIDE = dict(k=128, n_blocks=1048, n_tbins=8)


def _numpy_aggregates(addrs, times, starts, ends, base, n_blocks, n_tbins):
    """Plain numpy reduction: per-object counts over the half-open ranges
    and the [time bin, 2 MiB block] map (time axis over [0, 1))."""
    a = np.asarray(addrs, np.int64)
    idx = np.searchsorted(starts, a, side="right") - 1
    hit = (idx >= 0) & (a < ends[np.maximum(idx, 0)])
    counts = np.bincount(idx[hit], minlength=len(starts))
    tb = np.minimum((np.asarray(times) * n_tbins).astype(np.int64),
                    n_tbins - 1)
    blk = (a - base) // (512 << 12)
    ok = (blk >= 0) & (blk < n_blocks)
    hot = np.zeros((n_tbins, n_blocks), np.int64)
    np.add.at(hot, (tb[ok], blk[ok]), 1)
    return counts, hot


@pytest.mark.parametrize("n", [1, 1024, 1025, 2048, 2049, 4096])
def test_two_program_aggregate_matches_separate_and_numpy(rng, n,
                                                          monkeypatch):
    """Where the fused kernel's VMEM budget refuses the problem,
    ``trace_aggregate`` runs the two tiled programs on one transfer's
    inputs; at each padding edge (up to 1,024 records the two kernels pad
    the addresses to different lengths) it equals the separate calls and
    numpy, exactly."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    from repro.kernels import ops
    assert not ops.can_fuse(WIDE["k"], WIDE["n_blocks"], WIDE["n_tbins"])
    assert (ops._padded(n, ops.AGG_BLOCK_T)
            == ops._padded(n, ops.HOT_BLOCK_T)) == (n > 1024)
    addrs, times, starts, ends = _mk_trace(rng, k=WIDE["k"], n=n)
    base = 2 << 20
    nb, tb = WIDE["n_blocks"], WIDE["n_tbins"]
    counts, hot = ops.trace_aggregate(addrs, times, starts, ends, base, nb,
                                      tb, 1.0)
    np.testing.assert_array_equal(counts,
                                  ops.object_histogram(addrs, starts, ends))
    np.testing.assert_array_equal(
        hot, ops.hotness_histogram(addrs, times, base, nb, tb, 1.0))
    want_counts, want_hot = _numpy_aggregates(addrs, times, starts, ends,
                                              base, nb, tb)
    np.testing.assert_array_equal(counts, want_counts)
    np.testing.assert_array_equal(hot, want_hot)
    assert counts.dtype == hot.dtype == np.int64
    assert counts.shape == (WIDE["k"],) and hot.shape == (tb, nb)


def test_processor_routes_refused_fusion_through_trace_aggregate(
        handler, rng, monkeypatch):
    """A buffer the fused kernel cannot host still goes through
    ``ops.trace_aggregate``, the seam a planted fault in the per-object
    counts wraps, and never through the separate calls."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    from repro.kernels import ops
    calls = []

    def spy(name, fn):
        def wrapped(*a, **kw):
            calls.append(name)
            return fn(*a, **kw)
        monkeypatch.setattr(ops, name, wrapped)
    for name in ("trace_aggregate", "object_histogram", "hotness_histogram"):
        spy(name, getattr(ops, name))
    addrs, _times, starts, ends = _mk_trace(rng, k=WIDE["k"], n=3000)
    objs = list(zip(starts, ends))
    hp = {"base": 2 << 20, "n_blocks": WIDE["n_blocks"],
          "n_tbins": WIDE["n_tbins"], "t_max": 1.0}
    assert not ops.can_fuse(len(objs), hp["n_blocks"], hp["n_tbins"])
    seen = []
    proc = pasta.EventProcessor(handler, hotness=hp)
    handler.subscribe(lambda e: seen.append(e), kinds=("trace_buffer",))
    handler.trace_buffer(addrs, name="k", objects=objs,
                         object_sizes=[e - s for s, e in objs], time=0.5)
    proc.close()
    assert calls == ["trace_aggregate"]
    want_counts, want_hot = _numpy_aggregates(
        addrs, np.full(len(addrs), 0.5), starts, ends, hp["base"],
        hp["n_blocks"], hp["n_tbins"])
    np.testing.assert_array_equal(seen[0].attrs["object_counts"],
                                  want_counts)
    np.testing.assert_array_equal(seen[0].attrs["hotness_map"], want_hot)


def test_processor_fused_single_pass_matches_two_pass(handler, rng):
    """The processor's fused device path must attach the same aggregates as
    the two-kernel path (hotness disabled → separate; enabled → fused)."""
    addrs, _times, starts, ends = _mk_trace(rng, k=5, n=800)
    objs = list(zip(starts, ends))
    sizes = [e - s for s, e in objs]
    seen = []
    proc = pasta.EventProcessor(handler, hotness=dict(HOT_CFG, base=2 << 20))
    handler.subscribe(lambda e: seen.append(e), kinds=("trace_buffer",))
    handler.trace_buffer(addrs, name="k", objects=objs, object_sizes=sizes,
                         time=0.25)
    proc.close()
    fused = seen[-1]
    c2, _ = pasta.analyze_access_trace(addrs, objs, mode="device")
    hp = dict(HOT_CFG, base=2 << 20)
    h2, _ = pasta.analyze_hotness_trace(
        addrs, np.full(len(addrs), 0.25), hp["base"], hp["n_blocks"],
        hp["n_tbins"], hp["t_max"], mode="device",
        block_shift=hp["block_shift"])
    np.testing.assert_array_equal(fused.attrs["object_counts"], c2)
    np.testing.assert_array_equal(fused.attrs["hotness_map"], h2)


def test_hotness_tool_grows_to_processor_map(handler, rng):
    """A processor configured for more blocks or bins than the tool's own
    matrix (a 1.6B model's ~3.3k 2 MiB blocks against the default 1024)
    must accumulate every access instead of failing to broadcast."""
    tool = pasta.HotnessTool()                     # 64 x 1024 by default
    hp = {"base": 2 << 20, "n_blocks": 3328, "n_tbins": 8, "t_max": 1.0}
    proc = pasta.EventProcessor(handler, tools=[tool], hotness=hp)
    starts = np.array([2 << 20, 6000 << 20])
    ends = starts + (1 << 20)
    addrs = np.concatenate([rng.integers(starts[0], ends[0], 300),
                            rng.integers(starts[1], ends[1], 100)])
    handler.trace_buffer(addrs, name="k", objects=list(zip(starts, ends)),
                         object_sizes=[1 << 20, 1 << 20], time=0.5)
    proc.close()
    rep = tool.finalize()
    assert rep["total_accesses"] == 400
    assert rep["hot_matrix_shape"] == [64, 3328]
