"""PASTA core: events, annotations, pool, processor, tools, HLO walker."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.core as pasta
from repro.core.events import Event, EventKind
from repro.core.tools import offload
from repro.launch.mesh import make_mesh


# ------------------------------------------------------------- annotations
def test_region_stack_and_events(handler):
    seen = []
    handler.subscribe(lambda e: seen.append(e),
                      kinds=(EventKind.REGION_START, EventKind.REGION_END))
    with pasta.region("fwd"):
        assert pasta.current_region() == ("fwd",)
        with pasta.region("layer0"):
            assert pasta.current_region() == ("fwd", "layer0")
    assert pasta.current_region() == ()
    kinds = [e.kind for e in seen]
    assert kinds == [EventKind.REGION_START, EventKind.REGION_START,
                     EventKind.REGION_END, EventKind.REGION_END]


def test_mismatched_end_raises(handler):
    pasta.start("a")
    with pytest.raises(RuntimeError):
        pasta.end("b")
    pasta.end("a")


def test_grid_filter_env(monkeypatch):
    monkeypatch.setenv("START_GRID_ID", "5")
    monkeypatch.setenv("END_GRID_ID", "7")
    f = pasta.GridIdFilter()
    assert not f(4) and f(5) and f(7) and not f(8)


# -------------------------------------------------------------------- pool
def test_pool_suballocation_and_free(handler):
    pool = pasta.MemoryPool(handler, chunk_size=1 << 20)
    t1 = pool.alloc(1000, "a")
    t2 = pool.alloc(2000, "b")
    assert t1.object_id == t2.object_id          # same chunk
    assert t1.addr_range()[1] <= t2.addr_range()[0] or \
        t2.addr_range()[1] <= t1.addr_range()[0]
    pool.free(t1)
    t3 = pool.alloc(900, "c")
    assert t3.addr == t1.addr                    # best-fit reuse
    with pytest.raises(ValueError):
        pool.free(t1)                            # double free


def test_pool_free_event_sign_normalization(handler):
    """Raw TENSOR_FREE arrives negative (runtime quirk); processor fixes."""
    seen = []
    proc = pasta.EventProcessor(handler)
    handler.subscribe(lambda e: seen.append(e), kinds=(EventKind.TENSOR_FREE,))
    pool = pasta.MemoryPool(handler)
    t = pool.alloc(4096)
    pool.free(t)
    assert seen[0].normalized and seen[0].size == t.size > 0


# --------------------------------------------------------------- processor
def test_trace_analysis_host_vs_device_paths(handler, rng):
    starts = np.array([2 << 20, 16 << 20], dtype=np.int64)
    ends = starts + (1 << 20)
    addrs = np.concatenate([rng.integers(starts[0], ends[0], 500),
                            rng.integers(starts[1], ends[1], 250)])
    objs = list(zip(starts, ends))
    c_dev, _ = pasta.analyze_access_trace(addrs, objs, mode="device")
    c_host, _ = pasta.analyze_access_trace(addrs, objs, mode="host")
    np.testing.assert_array_equal(c_dev, c_host)
    np.testing.assert_array_equal(c_dev, [500, 250])


# ------------------------------------------------------------------- tools
def test_kernel_freq_tool(handler):
    proc = pasta.EventProcessor(handler, tools=[pasta.KernelFrequencyTool()])
    for i in range(3):
        handler.emit(Event(EventKind.KERNEL_LAUNCH, name="fusion.1",
                           attrs={"count": 10}))
    handler.emit(Event(EventKind.KERNEL_LAUNCH, name="dot.7",
                       attrs={"count": 5}))
    rep = proc.finalize()["KernelFrequencyTool"]
    assert rep["total_invocations"] == 35
    assert rep["top"][0] == ("fusion", 30)


def test_workingset_tool_and_locator(handler):
    tools = [pasta.WorkingSetTool(), pasta.LocatorTool()]
    proc = pasta.EventProcessor(handler, tools=tools)
    pool = pasta.MemoryPool(handler)
    t1 = pool.alloc(10 << 20, "w")
    t2 = pool.alloc(1 << 20, "x")
    handler.operator_start("big", tensors=[(t1.addr, t1.size),
                                           (t2.addr, t2.size)])
    handler.operator_start("small", tensors=[(t2.addr, t2.size)])
    handler.emit(Event(EventKind.KERNEL_LAUNCH, name="gemm.1",
                       attrs={"count": 2, "bytes": 1 << 30,
                              "op_name": "jit(step)/dot_general"}))
    rep = proc.finalize()
    ws = rep["WorkingSetTool"]
    assert ws["working_set_mb"] >= 10.9          # t1+t2
    assert ws["median_ws_mb"] <= ws["working_set_mb"]
    assert ws["max_mem_referenced_kernel"] == "big"
    loc = rep["LocatorTool"]
    assert loc["kernel"] == "gemm.1"
    assert "dot_general" in loc["hlo_op_name"]


def test_timeline_tool_ramp(handler):
    proc = pasta.EventProcessor(handler, tools=[pasta.MemoryTimelineTool()])
    pool = pasta.MemoryPool(handler)
    ts = [pool.alloc(1 << 20, f"t{i}") for i in range(4)]
    for t in ts:
        pool.free(t)
    rep = proc.finalize()["MemoryTimelineTool"]
    series = rep["series"][rep["devices"][0]]
    peaks = [b for _s, b, _r in series]
    assert max(peaks) == rep["peak_bytes"][rep["devices"][0]]
    assert peaks[-1] == 0                        # ramp-down to zero


# ----------------------------------------------------------------- offload
def _mk_stream_schedule(n=32, cold_per_object=0):
    """DL-like schedule: persistent weights + a stream of fresh activation
    tensors, 4 per 8 MiB pool object (optionally with never-accessed cold
    tensors sharing the objects — the paper's tensor-vs-object wedge)."""
    object_sizes = {0: 16 << 20}
    ks = []
    footprint = 16 << 20
    for i in range(n):
        oid = 10 + i // 4
        osz = (4 + cold_per_object) * (2 << 20)
        if oid not in object_sizes:
            object_sizes[oid] = osz
            footprint += osz
        ks.append(offload.KernelAccess(
            name=f"k{i}", compute_s=1e-3,
            tensors=[(0, 16 << 20, 0), (100 + i, 2 << 20, oid)]))
    return ks, object_sizes, footprint


def test_offload_no_pressure_prefetch_wins():
    """Paper Fig. 11: with memory headroom, both prefetch granularities beat
    on-demand migration, object-level at least as well as tensor-level."""
    ks, object_sizes, fp = _mk_stream_schedule()
    out = offload.plan(ks, object_sizes, footprint=fp, oversubscription=1.0)
    assert out["tensor"]["speedup_vs_none"] > 1.05
    assert out["object"]["speedup_vs_none"] > 1.05
    assert out["object"]["time_s"] <= out["tensor"]["time_s"] * 1.02


def test_offload_oversubscription_tensor_wins():
    """Paper Fig. 12: under 3× oversubscription object granularity migrates
    never-accessed co-located tensors and thrashes; tensor-level wins."""
    ks, object_sizes, fp = _mk_stream_schedule(cold_per_object=12)
    out = offload.plan(ks, object_sizes, footprint=fp, oversubscription=3.0)
    assert out["tensor"]["time_s"] < out["object"]["time_s"]
    assert out["object"]["migrated_bytes"] > out["tensor"]["migrated_bytes"]


# --------------------------------------------------------------------- hlo
def test_hlo_walker_counts_scan_trip(handler):
    def f(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y
    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    stats = handler.capture_compiled(compiled, label="scan7")
    # 7 iterations × 2·64³ flops
    assert stats.flops == pytest.approx(7 * 2 * 64 ** 3, rel=0.2)


def test_hlo_walker_collectives(handler):
    import jax.sharding as sh
    if jax.device_count() < 1:
        pytest.skip("no devices")
    mesh = make_mesh((1,), ("model",))
    spec = sh.NamedSharding(mesh, sh.PartitionSpec(None, "model"))

    def f(x):
        return jax.lax.with_sharding_constraint(x @ x.T, spec).sum()
    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile()
    stats = handler.capture_compiled(compiled)
    assert stats.flops > 0                        # parses without error


#: matmuls as the TPU compiler emits them: a projection whose head axis
#: rides a fully padded window, and attention scores whose batch axes ride
#: dilated, strided windows (one real tap per output along each)
TPU_CONV_HLO = """
HloModule tpu_convs

ENTRY %main (p0: bf16[8,1024,768,1], p1: bf16[768,12,64,1], p2: bf16[8,12,64,1024], p3: bf16[8,1024,12,64]) -> (bf16[8,1024,12,64], bf16[8,12,1024,1024]) {
  %p0 = bf16[8,1024,768,1]{3,2,1,0} parameter(0)
  %p1 = bf16[768,12,64,1]{3,2,1,0} parameter(1)
  %p2 = bf16[8,12,64,1024]{3,2,1,0} parameter(2)
  %p3 = bf16[8,1024,12,64]{3,2,1,0} parameter(3)
  %proj = bf16[8,1024,12,64]{3,2,1,0} convolution(bf16[8,1024,768,1]{3,2,1,0} %p0, bf16[768,12,64,1]{3,2,1,0} %p1), window={size=1x12 pad=0_0x11_11 rhs_reversal=0x1}, dim_labels=0bf1_i1o0->0b1f
  %scores = bf16[8,12,1024,1024]{3,2,1,0} convolution(bf16[8,12,64,1024]{3,2,1,0} %p2, bf16[8,1024,12,64]{3,2,1,0} %p3), window={size=8x12 stride=7x11 lhs_dilate=8x12}, dim_labels=01fb_0o1i->01bf
  ROOT %t = (bf16[8,1024,12,64]{3,2,1,0}, bf16[8,12,1024,1024]{3,2,1,0}) tuple(%proj, %scores)
}
"""


def test_hlo_walker_tpu_convolution_flops():
    from repro.core.hlo import analyze_text
    stats = analyze_text(TPU_CONV_HLO)
    proj = 2 * (8 * 1024) * 768 * (12 * 64)        # tokens x d x heads*hd
    scores = 2 * (8 * 12) * 1024 * 1024 * 64       # B*H x S x S x hd
    assert stats.flops == pytest.approx(proj + scores)
    assert not stats.warnings


def test_shape_bytes():
    from repro.core.hlo import shape_bytes
    assert shape_bytes("f32[128,1024]{1,0}") == 128 * 1024 * 4
    assert shape_bytes("bf16[8]") == 16
    assert shape_bytes("(f32[2,2]{1,0}, u8[16]{0})") == 32
    assert shape_bytes("pred[]") == 1
    assert shape_bytes("token[]") == 0
