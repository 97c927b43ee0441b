"""PASTA's own spans on the profiler's clock: a tiny fine-grained
instrumented forward on the CPU, recorded by ``jax.profiler`` and read back
from the ``.xplane.pb``.

The span tree (each span inside its parent, on the same thread):

    pasta.op                 one operator boundary (``op_hook``)
      pasta.op.records       record generation           [records]
      pasta.op.objects       the sorted live-tensor table
      pasta.dispatch         every subscriber of one batch
        pasta.process.trace  one trace buffer's reduction
          pasta.kernel.pad     unit conversion, padding  [records, padded]
          pasta.kernel.launch  transfers and the jitted call
          pasta.kernel.fetch   the read-back
        pasta.tool           one tool's on_batch / on_event
    pasta.free               a tensor's release (weakref finalizer)
"""

import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.core as pasta
from repro.core.events import EventKind
from repro.kernels import ops
from repro.models import ModelConfig, forward, init_params

PARENT = {
    "pasta.op.records": "pasta.op",
    "pasta.op.objects": "pasta.op",
    "pasta.process.trace": "pasta.dispatch",
    "pasta.kernel.pad": "pasta.process.trace",
    "pasta.kernel.launch": "pasta.process.trace",
    "pasta.kernel.fetch": "pasta.process.trace",
    "pasta.tool": "pasta.dispatch",
}
NAMES = {"pasta.op", "pasta.dispatch", "pasta.free", *PARENT}
TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128,
                   vocab_size=256)


def _profiled(fn, tmp):
    """Runs ``fn`` under the profiler; returns every host event whose name
    starts with ``pasta.`` as (name, start, end, stats, line)."""
    jax.profiler.start_trace(str(tmp))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("pasta."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats),
                                (plane.name, i)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _innermost_parent(ev, events):
    """The shortest other pasta span on ``ev``'s thread that holds it,
    leaving out ``pasta.free``: a finalizer can run inside any span."""
    name, s, e, _, line = ev
    holders = [p for p in events
               if p is not ev and p[4] == line and p[1] <= s and e <= p[2]
               and p[0] != "pasta.free"]
    return min(holders, key=lambda p: p[2] - p[1])[0] if holders else None


@pytest.fixture(scope="module")
def forward_spans(tmp_path_factory):
    params = init_params(jax.random.PRNGKey(0), TINY)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 16)))
    seen = {"ops": 0, "records": []}

    def count(batch):      # subscribed before the processor drops records
        for i in range(len(batch)):
            kind = batch.event(i).kind
            if kind is EventKind.OPERATOR_START:
                seen["ops"] += 1
            elif kind is EventKind.TRACE_BUFFER:
                seen["records"].append(
                    len(batch.attrs_at(i)["records"]))

    handler = pasta.EventHandler()
    handler.subscribe_batch(count)
    session = pasta.Session(
        tools="workingset,hotness,kernel_freq", handler=handler,
        instrument=True, fine=True,
        hotness={"base": 0, "n_blocks": 64, "n_tbins": 4, "t_max": 1e6})
    forward(params, tokens, TINY)        # compile outside the profile

    def run():
        with session:
            jax.block_until_ready(forward(params, tokens, TINY)[0])

    # the reference kernels (another test file may have chosen interpret
    # mode for the whole process as it was imported)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_PALLAS_INTERPRET", "0")
        events = _profiled(run, tmp_path_factory.mktemp("trace"))
    session.close()
    return events, seen


def test_every_span_nests_in_its_parent(forward_spans):
    events, _ = forward_spans
    names = {e[0] for e in events}
    assert names == NAMES
    for ev in events:
        if ev[0] in PARENT:
            assert _innermost_parent(ev, events) == PARENT[ev[0]], ev[:3]


def test_one_op_span_per_operator_and_one_reduction_per_buffer(
        forward_spans):
    events, seen = forward_spans
    count = {n: sum(e[0] == n for e in events) for n in NAMES}
    assert seen["ops"] > 0 and seen["records"]
    assert count["pasta.op"] == seen["ops"]
    assert count["pasta.op.records"] == count["pasta.op.objects"] \
        == len(seen["records"])
    assert count["pasta.process.trace"] == len(seen["records"])
    # the CPU's reference kernels always fuse: one round trip a buffer
    assert count["pasta.kernel.fetch"] == count["pasta.process.trace"]


def test_pad_span_counts_each_buffers_records(forward_spans):
    events, seen = forward_spans
    pads = [e[3] for e in events if e[0] == "pasta.kernel.pad"]
    assert [p["records"] for p in pads] == seen["records"]
    assert all(p["padded"] == p["records"] for p in pads)   # ref pads none
    made = [e[3]["records"] for e in events if e[0] == "pasta.op.records"]
    assert made == seen["records"]


KERNELS = {
    "object_histogram": (
        lambda a, t: ops.object_histogram(a, [0, 1 << 20], [1 << 19,
                                                            2 << 20]),
        ops.AGG_BLOCK_T),
    "hotness_histogram": (
        lambda a, t: ops.hotness_histogram(a, t, 0, 4, 2, 1.0),
        ops.HOT_BLOCK_T),
    "trace_aggregate": (
        lambda a, t: ops.trace_aggregate(a, t, [0, 1 << 20],
                                         [1 << 19, 2 << 20], 0, 4, 2, 1.0),
        ops.FUSE_BLOCK_T),
}


@pytest.mark.parametrize("backend", ["ref", "interpret"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_call_spans_on_every_backend(kernel, backend, tmp_path,
                                            monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET",
                       "1" if backend == "interpret" else "0")
    assert ops.backend() == backend
    call, tile = KERNELS[kernel]
    rng = np.random.default_rng(1)
    addrs = rng.integers(0, 2 << 20, 1000)
    times = rng.random(1000)
    want = call(addrs, times)                # compile outside the profile
    got = []
    events = _profiled(lambda: got.append(call(addrs, times)), tmp_path)
    assert [e[0] for e in events] == ["pasta.kernel.pad",
                                      "pasta.kernel.launch",
                                      "pasta.kernel.fetch"]
    assert all(a[2] <= b[1] for a, b in zip(events, events[1:]))
    padded = 1000 if backend == "ref" else ops._padded(1000, tile)
    assert events[0][3] == {"records": 1000, "padded": padded}
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got[0])):
        np.testing.assert_array_equal(w, g)


#: a hotness shape the fused kernel's VMEM budget refuses (the fine-grained
#: profile's own): ``trace_aggregate`` runs its two tiled programs
WIDE_BLOCKS, WIDE_TBINS = 1048, 8


@pytest.mark.parametrize("n", [1000, 4096])
def test_two_program_aggregate_spans(n, tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert not ops.can_fuse(2, WIDE_BLOCKS, WIDE_TBINS)
    rng = np.random.default_rng(2)
    addrs = rng.integers(0, 2 << 20, n)
    times = rng.random(n)

    def call():
        return ops.trace_aggregate(addrs, times, [0, 1 << 20],
                                   [1 << 19, 2 << 20], 0, WIDE_BLOCKS,
                                   WIDE_TBINS, 1.0)
    want = call()                            # compile outside the profile
    got = []
    events = _profiled(lambda: got.append(call()), tmp_path)
    assert [e[0] for e in events] == ["pasta.kernel.pad",
                                      "pasta.kernel.launch",
                                      "pasta.kernel.fetch"]
    assert all(a[2] <= b[1] for a, b in zip(events, events[1:]))
    # the padded length is the mean over the two programs' padding rules
    padded = (ops._padded(n, ops.AGG_BLOCK_T)
              + ops._padded(n, ops.HOT_BLOCK_T)) // 2
    assert events[0][3] == {"records": n, "padded": padded}
    assert events[1][3] == {"transfers": 1, "programs": 2}
    for w, g in zip(want, got[0]):
        np.testing.assert_array_equal(w, g)


def test_refused_fusion_reads_each_buffer_back_once(tmp_path, monkeypatch):
    """A buffer the fused kernel cannot host costs the processor one
    blocking read-back, as a fused one does."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    hp = {"base": 0, "n_blocks": WIDE_BLOCKS, "n_tbins": WIDE_TBINS,
          "t_max": 1.0}
    objects = [(0, 1 << 19), (1 << 20, 2 << 20)]
    assert not ops.can_fuse(len(objects), hp["n_blocks"], hp["n_tbins"])
    handler = pasta.EventHandler()
    proc = pasta.EventProcessor(handler, hotness=hp)
    rng = np.random.default_rng(3)

    def emit(n_buffers):
        for i in range(n_buffers):
            handler.trace_buffer(rng.integers(0, 2 << 20, 3000), name="k",
                                 objects=objects,
                                 object_sizes=[1 << 19, 1 << 20],
                                 time=i / n_buffers)
    emit(1)                                  # compile outside the profile
    events = _profiled(lambda: emit(3), tmp_path)
    proc.close()
    count = {n: sum(e[0] == n for e in events)
             for n in ("pasta.process.trace", "pasta.kernel.launch",
                       "pasta.kernel.fetch")}
    assert count == {"pasta.process.trace": 3, "pasta.kernel.launch": 3,
                     "pasta.kernel.fetch": 3}
    assert all(e[3] == {"transfers": 1, "programs": 2}
               for e in events if e[0] == "pasta.kernel.launch")


def test_no_span_is_recorded_without_a_profiler(tmp_path):
    """Spans opened before a profiler starts leave nothing in its trace."""
    def idle():
        pass
    ops.object_histogram(np.arange(64) * 512, [0], [1 << 20])
    assert _profiled(idle, tmp_path) == []
