"""Jitted dispatch wrappers for the PASTA analysis kernels.

Dispatch policy (:func:`backend`):

  * on TPU: the Pallas kernels, compiled — always; nothing off the chip's
    own compiler is ever chosen there;
  * off the chip with ``REPRO_PALLAS_INTERPRET=1``: Pallas kernels in
    interpret mode (CPU correctness path used by the test sweeps);
  * otherwise: the pure-jnp oracles in :mod:`repro.kernels.ref` compiled by
    XLA — still the device-resident (Fig. 2b) analysis model, just without
    hand tiling.

Addresses are byte int64 at the API; kernels work in 512-byte units (int32),
which is lossless because the pool rounds tensors to 512 B.

Each reduction call sends its inputs up in one transfer call, runs its
programs, and reads every result back in one blocking round trip, trimming
the padding on the host.  :func:`trace_aggregate` makes both aggregates of
a trace: with the fused kernel where :func:`can_fuse` says its working set
fits the VMEM budget, else with the two tiled programs back to back on the
same device-resident inputs.

Each reduction call opens three profiler spans on every backend:
``pasta.kernel.pad`` (unit conversion and padding; stats ``records`` and
``padded``), ``pasta.kernel.launch`` (the transfer call and the jitted
programs; stats ``transfers`` and ``programs``) and ``pasta.kernel.fetch``
(the read-back, which waits for the device).
"""

from __future__ import annotations

import os

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from . import ref
from .trace_aggregate import BLOCK_T as AGG_BLOCK_T, BLOCK_K as AGG_BLOCK_K
from .trace_aggregate import (FUSE_BLOCK_T, FUSE_BLOCK_K, FUSE_VMEM_BUDGET,
                              fuse_vmem_bytes, object_histogram_pallas,
                              trace_aggregate_pallas)
from .hotness import BLOCK_T as HOT_BLOCK_T, BLOCK_B as HOT_BLOCK_B
from .hotness import hotness_histogram_pallas
from .instrumented_matmul import matmul_traced_pallas, matmul_traced_ref

UNIT_SHIFT = 9                 # 512-byte address units
BLOCK_SHIFT = 12               # 2 MiB blocks = 4096 units = 2**12
_I32_MAX = np.int32(2**31 - 1)


def backend() -> str:
    """``"pallas"`` on TPU; off it ``"interpret"`` or ``"ref"``."""
    if jax.default_backend() == "tpu":
        return "pallas"
    if os.environ.get("REPRO_PALLAS_INTERPRET") == "1":
        return "interpret"
    return "ref"


_ref_object_histogram = jax.jit(ref.object_histogram_ref)
_ref_hotness = jax.jit(ref.hotness_histogram_ref,
                       static_argnames=("n_blocks", "n_tbins", "block_shift"))
_ref_trace_aggregate = jax.jit(
    ref.trace_aggregate_ref,
    static_argnames=("n_blocks", "n_tbins", "block_shift"))


def _padded(n: int, tile: int) -> int:
    """``n`` rounded up to ``tile`` × a power of two: traces and object
    tables of every length then share a few compiled kernel shapes instead
    of compiling one per length."""
    return tile * (1 << max(-(-n // tile) - 1, 0).bit_length())


def _pad_to(x: np.ndarray, tile: int, value) -> np.ndarray:
    n = x.shape[0]
    target = _padded(n, tile)
    if target == n:
        return x
    return np.concatenate([x, np.full(target - n, value, dtype=x.dtype)])


def _to_units(addrs_bytes) -> np.ndarray:
    a = np.asarray(addrs_bytes, dtype=np.int64) >> UNIT_SHIFT
    assert a.max(initial=0) < 2**31, "address space exceeds int32 units"
    return a.astype(np.int32)


def _to_tbins(times, n_tbins: int, t_max: float) -> np.ndarray:
    t = np.asarray(times, dtype=np.float64)
    return np.minimum((t / max(t_max, 1e-12) * n_tbins).astype(np.int32),
                      n_tbins - 1)


def _blocks_padded(n_blocks: int) -> int:
    return n_blocks + ((-n_blocks) % HOT_BLOCK_B)


def _pad_span(n: int, be: str, *tiles: int) -> TraceAnnotation:
    """The span around a call's unit conversion and padding, with the
    buffer's record count and its padded length: the mean over the
    programs that read it, one per tile (``records`` on the ``ref``
    backend, which pads nothing)."""
    padded = n if be == "ref" else \
        sum(_padded(n, t) for t in tiles) // len(tiles)
    return TraceAnnotation("pasta.kernel.pad", records=n, padded=padded)


def _put(*arrays) -> list:
    """A call's host arrays on the device in one transfer call; an array
    given twice goes up once."""
    uniq = {id(x): x for x in arrays}
    on_device = dict(zip(uniq, jax.device_put(tuple(uniq.values()))))
    return [on_device[id(x)] for x in arrays]


def _fetch(results, trims):
    """The call's one read-back: every result's copy is in flight at once,
    then each is trimmed of its padding on the host."""
    with TraceAnnotation("pasta.kernel.fetch"):
        return tuple(r[t].astype(np.int64)
                     for r, t in zip(jax.device_get(tuple(results)), trims))


def _count_units(addrs_bytes, starts_bytes, ends_bytes):
    a = _to_units(addrs_bytes)
    assert a.shape[0] < 2**24, "split traces >16M records for exact f32 accum"
    return a, _to_units(starts_bytes), _to_units(ends_bytes)


def object_histogram(addrs_bytes, starts_bytes, ends_bytes):
    """Per-object access counts. Returns int64[K]."""
    k = len(starts_bytes)
    be = backend()
    with _pad_span(len(addrs_bytes), be, AGG_BLOCK_T):
        a, s, e = _count_units(addrs_bytes, starts_bytes, ends_bytes)
        if be != "ref":
            a = _pad_to(a, AGG_BLOCK_T, -1)
            s = _pad_to(s, AGG_BLOCK_K, _I32_MAX)
            e = _pad_to(e, AGG_BLOCK_K, _I32_MAX)
    with TraceAnnotation("pasta.kernel.launch", transfers=1, programs=1):
        a, s, e = _put(a, s, e)
        if be == "ref":
            counts = _ref_object_histogram(a, s, e)
        else:
            counts = object_histogram_pallas(a, s, e,
                                             interpret=be == "interpret")
    return _fetch((counts,), (np.s_[:k],))[0]


def hotness_histogram(addrs_bytes, times, base_addr: int, n_blocks: int,
                      n_tbins: int, t_max: float,
                      block_shift: int = BLOCK_SHIFT):
    """[time-bin × block] hotness (block = 2^block_shift 512-B units; default
    2 MiB, the UVM page-group size). Returns int64[n_tbins, n_blocks]."""
    be = backend()
    with _pad_span(len(addrs_bytes), be, HOT_BLOCK_T):
        a = _to_units(addrs_bytes)
        tb = _to_tbins(times, n_tbins, t_max)
        base = np.int32(int(base_addr) >> UNIT_SHIFT)
        if be != "ref":
            a = _pad_to(a, HOT_BLOCK_T, -1)
            tb = _pad_to(tb, HOT_BLOCK_T, -1)
    with TraceAnnotation("pasta.kernel.launch", transfers=1, programs=1):
        a, tb, base = _put(a, tb, base)
        if be == "ref":
            out = _ref_hotness(a, tb, base, n_blocks=n_blocks,
                               n_tbins=n_tbins, block_shift=block_shift)
        else:
            out = hotness_histogram_pallas(
                a, tb, base, _blocks_padded(n_blocks), n_tbins, block_shift,
                interpret=be == "interpret")
    return _fetch((out,), (np.s_[:, :n_blocks],))[0]


def can_fuse(n_objects: int, n_blocks: int, n_tbins: int) -> bool:
    """Whether the fused counts+hotness kernel can host this problem.  The
    fused kernel keeps the whole object table and hotness matrix resident in
    VMEM and materializes (tile × table) one-hot operands, so its working
    set must fit the VMEM budget — limits the tiled two-pass kernels do not
    have; :func:`trace_aggregate` runs those two programs instead when this
    returns False.  The jnp oracle backend has no such limits."""
    if backend() == "ref":
        return True
    k_p = _padded(n_objects, FUSE_BLOCK_K)
    return fuse_vmem_bytes(k_p, _blocks_padded(n_blocks),
                           n_tbins) <= FUSE_VMEM_BUDGET


def trace_aggregate(addrs_bytes, times, starts_bytes, ends_bytes,
                    base_addr: int, n_blocks: int, n_tbins: int,
                    t_max: float, block_shift: int = BLOCK_SHIFT):
    """Per-object counts AND [time-bin × block] hotness of one trace, with
    one transfer call and one read-back.  Returns ``(int64[K] counts,
    int64[n_tbins, n_blocks] hotness)`` identical to running
    :func:`object_histogram` and :func:`hotness_histogram` separately.

    Where :func:`can_fuse` admits the problem, the fused kernel makes both
    in one pass over the trace; otherwise the two tiled programs,
    ``object_histogram_pallas`` and ``hotness_histogram_pallas``, run back
    to back on the same device-resident inputs, which share one address
    array where both kernels' padding rules give the same length (every
    trace of more than ``HOT_BLOCK_T`` records)."""
    k = len(starts_bytes)
    n = len(addrs_bytes)
    be = backend()
    fused = can_fuse(k, n_blocks, n_tbins)
    tiles = (FUSE_BLOCK_T,) if fused else (AGG_BLOCK_T, HOT_BLOCK_T)
    with _pad_span(n, be, *tiles):
        a, s, e = _count_units(addrs_bytes, starts_bytes, ends_bytes)
        tb = _to_tbins(times, n_tbins, t_max)
        base = np.int32(int(base_addr) >> UNIT_SHIFT)
        if be == "ref":
            a_hot = a
        elif fused:
            a = a_hot = _pad_to(a, FUSE_BLOCK_T, -1)
            tb = _pad_to(tb, FUSE_BLOCK_T, -1)
            s = _pad_to(s, FUSE_BLOCK_K, _I32_MAX)
            e = _pad_to(e, FUSE_BLOCK_K, _I32_MAX)
        else:
            a_hot = _pad_to(a, HOT_BLOCK_T, -1)
            a = _pad_to(a, AGG_BLOCK_T, -1)
            if a.shape == a_hot.shape:
                a_hot = a
            tb = _pad_to(tb, HOT_BLOCK_T, -1)
            s = _pad_to(s, AGG_BLOCK_K, _I32_MAX)
            e = _pad_to(e, AGG_BLOCK_K, _I32_MAX)
    with TraceAnnotation("pasta.kernel.launch", transfers=1,
                         programs=len(tiles)):
        a, a_hot, tb, s, e, base = _put(a, a_hot, tb, s, e, base)
        if be == "ref":
            counts, hist = _ref_trace_aggregate(
                a, tb, s, e, base, n_blocks=n_blocks, n_tbins=n_tbins,
                block_shift=block_shift)
        elif fused:
            counts, hist = trace_aggregate_pallas(
                a, tb, s, e, base, block_shift,
                n_blocks=_blocks_padded(n_blocks), n_tbins=n_tbins,
                interpret=be == "interpret")
        else:
            counts = object_histogram_pallas(a, s, e,
                                             interpret=be == "interpret")
            hist = hotness_histogram_pallas(
                a_hot, tb, base, _blocks_padded(n_blocks), n_tbins,
                block_shift, interpret=be == "interpret")
    return _fetch((counts, hist), (np.s_[:k], np.s_[:, :n_blocks]))


def matmul_traced(x: jax.Array, w: jax.Array):
    """(M,K)@(K,N) → ``(f32[M,N], int32[grid steps, 4] access trace)`` from
    the instrumented kernel (or its analytic oracle on the ``ref``
    backend)."""
    be = backend()
    if be == "ref":
        return matmul_traced_ref(x, w)
    return matmul_traced_pallas(x, w, interpret=be == "interpret")
