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
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

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


def object_histogram(addrs_bytes, starts_bytes, ends_bytes):
    """Per-object access counts. Returns int64[K]."""
    k = len(starts_bytes)
    a = _to_units(addrs_bytes)
    s = _to_units(starts_bytes)
    e = _to_units(ends_bytes)
    assert a.shape[0] < 2**24, "split traces >16M records for exact f32 accum"
    be = backend()
    if be == "ref":
        return np.asarray(_ref_object_histogram(
            jnp.asarray(a), jnp.asarray(s), jnp.asarray(e))).astype(np.int64)
    a = _pad_to(a, AGG_BLOCK_T, -1)
    s = _pad_to(s, AGG_BLOCK_K, _I32_MAX)
    e = _pad_to(e, AGG_BLOCK_K, _I32_MAX)
    counts = object_histogram_pallas(jnp.asarray(a), jnp.asarray(s),
                                     jnp.asarray(e),
                                     interpret=be == "interpret")
    return np.asarray(counts[:k]).astype(np.int64)


def hotness_histogram(addrs_bytes, times, base_addr: int, n_blocks: int,
                      n_tbins: int, t_max: float,
                      block_shift: int = BLOCK_SHIFT):
    """[time-bin × block] hotness (block = 2^block_shift 512-B units; default
    2 MiB, the UVM page-group size). Returns int64[n_tbins, n_blocks]."""
    a = _to_units(addrs_bytes)
    t = np.asarray(times, dtype=np.float64)
    tb = np.minimum((t / max(t_max, 1e-12) * n_tbins).astype(np.int32),
                    n_tbins - 1)
    base = np.int32(int(base_addr) >> UNIT_SHIFT)
    be = backend()
    if be == "ref":
        out = _ref_hotness(jnp.asarray(a), jnp.asarray(tb), base,
                           n_blocks=n_blocks, n_tbins=n_tbins,
                           block_shift=block_shift)
        return np.asarray(out).astype(np.int64)
    a_p = _pad_to(a, HOT_BLOCK_T, -1)
    tb_p = _pad_to(tb, HOT_BLOCK_T, -1)
    nb_p = n_blocks + ((-n_blocks) % HOT_BLOCK_B)
    out = hotness_histogram_pallas(jnp.asarray(a_p), jnp.asarray(tb_p), base,
                                   nb_p, n_tbins, block_shift,
                                   interpret=be == "interpret")
    return np.asarray(out[:, :n_blocks]).astype(np.int64)


def can_fuse(n_objects: int, n_blocks: int, n_tbins: int) -> bool:
    """Whether the fused counts+hotness kernel can host this problem.  The
    fused kernel keeps the whole object table and hotness matrix resident in
    VMEM and materializes (tile × table) one-hot operands, so its working
    set must fit the VMEM budget — limits the tiled two-pass kernels do not
    have; callers fall back to the separate kernels when this returns False.
    The jnp oracle backend has no such limits."""
    if backend() == "ref":
        return True
    k_p = _padded(n_objects, FUSE_BLOCK_K)
    nb_p = n_blocks + ((-n_blocks) % HOT_BLOCK_B)
    return fuse_vmem_bytes(k_p, nb_p, n_tbins) <= FUSE_VMEM_BUDGET


def trace_aggregate(addrs_bytes, times, starts_bytes, ends_bytes,
                    base_addr: int, n_blocks: int, n_tbins: int,
                    t_max: float, block_shift: int = BLOCK_SHIFT):
    """Fused per-object counts AND [time-bin × block] hotness from ONE pass
    over the trace (one device round-trip instead of two).  Returns
    ``(int64[K] counts, int64[n_tbins, n_blocks] hotness)`` identical to
    running :func:`object_histogram` and :func:`hotness_histogram`
    separately."""
    k = len(starts_bytes)
    a = _to_units(addrs_bytes)
    s = _to_units(starts_bytes)
    e = _to_units(ends_bytes)
    t = np.asarray(times, dtype=np.float64)
    tb = np.minimum((t / max(t_max, 1e-12) * n_tbins).astype(np.int32),
                    n_tbins - 1)
    base = np.int32(int(base_addr) >> UNIT_SHIFT)
    assert a.shape[0] < 2**24, "split traces >16M records for exact f32 accum"
    be = backend()
    if be == "ref":
        counts, hist = _ref_trace_aggregate(
            jnp.asarray(a), jnp.asarray(tb), jnp.asarray(s), jnp.asarray(e),
            base, n_blocks=n_blocks, n_tbins=n_tbins, block_shift=block_shift)
        return (np.asarray(counts).astype(np.int64),
                np.asarray(hist).astype(np.int64))
    a_p = _pad_to(a, FUSE_BLOCK_T, -1)
    tb_p = _pad_to(tb, FUSE_BLOCK_T, -1)
    s_p = _pad_to(s, FUSE_BLOCK_K, _I32_MAX)
    e_p = _pad_to(e, FUSE_BLOCK_K, _I32_MAX)
    nb_p = n_blocks + ((-n_blocks) % HOT_BLOCK_B)
    counts, hist = trace_aggregate_pallas(
        jnp.asarray(a_p), jnp.asarray(tb_p), jnp.asarray(s_p),
        jnp.asarray(e_p), base, block_shift, n_blocks=nb_p, n_tbins=n_tbins,
        interpret=be == "interpret")
    return (np.asarray(counts[:k]).astype(np.int64),
            np.asarray(hist[:, :n_blocks]).astype(np.int64))


def matmul_traced(x: jax.Array, w: jax.Array):
    """(M,K)@(K,N) → ``(f32[M,N], int32[grid steps, 4] access trace)`` from
    the instrumented kernel (or its analytic oracle on the ``ref``
    backend)."""
    be = backend()
    if be == "ref":
        return matmul_traced_ref(x, w)
    return matmul_traced_pallas(x, w, interpret=be == "interpret")
