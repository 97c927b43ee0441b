"""Device-resident trace aggregation (paper Fig. 2b) as a Pallas TPU kernel.

The paper's GPU version has warps increment per-object access counters with
atomics.  Scatter atomics are the wrong shape for a TPU; the TPU-native
formulation is *histogramming as a matmul*:

    in_range[t, k] = (starts[k] <= addr[t] < ends[k])     # VPU compares
    counts[k]     += ones[1, T] @ in_range[T, K]           # MXU reduction

Object ranges are disjoint, so ``in_range`` rows are one-hot and the f32
accumulation is exact for N < 2**24 records (asserted by the wrapper).

Tiling: the trace is streamed through VMEM in (1, BLOCK_T) tiles; object
tables live in (1, BLOCK_K) tiles; the grid is (K/BLOCK_K, N/BLOCK_T) with
the trace axis innermost so each counts tile stays resident in VMEM across
the whole stream (revisit-free output).  VMEM footprint per step:
BLOCK_T·4 B (addrs) + 2·BLOCK_K·4 B (ranges) + BLOCK_T·BLOCK_K·4 B (one-hot)
+ BLOCK_K·4 B (counts) ≈ 4.2 MiB at the default 2048×512 — comfortably
inside 16 MiB VMEM with double buffering; both block dims are multiples of
the 128-lane MXU/VPU tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .hotness import pad_tbins, tile_hotness

BLOCK_T = 2048     # trace records per tile
BLOCK_K = 512      # objects per tile


def tile_counts(a, s, e):
    """One trace tile's per-object hit counts: ``a`` is the (1, T) address
    row, ``s``/``e`` the (1, K) range rows → f32 (1, K)."""
    col = a[0, :][:, None]                     # (T, 1) int32 column
    in_range = ((col >= s) & (col < e)).astype(jnp.float32)      # (T, K)
    ones = jnp.ones((1, a.shape[1]), dtype=jnp.float32)
    return jax.lax.dot(ones, in_range, preferred_element_type=jnp.float32)


def _kernel(addrs_ref, starts_ref, ends_ref, counts_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    counts_ref[...] += tile_counts(addrs_ref[...], starts_ref[...],
                                   ends_ref[...])


#: trace records per tile for the fused counts+hotness kernel; smaller than
#: BLOCK_T because the tile feeds THREE one-hot matmuls' operands at once
FUSE_BLOCK_T = 1024
#: object-table padding granularity for the fused kernel (full table
#: resident in VMEM, so pad to the 128-lane tile only)
FUSE_BLOCK_K = 128
#: conservative slice of the ~16 MiB scoped VMEM left for the fused
#: kernel's working set (accumulators + one-hot operands + compiler
#: temporaries).  A routing threshold, not a compiler limit: the v5e
#: compiler accepted the fused kernel at every size probed, up to a
#: :func:`fuse_vmem_bytes` estimate of 1 GiB.
FUSE_VMEM_BUDGET = 12 * 1024 * 1024


def fuse_vmem_bytes(k: int, n_blocks: int, n_tbins: int) -> int:
    """Worst-case f32 VMEM footprint of one fused-kernel grid step: the
    resident accumulators (counts[K], hist[tbins, blocks]) plus the
    per-tile transients — in_range (T×K), onehot_t (T×tbins), onehot_b
    (T×blocks) — doubled for their iota/compare intermediates.  Used by
    :func:`repro.kernels.ops.can_fuse` to route oversize problems to the
    tiled two-pass kernels instead."""
    resident = 4 * (k + n_tbins * n_blocks)
    transient = 4 * FUSE_BLOCK_T * (k + n_blocks + n_tbins)
    return resident + 2 * transient


def _fused_kernel(meta_ref, addrs_ref, tbins_ref, starts_ref, ends_ref,
                  counts_ref, hist_ref):
    """One stream over the trace, two accumulators: per-object counts and
    the [time-bin × block] hotness map share each (1, FUSE_BLOCK_T) addr
    tile, so the trace is read from HBM exactly once (vs twice for the
    separate object_histogram + hotness_histogram kernels)."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        hist_ref[...] = jnp.zeros_like(hist_ref)

    a = addrs_ref[...]                         # (1, T) shared addr tile
    counts_ref[...] += tile_counts(a, starts_ref[...], ends_ref[...])
    n_tbins, n_blocks = hist_ref.shape
    hist_ref[...] += tile_hotness(a, tbins_ref[...], meta_ref[0, 0],
                                  meta_ref[0, 1], 0, n_tbins, n_blocks)


@functools.partial(jax.jit, static_argnames=("n_blocks", "n_tbins",
                                              "interpret"))
def trace_aggregate_pallas(addrs: jax.Array, tbins: jax.Array,
                           starts: jax.Array, ends: jax.Array, base,
                           block_shift, n_blocks: int, n_tbins: int,
                           interpret: bool = False):
    """Fused device pass: addrs int32[N] (512 B units, -1 = padding),
    tbins int32[N] (-1 = padding), starts/ends int32[K] (disjoint sorted
    ranges, padded with empty [MAX, MAX)) → (f32[K] counts,
    f32[n_tbins, n_blocks] hotness).  Both the object table and the hotness
    matrix stay resident in VMEM across the whole stream (grid is the trace
    axis only), bounded by FUSE_VMEM_BUDGET — callers must pre-check with
    ``ops.can_fuse`` and fall back to the tiled two-pass kernels."""
    n = addrs.shape[0]
    k = starts.shape[0]
    assert n % FUSE_BLOCK_T == 0 and k % FUSE_BLOCK_K == 0, (n, k)
    assert fuse_vmem_bytes(k, n_blocks, n_tbins) <= FUSE_VMEM_BUDGET, \
        f"fused working set exceeds VMEM budget: {(k, n_blocks, n_tbins)}"
    nt_p = pad_tbins(n_tbins)
    grid = (n // FUSE_BLOCK_T,)
    meta = jnp.array([[base, block_shift]], dtype=jnp.int32)
    counts, hist = pl.pallas_call(
        _fused_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, FUSE_BLOCK_T), lambda nn: (0, nn)),
            pl.BlockSpec((1, FUSE_BLOCK_T), lambda nn: (0, nn)),
            pl.BlockSpec((1, k), lambda nn: (0, 0)),
            pl.BlockSpec((1, k), lambda nn: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, k), lambda nn: (0, 0)),
            pl.BlockSpec((nt_p, n_blocks), lambda nn: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((nt_p, n_blocks), jnp.float32),
        ],
        interpret=interpret,
    )(meta, addrs.reshape(1, n), tbins.reshape(1, n), starts.reshape(1, k),
      ends.reshape(1, k))
    return counts[0], hist[:n_tbins]


@functools.partial(jax.jit, static_argnames=("interpret",))
def object_histogram_pallas(addrs: jax.Array, starts: jax.Array,
                            ends: jax.Array, interpret: bool = False):
    """addrs int32[N], starts/ends int32[K] (disjoint sorted ranges) →
    f32[K] counts.  N, K are padded to tile multiples by the caller
    (pad addrs with -1; pad ranges with empty [0, 0))."""
    n = addrs.shape[0]
    k = starts.shape[0]
    assert n % BLOCK_T == 0 and k % BLOCK_K == 0, (n, k)
    grid = (k // BLOCK_K, n // BLOCK_T)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BLOCK_T), lambda kk, nn: (0, nn)),
            pl.BlockSpec((1, BLOCK_K), lambda kk, nn: (0, kk)),
            pl.BlockSpec((1, BLOCK_K), lambda kk, nn: (0, kk)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_K), lambda kk, nn: (0, kk)),
        out_shape=jax.ShapeDtypeStruct((1, k), jnp.float32),
        interpret=interpret,
    )(addrs.reshape(1, n), starts.reshape(1, k), ends.reshape(1, k))
    return out[0]
