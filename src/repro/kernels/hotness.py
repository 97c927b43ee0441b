"""Time-series hotness aggregation (paper §V-C2) as a Pallas TPU kernel.

Builds the [time-bin × 2 MiB-block] access-hotness matrix on device.  The 2-D
histogram is expressed as a rank-expanding one-hot **matmul** so the MXU does
the scatter.  Records stay on lanes (the trace tile is a (1, T) row):

    onehot_t[i, t] = (tbin[t] == i)              # (TBINS, T)
    onehot_b[j, t] = (block[t] == j)             # (BLOCK_B, T)
    hist[i, j]    += onehot_t @ onehot_b.T       # MXU, bf16 0/1 operands,
                                                 # exact in f32 < 2**24

Grid: (n_block_tiles, n_trace_tiles), trace axis innermost so each hist tile
accumulates in VMEM across the full stream.  Time bins are padded to the
8-row sublane tile; ``base``/``shift`` are SMEM scalars.  VMEM per step at
defaults (T=1024, BLOCK_B=512): the block one-hot (512×1024 bf16 = 1 MiB)
plus the hist tile."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_T = 1024     # trace records per tile
BLOCK_B = 512      # memory blocks per tile


def tile_hotness(a, tb, base, shift, block0, n_tbins: int, n_blocks: int):
    """One trace tile's [time-bin × block] counts for blocks
    ``[block0, block0 + n_blocks)``.  ``a``/``tb`` are (1, T) rows: records
    stay on lanes, so each one-hot is a sublane broadcast of the row against
    an iota column — no lane→sublane relayout (Mosaic refuses reshapes of
    i1 vectors).  The [tbins, T] × [blocks, T]ᵀ contraction runs on the MXU
    in bf16, exact for 0/1 operands with f32 accumulation."""
    blk = jax.lax.shift_right_arithmetic(a - base, shift) - block0
    valid = (blk >= 0) & (blk < n_blocks) & (tb >= 0) & (tb < n_tbins) & \
        (a >= 0)
    tb = jnp.where(valid, tb, -1)
    t = a.shape[1]
    onehot_t = (tb == jax.lax.broadcasted_iota(jnp.int32, (n_tbins, t), 0)
                ).astype(jnp.bfloat16)
    onehot_b = (blk == jax.lax.broadcasted_iota(jnp.int32, (n_blocks, t), 0)
                ).astype(jnp.bfloat16)
    return jax.lax.dot_general(onehot_t, onehot_b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _kernel(meta_ref, addrs_ref, tbins_ref, hist_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    n_tbins, n_blocks = hist_ref.shape
    hist_ref[...] += tile_hotness(addrs_ref[...], tbins_ref[...],
                                  meta_ref[0, 0], meta_ref[0, 1],
                                  pl.program_id(0) * n_blocks,
                                  n_tbins, n_blocks)


def pad_tbins(n_tbins: int) -> int:
    """Time bins padded to the 8-row sublane tile (padding rows stay 0)."""
    return n_tbins + (-n_tbins) % 8


@functools.partial(jax.jit, static_argnames=("n_blocks", "n_tbins",
                                              "block_shift", "interpret"))
def hotness_histogram_pallas(addrs: jax.Array, tbins: jax.Array, base,
                             n_blocks: int, n_tbins: int, block_shift: int,
                             interpret: bool = False):
    """addrs int32[N] (512 B units, -1 = padding), tbins int32[N], base
    scalar int32 → f32[n_tbins, n_blocks]."""
    n = addrs.shape[0]
    assert n % BLOCK_T == 0 and n_blocks % BLOCK_B == 0, (n, n_blocks)
    nt_p = pad_tbins(n_tbins)
    grid = (n_blocks // BLOCK_B, n // BLOCK_T)
    meta = jnp.array([[base, block_shift]], dtype=jnp.int32)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, BLOCK_T), lambda bb, nn: (0, nn)),
            pl.BlockSpec((1, BLOCK_T), lambda bb, nn: (0, nn)),
        ],
        out_specs=pl.BlockSpec((nt_p, BLOCK_B), lambda bb, nn: (0, bb)),
        out_shape=jax.ShapeDtypeStruct((nt_p, n_blocks), jnp.float32),
        interpret=interpret,
    )(meta, addrs.reshape(1, n), tbins.reshape(1, n))
    return out[:n_tbins]
