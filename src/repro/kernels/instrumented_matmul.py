"""Instrumented compute kernel — in-kernel device-side event recording.

Table II's fine-grained tier (thread-block entry/exit, per-access events) has
no interception surface on TPU; the PASTA way to get it is *opt-in kernel
instrumentation*: the kernel itself appends records to a device-resident
trace buffer as it runs (paper Fig. 2b: produce events where the data is).

This blocked matmul writes, per (i, j) grid step, one record
``[block_i, block_j, bytes_read, bytes_written]`` into a trace output that
lives entirely on device; the PASTA processor aggregates it without ever
copying raw per-access data to the host.  The compute tile is the standard
MXU-aligned (BM×K)·(K×BN) block with f32 accumulation; the instrumentation
adds one 4-int VMEM row per grid step (<0.01 % overhead), matching the
paper's low-overhead-hooks principle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BM = 128
BN = 128


def _kernel(x_ref, w_ref, o_ref, trace_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)
    x = x_ref[...]
    w = w_ref[...]
    o_ref[...] = jax.lax.dot(x, w, preferred_element_type=jnp.float32) \
        .astype(o_ref.dtype)
    # ---- device-side event record (fine-grained tier) ----------------------
    bytes_read = x.size * x.dtype.itemsize + w.size * w.dtype.itemsize
    bytes_written = o_ref.size * o_ref.dtype.itemsize
    row = 4 * (i * pl.num_programs(1) + j)
    trace_ref[row] = i
    trace_ref[row + 1] = j
    trace_ref[row + 2] = bytes_read
    trace_ref[row + 3] = bytes_written


@functools.partial(jax.jit, static_argnames=("interpret",))
def matmul_traced_pallas(x: jax.Array, w: jax.Array, interpret: bool = False):
    """(M,K)@(K,N) with an on-device access-record trace.

    Returns (out f32[M,N], trace int32[n_grid_steps, 4])."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2 and m % BM == 0 and n % BN == 0, (x.shape, w.shape)
    grid = (m // BM, n // BN)
    out, trace = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, BN), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((BM, BN), lambda i, j: (i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((grid[0] * grid[1] * 4,), jnp.int32),
        ],
        interpret=interpret,
    )(x, w)
    return out, trace.reshape(-1, 4)


def matmul_traced_ref(x: jax.Array, w: jax.Array):
    """Oracle: plain matmul + analytically derived trace."""
    m, k = x.shape
    _, n = w.shape
    gi, gj = m // BM, n // BN
    ij = jnp.stack(jnp.meshgrid(jnp.arange(gi), jnp.arange(gj),
                                indexing="ij"), -1).reshape(-1, 2)
    br = BM * k * x.dtype.itemsize + k * BN * w.dtype.itemsize
    bw = BM * BN * 4
    trace = jnp.concatenate(
        [ij.astype(jnp.int32),
         jnp.full((gi * gj, 1), br, jnp.int32),
         jnp.full((gi * gj, 1), bw, jnp.int32)], axis=1)
    return (x.astype(jnp.float32) @ w.astype(jnp.float32)), trace
