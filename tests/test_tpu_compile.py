"""Compile the main path's kernels for a described TPU v5e (no chip needed).

The TPU compiler ships with jaxlib's libtpu; it compiles for a chip that is
described and not attached, so Mosaic's layout and tiling rules, which
interpret mode never checks, are enforced here at the sizes the chip runs.
The topology is described only inside the fixture: one process at a time
may load libtpu, and a test worker that is not given this file never does.
"""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as C
from repro.kernels.hotness import BLOCK_B, hotness_histogram_pallas
from repro.kernels.instrumented_matmul import matmul_traced_pallas
from repro.kernels.trace_aggregate import (object_histogram_pallas,
                                           trace_aggregate_pallas)
from repro.models import init_params
from repro.models.moe import init_moe, moe_layer
from repro.serve.engine import ServeEngine

N = 2 ** 20                 # trace records per call
K = 1024                    # objects, tiled kernel
#: 2 MiB blocks covering stablelm-1.6b's f32 params, padded to the tile
HOT_BLOCKS = -(-3328 // BLOCK_B) * BLOCK_B
FUSED = dict(k=256, n_blocks=512, n_tbins=8)   # a size can_fuse accepts
EXPERTS = dataclasses.replace(C.get("lfm2-8b-a1b"), n_experts_held=8)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                              # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # such compiles cannot be read back without a chip: keep them out
        # of any persistent cache
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases(s):
    i32 = jnp.int32
    return {
        "object_histogram": (
            lambda a, st, e: object_histogram_pallas(a, st, e),
            (s((N,), i32), s((K,), i32), s((K,), i32))),
        "hotness_histogram": (
            lambda a, t, b: hotness_histogram_pallas(
                a, t, b, n_blocks=HOT_BLOCKS, n_tbins=8, block_shift=12),
            (s((N,), i32), s((N,), i32), s((), i32))),
        "trace_aggregate": (
            lambda a, t, st, e, b, sh: trace_aggregate_pallas(
                a, t, st, e, b, sh, n_blocks=FUSED["n_blocks"],
                n_tbins=FUSED["n_tbins"]),
            (s((N,), i32), s((N,), i32), s((FUSED["k"],), i32),
             s((FUSED["k"],), i32), s((), i32), s((), i32))),
        "matmul_traced": (
            matmul_traced_pallas,
            (s((1024, 1024), jnp.bfloat16), s((1024, 1024), jnp.bfloat16))),
        # lfm2-8b-a1b's expert layer on one chip's 8 of 32 experts, forward
        # and backward: its grouped matmuls lower to Mosaic kernels
        "expert_share": (
            jax.grad(lambda x, w: moe_layer(w, x, EXPERTS)[0].astype(
                jnp.float32).sum(), argnums=(0, 1)),
            (s((2, 1024, EXPERTS.d_model), jnp.bfloat16),
             jax.tree.map(lambda a: s(a.shape, a.dtype), jax.eval_shape(
                 lambda: init_moe(jax.random.PRNGKey(0), EXPERTS,
                                  jnp.float32))))),
    }


@pytest.mark.parametrize("name", ["object_histogram", "hotness_histogram",
                                  "trace_aggregate", "matmul_traced",
                                  "expert_share"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_cases(functools.partial(_spec, sharding=one_chip))[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_serve_decode_step_fits_one_v5e(one_chip):
    """stablelm-1.6b's paged decode at published widths (4 slots, 544
    positions) compiles for one chip and fits its 16 GB."""
    cfg = C.get("stablelm-1.6b")
    params = jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    slots, block, per_seq = 4, 16, 34
    n_blocks = (slots + 2) * per_seq
    pool = (cfg.n_layers, n_blocks, block, cfg.n_kv_heads, cfg.head_dim)
    cache = {"kv": {"pk": _spec(pool, jnp.bfloat16, one_chip),
                    "pv": _spec(pool, jnp.bfloat16, one_chip),
                    "bt": _spec((slots, per_seq), jnp.int32, one_chip),
                    "length": _spec((slots,), jnp.int32, one_chip)}}
    step = jax.jit(functools.partial(ServeEngine._decode_impl, cfg),
                   donate_argnums=(1,))
    compiled = step.lower(params, cache,
                          _spec((slots, 1), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 16e9, total
