"""repro.dist.sharding rule-table tests — single device, no subprocess.

The 8-device behaviour (actual resharded execution) lives in test_dist.py;
these tests pin the *resolution* semantics: every logical axis name the
models emit resolves, rank mismatches are tolerated hints, divisibility and
duplicate-axis filtering work, and set_mesh/get_rules override semantics
match what trainer.tree_shardings relies on.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import repro.configs as C
from repro.dist.collectives import dequantize_int8, quantize_int8
from repro.dist.sharding import (DEFAULT_RULES, ShardingRules, get_mesh,
                                 get_rules, logical, mesh_axis_size,
                                 set_mesh, shard)
from repro.launch.mesh import make_mesh
from repro.models import cache_axes, param_axes

# the activation-annotation names used by models.{layers,lm,moe,mamba2}
ACTIVATION_AXES = [
    "batch", "seq", "seq_sp", "heads", "kv_heads", "head_dim", "embed",
    "ff", "vocab", "experts", "experts_ep", "expert_ff", "p_ssm_inner",
    "ssm_heads",
]

# duck-typed stand-in for a multi-device mesh (logical() only reads
# .shape/.axis_names, so rule resolution is testable on one CPU device)
FAKE_MESH = types.SimpleNamespace(shape={"data": 2, "model": 4},
                                  axis_names=("data", "model"))
FAKE_POD_MESH = types.SimpleNamespace(
    shape={"pod": 2, "data": 2, "model": 2},
    axis_names=("pod", "data", "model"))


@pytest.fixture(autouse=True)
def _reset_global_mesh():
    yield
    set_mesh(None)


def _axis_names(tree):
    is_ax = lambda x: isinstance(x, tuple)  # noqa: E731
    names = set()
    for leaf in jax.tree.leaves(tree, is_leaf=is_ax):
        names.update(a for a in leaf if a is not None)
    return names


def test_default_rules_cover_every_model_axis_name():
    names = set(ACTIVATION_AXES)
    import dataclasses
    for arch in C.ASSIGNED:
        cfg = C.reduced(C.get(arch))
        names |= _axis_names(param_axes(cfg))
        names |= _axis_names(cache_axes(cfg))
        if cfg.n_experts:                     # both MoE parallelism modes
            cfg_ep = dataclasses.replace(cfg, moe_parallelism="ep")
            names |= _axis_names(param_axes(cfg_ep))
    missing = {n for n in names if n not in DEFAULT_RULES}
    assert not missing, f"DEFAULT_RULES missing {sorted(missing)}"
    for n in sorted(names):                   # and each resolves standalone
        logical(n, mesh=FAKE_POD_MESH)


def test_logical_resolves_named_axes():
    assert logical("p_vocab", "p_embed", mesh=FAKE_MESH) == P("model", "data")
    assert logical("p_embed", "p_ff", mesh=FAKE_MESH) == P("data", "model")
    assert logical("seq_sp", mesh=FAKE_MESH) == P("model")
    assert logical("p_ssm_inner", mesh=FAKE_MESH) == P("model")
    assert logical("expert_ff", mesh=FAKE_MESH) == P("model")
    assert logical(None, "seq", "embed", mesh=FAKE_MESH) == P(None, None, None)


def test_logical_batch_composes_pod_and_data():
    assert logical("batch", mesh=FAKE_POD_MESH) == P(("pod", "data"))
    # pod axis absent -> silently drops to data only
    assert logical("batch", mesh=FAKE_MESH) == P("data")


def test_logical_drops_duplicate_physical_axes():
    # TP-MoE expert weights: p_experts claims "data" first, p_embed yields
    spec = logical("p_experts", "p_embed", "p_expert_ff", mesh=FAKE_MESH)
    assert spec == P("data", None, "model")


def test_logical_divisibility_filter():
    # 1 KV head can't shard 4 ways -> dropped; the rest shard normally
    spec = logical("p_embed", "p_kv_heads", None, dims=(64, 1, 16),
                   mesh=FAKE_MESH)
    assert spec == P("data", None, None)
    spec = logical("p_embed", "p_heads", None, dims=(64, 4, 16),
                   mesh=FAKE_MESH)
    assert spec == P("data", "model", None)


def test_logical_rank_mismatch_raises_with_dims():
    with pytest.raises(ValueError):
        logical("p_embed", "p_ff", dims=(64,), mesh=FAKE_MESH)


def test_logical_unknown_axis_raises():
    with pytest.raises(KeyError):
        logical("p_nonexistent", mesh=FAKE_MESH)


def test_shard_is_noop_without_mesh():
    set_mesh(None)
    x = jnp.ones((4, 8))
    assert shard(x, "batch", "embed") is x


def test_shard_rank_mismatch_is_tolerated_hint():
    mesh = make_mesh((1, 1), ("data", "model"))
    set_mesh(mesh)
    x = jnp.ones((4, 8))
    assert shard(x, "batch") is x             # rank 1 hint on rank-2 tensor
    y = shard(x, "batch", "embed")            # matching rank constrains
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_set_mesh_rules_override_and_reset():
    mesh = make_mesh((1, 1), ("data", "model"))
    set_mesh(mesh, ShardingRules({**DEFAULT_RULES, "p_embed": None}))
    assert get_mesh() is mesh
    assert get_rules()["p_embed"] is None
    assert logical("p_embed", mesh=FAKE_MESH) == P(None)
    # trainer.tree_shardings keeps custom rules alive explicitly:
    set_mesh(mesh, get_rules())
    assert get_rules()["p_embed"] is None
    # plain set_mesh resets to the defaults (dryrun.run_cell relies on it)
    set_mesh(mesh)
    assert get_rules() == DEFAULT_RULES
    assert logical("p_embed", mesh=FAKE_MESH) == P("data")


def test_mesh_axis_size_defaults_to_one():
    set_mesh(None)
    assert mesh_axis_size("data") == 1
    mesh = make_mesh((1, 1), ("data", "model"))
    set_mesh(mesh)
    assert mesh_axis_size("data") == 1
    assert mesh_axis_size("pod") == 1         # absent axis


def test_quantize_int8_roundtrip_bounds(rng):
    x = jnp.asarray(rng.standard_normal((16, 64)) * 3.0, jnp.float32)
    q, s = quantize_int8(x)
    assert q.dtype == jnp.int8 and s.shape == (16, 1)
    back = dequantize_int8(q, s)
    amax = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True)
    # per-element error bounded by half a quantization step (slack for
    # rounding), and <1% relative error overall
    assert np.all(np.abs(np.asarray(back - x)) <= amax / 126.0 + 1e-12)
    rel = float(jnp.linalg.norm(back - x) / jnp.linalg.norm(x))
    assert rel < 0.01, rel


def test_quantize_int8_preserves_shapes_and_zeros(rng):
    x = jnp.zeros((4, 4), jnp.float32)
    q, s = quantize_int8(x)
    np.testing.assert_array_equal(np.asarray(dequantize_int8(q, s)), 0.0)
    x3 = jnp.asarray(rng.standard_normal((2, 3, 5)), jnp.float32)
    q3, s3 = quantize_int8(x3)
    assert q3.shape == x3.shape and s3.shape == (2, 3, 1)
