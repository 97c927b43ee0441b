"""Per-arch smoke tests (reduced configs) + model-math correctness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import (init_params, forward, init_cache, cross_entropy,
                          param_axes)
from repro.models.mamba2 import ssd_chunked, ssd_ref
from repro.models.layers import _sdpa_dense, _sdpa_blocked, _group
from repro.train import OptConfig, make_train_step
from repro.train.optimizer import init_opt_state

ARCHS = C.ASSIGNED


def _inputs(cfg, key, b=2, s=64):
    if cfg.frontend == "embed":
        x = jax.random.normal(key, (b, s, cfg.d_model), jnp.float32)
    else:
        x = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    labels = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    return x, labels


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_forward_and_train_step(arch):
    """One forward + one full train step on CPU: output shapes + no NaNs."""
    cfg = C.reduced(C.get(arch))
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    x, labels = _inputs(cfg, key)
    logits, _ = jax.jit(lambda p, x: forward(p, x, cfg))(params, x)
    assert logits.shape == (2, 64, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())

    opt_cfg = OptConfig(lr=1e-3, moment_dtype=cfg.opt_moment_dtype)
    step = make_train_step(cfg, opt_cfg, microbatches=2)
    opt = init_opt_state(params, opt_cfg)
    batch = {"inputs": x, "labels": labels}
    p2, o2, metrics = jax.jit(step)(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0
    # params actually moved
    moved = any(float(jnp.abs(a - b).max()) > 0
                for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert moved


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_param_axes_cover_tree(arch):
    """Sharding-axes tree must mirror the param tree exactly."""
    cfg = C.reduced(C.get(arch))
    params = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    axes = param_axes(cfg)
    is_ax = lambda x: isinstance(x, tuple)  # noqa: E731
    matched = jax.tree.map(lambda ax, leaf: len(ax) == leaf.ndim, axes,
                           params, is_leaf=is_ax)
    assert all(jax.tree.leaves(matched))


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "glm4-9b", "zamba2-7b",
                                  "mamba2-2.7b", "dbrx-132b"])
def test_prefill_decode_consistency(arch):
    """Greedy decode after prefill == teacher-forced forward logits."""
    cfg = C.reduced(C.get(arch))
    key = jax.random.PRNGKey(1)
    params = init_params(key, cfg)
    toks = jax.random.randint(key, (2, 32), 0, cfg.vocab_size)
    full_logits, _ = forward(params, toks, cfg)
    # prefill on the first 24 tokens, then 8 decode steps
    _, cache = forward(params, toks[:, :24], cfg, return_cache=True,
                       logits_mode="last")
    # grow cache to 32 slots
    from repro.serve.engine import _pad_cache_to
    cache = _pad_cache_to(cache, cfg, 32)
    errs = []
    for t in range(24, 32):
        lg, cache = forward(params, toks[:, t:t + 1], cfg, cache=cache,
                            logits_mode="last")
        errs.append(float(jnp.abs(lg[:, 0] - full_logits[:, t]).max()))
    assert max(errs) < 2e-2, errs


def test_ssd_chunked_matches_sequential(rng):
    b, s, h, p, n = 2, 128, 4, 8, 16
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.standard_normal((b, s, h))) * 0.4,
                     jnp.float32)
    A = jnp.asarray(-np.abs(rng.standard_normal(h)) - 0.05, jnp.float32)
    B = jnp.asarray(rng.standard_normal((b, s, h, n)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((b, s, h, n)), jnp.float32)
    y_ref, st_ref = ssd_ref(x, dt, A, B, Cm)
    for chunk in (16, 32, 128):
        y, st = ssd_chunked(x, dt, A, B, Cm, chunk)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=2e-4, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                                   atol=2e-4, rtol=1e-3)


def test_blocked_attention_matches_dense(rng):
    b, s, hkv, g, d = 2, 256, 2, 2, 16
    q = jnp.asarray(rng.standard_normal((b, s, hkv * g, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    qg = _group(q, hkv)
    dense = _sdpa_dense(qg, k, v, causal=True)
    blocked = _sdpa_blocked(qg, k, v, causal=True, chunk=64)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(blocked),
                               atol=2e-5, rtol=1e-4)


def test_moe_capacity_drops_are_bounded(rng):
    """The expert layer has no capacity: every assignment is computed."""
    cfg = C.reduced(C.get("dbrx-132b"))
    from repro.models.moe import moe_layer, init_moe
    p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 64, cfg.d_model)), jnp.float32)
    y, aux = moe_layer(p, x, cfg)
    assert y.shape == x.shape
    assert int(aux["expert_counts"].sum()) == 2 * 64 * cfg.n_experts_active
    assert float(aux["held_share"]) == 1.0


def test_embedding_gradient_sums_in_float32():
    """A token used 1,756 times in bf16 compute: its embedding row's gradient
    matches the float32 computation's to bf16 rounding of the terms (0.7%).
    Summed in a bf16 row, the small terms round away (46% off, the norm 56%
    of the float32 one)."""
    import dataclasses
    cfg = dataclasses.replace(C.reduced(C.get("qwen3-32b")),
                              tie_embeddings=False, dtype="bfloat16")
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.full((2, 1024), 3, jnp.int32).at[:, ::7].set(5)

    def row(cfg):
        g = jax.grad(lambda p: cross_entropy(
            forward(p, toks, cfg)[0], jnp.roll(toks, -1, 1))[0])(params)
        return g["embed"][3]
    g16 = row(cfg)
    g32 = row(dataclasses.replace(cfg, dtype="float32"))
    assert float(jnp.linalg.norm(g16 - g32) / jnp.linalg.norm(g32)) < 0.03


def test_cross_entropy_matches_manual(rng):
    logits = jnp.asarray(rng.standard_normal((2, 8, 32)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 32, (2, 8)))
    loss, parts = cross_entropy(logits, labels, z_loss=0.0)
    p = jax.nn.log_softmax(logits, -1)
    want = -np.mean([p[i, j, labels[i, j]] for i in range(2)
                     for j in range(8)])
    np.testing.assert_allclose(float(loss), want, rtol=1e-5)


def test_full_config_param_counts():
    """Full configs must match published parameter counts (±5%)."""
    expected = {
        "mamba2-2.7b": 2.7e9, "stablelm-1.6b": 1.6e9, "glm4-9b": 9.4e9,
        "gemma-7b": 8.5e9, "qwen3-32b": 32.8e9, "zamba2-7b": 7.0e9,
        "qwen2-vl-72b": 72.7e9, "dbrx-132b": 132e9,
        "kimi-k2-1t-a32b": 1.04e12, "musicgen-large": 3.3e9,
    }
    for arch, want in expected.items():
        got = C.get(arch).n_params
        assert abs(got - want) / want < 0.06, (arch, got, want)
    assert abs(C.get("kimi-k2-1t-a32b").n_active_params - 33e9) / 33e9 < 0.1
    assert abs(C.get("dbrx-132b").n_active_params - 36e9) / 36e9 < 0.05
