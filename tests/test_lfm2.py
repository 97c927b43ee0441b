"""LFM2-MoE (``conv_moe``) against its plain float32 reference
(``bench/configs/lfm2_moe.py``) on seeded random weights at a tiny size:
the forward, the loss and the gradients; the expert share; the biased
sigmoid router; the dropless layer; the causal convolution; and the
operators PASTA's fine-grained eager session sees."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as pasta
import repro.configs as C
from repro.core.events import EventKind
from repro.models import forward, init_params, param_axes
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.moe import moe_layer
from repro.train import OptConfig, make_train_step
from repro.train.optimizer import init_opt_state

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "lfm2_moe_ref", os.path.join(ROOT, "bench", "configs", "lfm2_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _program(cfg: ModelConfig) -> dict:
    """The reference's configuration dict of a program config."""
    return {**dataclasses.asdict(cfg),
            "layer_pattern": list(cfg.layer_pattern)}


@pytest.fixture(scope="module")
def tiny():
    """The registry's reduced LFM2 at float32 compute with a share of its
    experts held, and the reference's weights for it."""
    cfg = dataclasses.replace(C.reduced(C.get("lfm2-8b-a1b")),
                              n_experts_held=5, vocab_size=128)
    c = _program(cfg)
    return cfg, c, REF.init_weights(c, 2**31 + 7)


def _tokens(n=2, s=48, vocab=128, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), (n, s), 0, vocab)


def test_reduced_keeps_the_family():
    cfg = C.reduced(C.get("lfm2-8b-a1b"))
    assert cfg.family == "conv_moe" and cfg.n_layers == 6
    assert set(cfg.layer_pattern) == {"conv", "attn"}
    assert cfg.n_experts == 8 and cfg.n_dense_layers == 2
    held = dataclasses.replace(C.get("lfm2-8b-a1b"), n_experts_held=8)
    assert C.reduced(held).experts_held == 2     # a quarter, as 8 of 32
    for c in (cfg, C.get("lfm2-8b-a1b")):        # with a suffix, without
        shapes = jax.eval_shape(lambda c=c: init_params(
            jax.random.PRNGKey(0), c))
        same = jax.tree.map(lambda ax, leaf: len(ax) == leaf.ndim,
                            param_axes(c), shapes,
                            is_leaf=lambda x: isinstance(x, tuple))
        assert all(jax.tree.leaves(same))


def test_registered_config_counts_the_published_model():
    cfg = C.get("lfm2-8b-a1b")
    assert abs(cfg.n_params - 8.3e9) / 8.3e9 < 0.02
    assert abs(cfg.n_active_params - 1.5e9) / 1.5e9 < 0.05
    assert cfg.layer_period() == (4, 4)
    bench = json.load(open(os.path.join(ROOT, "bench", "configs",
                                        "lfm2-8b-a1b.json")))["program"]
    share = ModelConfig(**bench)
    assert abs(share.n_params - 568.6e6) < 0.1e6
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                share))
    assert sum(x.size for x in jax.tree.leaves(shapes)) \
        == share.n_params + sum(2 * share.head_dim for k in
                                share.layer_pattern if k == "attn") \
        + share.d_model * (2 * share.n_layers + 1)       # norm gains


# logits at float32 compute agree to rounding (measured max 4e-7 of an RMS
# of 0.16); the loss and the per-leaf gradient norms likewise (measured
# relative gaps under 1e-6), so 1e-4 leaves room for the CPU's summation
# order and still fails on any change to the mathematics
@pytest.mark.parametrize("what", ["logits", "loss", "grad_norms"])
def test_program_matches_reference(tiny, what):
    cfg, c, w = tiny
    toks = _tokens()
    with jax.default_matmul_precision("highest"):
        if what == "logits":
            got = forward(w, toks, cfg)[0]
            want = REF.logits(w, toks, c)
            scale = float(jnp.sqrt(jnp.mean(want ** 2)))
            assert float(jnp.abs(got - want).max()) < 1e-4 * scale
            return
        labels = jnp.roll(toks, -1, axis=1)

        def prog_loss(w):
            from repro.models import cross_entropy
            return cross_entropy(forward(w, toks, cfg)[0], labels)[0]
        pl, pg = jax.value_and_grad(prog_loss)(w)
        rl, rg = jax.value_and_grad(
            lambda w: REF.loss(w, toks, labels, c))(w)
    if what == "loss":
        assert abs(float(pl) - float(rl)) < 1e-4 * abs(float(rl))
        return
    pn = np.array([float(jnp.linalg.norm(x)) for x in jax.tree.leaves(pg)])
    rn = np.array([float(jnp.linalg.norm(x)) for x in jax.tree.leaves(rg)])
    assert (np.abs(pn - rn) <= 1e-4 * np.maximum(rn, np.median(rn))).all()


def _layer_cfg(held: int) -> ModelConfig:
    return ModelConfig(name="share", family="conv_moe", n_layers=1,
                       d_model=32, n_experts=32, n_experts_active=4,
                       d_ff_expert=16, router="sigmoid_bias",
                       n_experts_held=held, dtype="float32")


def _layer_weights(cfg, seed=0):
    c = {**_program(cfg), "n_experts_held": 32}
    return REF.make_weights(
        {**c, "n_layers": 1, "layer_pattern": ["conv"], "n_dense_layers": 0,
         "n_heads": 1, "n_kv_heads": 1, "head_dim": 8, "vocab_size": 8},
        jax.random.PRNGKey(seed))["period"][0]["moe"]


def _unstack(m):
    return jax.tree.map(lambda a: a[0], m)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of an expert-parallel group, each holding 8 of 32
    experts: the layer on each (experts relabelled so that its own come
    first, as the layer holds [0, held)) gives its part, and the parts add
    up to the reference's uncut layer."""
    cfg = _layer_cfg(8)
    m = _unstack(_layer_weights(cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    whole = REF.experts(x, m, {**_program(cfg), "n_experts_held": 32}, None)
    total = 0
    with jax.default_matmul_precision("highest"):
        for first in (0, 8, 16, 24):
            order = (jnp.arange(32) + first) % 32     # this chip's first
            share = {"router": m["router"][:, order], "bias": m["bias"][order],
                     **{k: m[k][first:first + 8]
                        for k in ("w_gate", "w_up", "w_down")}}
            y, aux = moe_layer(share, x, cfg)
            total = total + y
            assert aux["expert_counts"].shape == (8,)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-5, rtol=1e-4)


def test_bias_selects_and_scores_weigh():
    """Selection is by s + b, the gates are s renormalized over the chosen:
    on a token whose choice the bias changes, the layer follows the bias
    and weighs by the scores."""
    cfg = dataclasses.replace(_layer_cfg(32), n_experts_active=2)
    m = _unstack(_layer_weights(cfg, seed=4))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 16, 32))
    s = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, m["router"],
                                  precision="highest"))[0]
    best = int(jnp.argmax(s[0]))
    m = dict(m, bias=jnp.zeros(32).at[(best + 1) % 32].set(2.0))
    by_bias = jax.lax.top_k(s + m["bias"], 2)[1]
    by_score = jax.lax.top_k(s, 2)[1]
    assert set(by_bias[0].tolist()) != set(by_score[0].tolist())
    with jax.default_matmul_precision("highest"):
        y, _ = moe_layer(m, x, cfg)
    mlp = lambda e, v: (jax.nn.silu(v @ m["w_gate"][e]) * (          # noqa
        v @ m["w_up"][e])) @ m["w_down"][e]
    for t in range(16):
        chosen = by_bias[t].tolist()
        gates = s[t, jnp.asarray(chosen)] / s[t, jnp.asarray(chosen)].sum()
        want = sum(g * mlp(e, x[0, t]) for g, e in zip(gates, chosen))
        np.testing.assert_allclose(np.asarray(y[0, t]), np.asarray(want),
                                   atol=1e-5, rtol=1e-4)


def test_no_assignment_is_dropped_when_all_go_to_one_expert():
    """Every token to held expert 0: a capacity layer would drop all but
    its capacity; this one computes every assignment."""
    cfg = dataclasses.replace(_layer_cfg(8), n_experts_active=1)
    m = _unstack(_layer_weights(cfg, seed=6))
    m = dict(m, bias=jnp.zeros(32).at[0].set(100.0))
    m = {**m, **{k: m[k][:8] for k in ("w_gate", "w_up", "w_down")}}
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 64, 32))
    with jax.default_matmul_precision("highest"):
        y, aux = moe_layer(m, x, cfg)
    counts = np.asarray(aux["expert_counts"])
    assert counts[0] == 128 and counts[1:].sum() == 0
    assert float(aux["held_share"]) == 1.0
    want = REF.experts(x, m, {**_program(cfg), "n_experts_held": 8}, None)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5,
                               rtol=1e-4)


def test_convolution_is_causal(tiny):
    cfg, _, w = tiny
    p = w["prefix"][0]["conv"]
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 32, cfg.d_model))
    t = 17
    moved = x.at[0, t].add(1.0)
    a, b = L.short_conv(p, x, cfg), L.short_conv(p, moved, cfg)
    assert float(jnp.abs(a[0, :t] - b[0, :t]).max()) == 0.0
    assert float(jnp.abs(a[0, t:t + 3] - b[0, t:t + 3]).min()) > 0.0
    toks = _tokens(1, 32)
    la = forward(w, toks, cfg)[0]
    lb = forward(w, toks.at[0, t].set((toks[0, t] + 1) % 128), cfg)[0]
    assert float(jnp.abs(la[0, :t] - lb[0, :t]).max()) == 0.0


def test_train_step_returns_the_expert_counters(tiny):
    cfg, _, w = tiny
    opt_cfg = OptConfig(lr=1e-3)
    toks = _tokens(4, 16)
    for mb in (1, 2):
        _, _, m = jax.jit(make_train_step(cfg, opt_cfg, microbatches=mb))(
            w, init_opt_state(w, opt_cfg), {"inputs": toks, "labels": toks})
        layers = cfg.n_layers - cfg.n_dense_layers
        assigned = layers * 4 * 16 * cfg.n_experts_active
        counts = np.asarray(m["expert_counts"])
        assert counts.shape == (cfg.experts_held,)
        assert float(m["held_share"]) == pytest.approx(counts.sum()
                                                       / assigned)


def test_eager_session_sees_the_new_operators(tiny):
    cfg, _, w = tiny
    names = set()

    def collect(batch):
        for i in range(len(batch)):
            ev = batch.event(i)
            if ev.kind is EventKind.OPERATOR_START:
                names.add(ev.name)

    handler = pasta.EventHandler()
    handler.subscribe_batch(collect)
    with pasta.Session(tools="kernel_freq", handler=handler,
                       instrument=True, fine=True):
        jax.block_until_ready(forward(w, _tokens(1, 16), cfg)[0])
    assert {"conv.short", "moe.experts", "attn.sdpa", "mlp.glu",
            "layer5"} <= names


def test_serving_is_refused():
    from repro.models import init_cache
    with pytest.raises(NotImplementedError):
        init_cache(C.reduced(C.get("lfm2-8b-a1b")), 1, 16)
