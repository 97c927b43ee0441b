"""Unified decoder LM covering all assigned families.

  * dense / vlm / audio — GQA attention + GLU MLP blocks (vlm/audio differ
    only in the stubbed modality frontend and M-RoPE);
  * moe   — attention + dropless MoE blocks (grouped matmuls over the
    experts held);
  * ssm   — Mamba2 (SSD) blocks, attention-free;
  * hybrid — Mamba2 backbone with ONE weight-shared transformer block applied
    after every ``shared_attn_every`` SSM layers (Zamba2): layers are scanned
    in (group, layer-in-group) shape so each shared-attention application has
    its own KV-cache slot while the block weights stay shared;
  * conv_moe — a per-layer pattern of gated short convolutions and GQA
    attention (LFM2): ``n_dense_layers`` leading layers with the dense MLP
    (``prefix``), then whole repeats of the pattern's period scanned with
    each position's weights stacked (``period``), then any layers left over
    (``suffix``); every layer after the dense ones takes the experts.
    Training only: the convolution's state is not cached for serving.

All layer stacks run under ``jax.lax.scan`` (compact HLO, fast compiles at
512 devices) with configurable remat.  Instrumented eager execution for the
PASTA tools goes through :mod:`repro.core.instrument` hooks, which are
no-ops under tracing.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core.instrument import op_hook
from repro.dist.sharding import shard
from .config import ModelConfig
from . import layers as L
from . import mamba2 as M
from . import moe as MOE


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stack_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def init_params(key, cfg: ModelConfig) -> dict:
    dt = _pdtype(cfg)
    keys = jax.random.split(key, 8)
    p: dict = {}
    if cfg.frontend == "none":
        p["embed"] = jax.random.normal(
            keys[0], (cfg.vocab_size, cfg.d_model), dt) * 0.02
    if not cfg.tie_embeddings and cfg.vocab_size:
        p["lm_head"] = jax.random.normal(
            keys[1], (cfg.d_model, cfg.vocab_size), dt) \
            / math.sqrt(cfg.d_model)
    p["final_norm"] = jnp.zeros((cfg.d_model,), dt)

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        def one(k):
            k1, k2, k3, k4 = jax.random.split(k, 4)
            blk = {"ln1": jnp.zeros((cfg.d_model,), dt),
                   "ln2": jnp.zeros((cfg.d_model,), dt),
                   "attn": L.init_attention(k1, cfg, dt)}
            if cfg.family == "moe":
                blk["moe"] = MOE.init_moe(k2, cfg, dt)
            else:
                blk["mlp"] = L.init_mlp(k3, cfg, dt)
            return blk
        p["layers"] = _stack_init(one, keys[2], cfg.n_layers)
    elif cfg.family == "ssm":
        def one(k):
            return {"ln": jnp.zeros((cfg.d_model,), dt),
                    "mamba": M.init_mamba2(k, cfg, dt)}
        p["layers"] = _stack_init(one, keys[2], cfg.n_layers)
    elif cfg.family == "conv_moe":
        p.update(_init_pattern(keys[2], cfg, dt))
    elif cfg.family == "hybrid":
        every = cfg.shared_attn_every
        n_groups = cfg.n_layers // every
        tail = cfg.n_layers - n_groups * every

        def one(k):
            return {"ln": jnp.zeros((cfg.d_model,), dt),
                    "mamba": M.init_mamba2(k, cfg, dt)}
        grouped = _stack_init(one, keys[2], n_groups * every)
        p["groups"] = jax.tree.map(
            lambda a: a.reshape(n_groups, every, *a.shape[1:]), grouped)
        if tail:
            p["tail"] = _stack_init(one, keys[3], tail)
        p["shared"] = {
            "ln1": jnp.zeros((cfg.d_model,), dt),
            "ln2": jnp.zeros((cfg.d_model,), dt),
            "attn": L.init_attention(keys[4], cfg, dt),
            "mlp": L.init_mlp(keys[5], cfg, dt),
        }
    else:
        raise ValueError(cfg.family)
    return p


def pattern_layout(cfg: ModelConfig):
    """(prefix, period, n_periods, suffix) of a conv_moe config: layer
    indices of the unstacked leading and trailing layers, and the period's
    operator kinds."""
    n_dense, pat = cfg.n_dense_layers, cfg.layer_pattern
    per, n_periods = cfg.layer_period()
    first_suffix = n_dense + per * n_periods
    return (list(range(n_dense)), tuple(pat[n_dense:n_dense + per]),
            n_periods, list(range(first_suffix, cfg.n_layers)))


def _pattern_layer(key, cfg: ModelConfig, dt, kind: str, dense: bool):
    k1, k2 = jax.random.split(key)
    blk = {"ln1": jnp.zeros((cfg.d_model,), dt),
           "ln2": jnp.zeros((cfg.d_model,), dt)}
    if kind == "attn":
        blk["attn"] = L.init_attention(k1, cfg, dt)
    else:
        blk["conv"] = L.init_short_conv(k1, cfg, dt)
    if dense:
        blk["mlp"] = L.init_mlp(k2, cfg, dt)
    else:
        blk["moe"] = MOE.init_moe(k2, cfg, dt)
    return blk


def _init_pattern(key, cfg: ModelConfig, dt) -> dict:
    prefix, period, n_periods, suffix = pattern_layout(cfg)
    keys = jax.random.split(key, cfg.n_layers)
    pat, n_dense = cfg.layer_pattern, cfg.n_dense_layers
    out: dict = {}
    if prefix:
        out["prefix"] = [_pattern_layer(keys[i], cfg, dt, pat[i], True)
                         for i in prefix]
    if n_periods:
        out["period"] = [
            _stack_init(lambda k, kind=kind: _pattern_layer(
                k, cfg, dt, kind, False), keys[n_dense + j], n_periods)
            for j, kind in enumerate(period)]
    if suffix:
        out["suffix"] = [_pattern_layer(keys[i], cfg, dt, pat[i], False)
                         for i in suffix]
    return out


def _pattern_axes(cfg: ModelConfig) -> dict:
    prefix, period, n_periods, suffix = pattern_layout(cfg)

    def one(kind, dense):
        blk = {"ln1": (None,), "ln2": (None,)}
        if kind == "attn":
            blk["attn"] = {k: v for k, v in L.attention_param_axes().items()
                           if cfg.qk_norm or k not in ("q_norm", "k_norm")}
        else:
            blk["conv"] = L.short_conv_param_axes()
        blk["mlp" if dense else "moe"] = (L.mlp_param_axes() if dense
                                          else MOE.moe_param_axes(cfg))
        return blk
    stack = lambda d: jax.tree.map(                         # noqa: E731
        lambda ax: ("p_layers",) + tuple(ax), d,
        is_leaf=lambda x: isinstance(x, tuple))
    pat = cfg.layer_pattern
    out: dict = {}
    if prefix:
        out["prefix"] = [one(pat[i], True) for i in prefix]
    if n_periods:
        out["period"] = [stack(one(kind, False)) for kind in period]
    if suffix:
        out["suffix"] = [one(pat[i], False) for i in suffix]
    return out


def param_axes(cfg: ModelConfig) -> dict:
    """Logical sharding axes mirroring the param tree (leading 'p_layers'
    prepended for stacked leaves)."""
    def stack(d, extra=1):
        return jax.tree.map(lambda ax: ("p_layers",) * extra + tuple(ax), d,
                            is_leaf=lambda x: isinstance(x, tuple))

    axes: dict = {"final_norm": (None,)}
    if cfg.frontend == "none":
        axes["embed"] = ("p_vocab", "p_embed")
    if not cfg.tie_embeddings and cfg.vocab_size:
        axes["lm_head"] = ("p_embed", "p_vocab")
    blk_attn = {"ln1": (None,), "ln2": (None,),
                "attn": L.attention_param_axes()}
    if cfg.qk_norm is False:
        blk_attn["attn"] = {k: v for k, v in blk_attn["attn"].items()
                            if k not in ("q_norm", "k_norm")}
    if cfg.family in ("dense", "vlm", "audio"):
        axes["layers"] = stack({**blk_attn, "mlp": L.mlp_param_axes()})
    elif cfg.family == "moe":
        axes["layers"] = stack({**blk_attn, "moe": MOE.moe_param_axes(cfg)})
    elif cfg.family == "ssm":
        axes["layers"] = stack({"ln": (None,), "mamba": M.mamba2_param_axes()})
    elif cfg.family == "conv_moe":
        axes.update(_pattern_axes(cfg))
    elif cfg.family == "hybrid":
        axes["groups"] = stack({"ln": (None,),
                                "mamba": M.mamba2_param_axes()}, extra=2)
        every = cfg.shared_attn_every
        if cfg.n_layers % every:
            axes["tail"] = stack({"ln": (None,),
                                  "mamba": M.mamba2_param_axes()})
        axes["shared"] = {**blk_attn, "mlp": L.mlp_param_axes()}
    return axes


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def _instrumented_eager(x) -> bool:
    """True when a PASTA eager instrumenter is active and we are NOT under
    tracing: layer stacks then run as Python loops instead of lax.scan (scan
    always traces its body, which would silence the operator hooks)."""
    from repro.core import instrument
    return instrument.ACTIVE is not None \
        and not isinstance(x, jax.core.Tracer)


def _tree_at(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _attn_block(blk, h, cfg, positions, cache=None):
    a, new_cache = L.attention(blk["attn"], L.rmsnorm(h, blk["ln1"],
                                                      cfg.rmsnorm_eps),
                               cfg, positions, cache)
    h = h + a
    if "moe" in blk:
        y, aux = MOE.moe_layer(blk["moe"], L.rmsnorm(h, blk["ln2"],
                                                     cfg.rmsnorm_eps), cfg)
    else:
        y = L.mlp(blk["mlp"], L.rmsnorm(h, blk["ln2"], cfg.rmsnorm_eps), cfg)
        aux = {}
    return h + y, new_cache, aux


def _pattern_block(blk, h, cfg, positions):
    """One conv_moe layer; its kind is read from its weights."""
    x = L.rmsnorm(h, blk["ln1"], cfg.rmsnorm_eps)
    if "attn" in blk:
        a, _ = L.attention(blk["attn"], x, cfg, positions)
    else:
        a = L.short_conv(blk["conv"], x, cfg)
    h = h + a
    x = L.rmsnorm(h, blk["ln2"], cfg.rmsnorm_eps)
    if "moe" in blk:
        y, aux = MOE.moe_layer(blk["moe"], x, cfg)
    else:
        y, aux = L.mlp(blk["mlp"], x, cfg), {}
    return h + y, aux


def _mamba_block(blk, h, cfg, state=None):
    y, new_state = M.mamba2_layer(blk["mamba"],
                                  L.rmsnorm(h, blk["ln"], cfg.rmsnorm_eps),
                                  cfg, state)
    return h + y, new_state


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    """Decode caches, zero-initialized (filled by prefill)."""
    if cfg.family == "conv_moe":
        raise NotImplementedError("conv_moe has no serving cache")
    dt = _dtype(cfg)

    def kv(n):
        out = {
            "k": jnp.zeros((n, batch, max_seq, cfg.n_kv_heads,
                            cfg.head_dim), dt),
            "v": jnp.zeros((n, batch, max_seq, cfg.n_kv_heads,
                            cfg.head_dim), dt),
            "length": jnp.zeros((batch,), jnp.int32),
        }
        if cfg.kv_two_tier and cfg.family != "hybrid":
            out["rk"] = jnp.zeros((n, batch, cfg.kv_recent_len,
                                   cfg.n_kv_heads, cfg.head_dim), dt)
            out["rv"] = jnp.zeros_like(out["rk"])
            out["main_len"] = jnp.zeros((batch,), jnp.int32)
        return out
    ssm = lambda *lead: {                                  # noqa: E731
        "conv_x": jnp.zeros((*lead, batch, cfg.ssm_conv_width - 1,
                             cfg.d_inner), dt),
        "conv_B": jnp.zeros((*lead, batch, cfg.ssm_conv_width - 1,
                             cfg.ssm_groups * cfg.ssm_state), dt),
        "conv_C": jnp.zeros((*lead, batch, cfg.ssm_conv_width - 1,
                             cfg.ssm_groups * cfg.ssm_state), dt),
        "ssm": jnp.zeros((*lead, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                          cfg.ssm_state), jnp.float32),
    }
    if cfg.family in ("dense", "vlm", "audio", "moe"):
        return {"kv": kv(cfg.n_layers)}
    if cfg.family == "ssm":
        return {"ssm": ssm(cfg.n_layers),
                "length": jnp.zeros((batch,), jnp.int32)}
    every = cfg.shared_attn_every
    n_groups = cfg.n_layers // every
    tail = cfg.n_layers - n_groups * every
    out = {"kv": kv(n_groups), "ssm_groups": ssm(n_groups, every)}
    if tail:
        out["ssm_tail"] = ssm(tail)
    return out


def cache_axes(cfg: ModelConfig) -> dict:
    """Logical sharding for caches: KV sequence shards over `model` (SP
    flash-decode), batch over data axes; SSM heads over `model`."""
    if cfg.family == "conv_moe":
        raise NotImplementedError("conv_moe has no serving cache")
    kv = {"k": (None, "batch", "seq_sp", None, None),
          "v": (None, "batch", "seq_sp", None, None),
          "length": (None,)}
    if cfg.kv_two_tier and cfg.family != "hybrid":
        kv.update({"rk": (None, "batch", None, None, None),
                   "rv": (None, "batch", None, None, None),
                   "main_len": (None,)})
    ssm = lambda n: {                                      # noqa: E731
        "conv_x": (None,) * n + ("batch", None, "p_ssm_inner"),
        "conv_B": (None,) * n + ("batch", None, None),
        "conv_C": (None,) * n + ("batch", None, None),
        "ssm": (None,) * n + ("batch", "ssm_heads", None, None),
    }
    if cfg.family in ("dense", "vlm", "audio", "moe"):
        return {"kv": kv}
    if cfg.family == "ssm":
        return {"ssm": ssm(1), "length": (None,)}
    out = {"kv": kv, "ssm_groups": ssm(2)}
    every = cfg.shared_attn_every
    if cfg.n_layers % every:
        out["ssm_tail"] = ssm(1)
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def forward(params: dict, inputs: jax.Array, cfg: ModelConfig,
            cache: dict | None = None, positions: jax.Array | None = None,
            return_cache: bool = False, logits_mode: str = "all",
            logits_index: jax.Array | None = None):
    """inputs: (B,S) int tokens or (B,S,d) embeddings (frontend stub).
    Returns (logits, new_cache_or_None).  ``return_cache=True`` without an
    input cache collects the prefill KV/SSM caches.  ``logits_mode="index"``
    runs the lm_head on per-row positions gathered from ``logits_index`` —
    (B,) for ragged right-padded serving prefill (each row's last real token
    sits at a different offset), or (B, P) to read logits at several
    positions per row (speculative verify reads every draft position)."""
    logits, new_cache, _aux = _forward(params, inputs, cfg, cache, positions,
                                       return_cache, logits_mode,
                                       logits_index)
    return logits, new_cache


def forward_with_aux(params: dict, inputs: jax.Array, cfg: ModelConfig):
    """The training forward: (logits, aux), where ``aux`` holds the expert
    layers' counters over the whole model (:func:`_reduce_aux`; empty for a
    model without experts)."""
    logits, _, aux = _forward(params, inputs, cfg)
    return logits, aux


def _reduce_aux(per_layer: list) -> dict:
    """Expert counters of every expert layer, each with a leading layer
    axis, to the model's: assignments per held expert summed over the
    layers, and the held share of all assignments."""
    per_layer = [a for a in per_layer if a]
    if not per_layer:
        return {}
    return {"expert_counts": sum(a["expert_counts"].sum(0)
                                 for a in per_layer),
            "held_share": jnp.concatenate(
                [a["held_share"] for a in per_layer]).mean()}


def _one_layer(aux: dict) -> dict:
    return jax.tree.map(lambda a: a[None], aux)


def _forward(params, inputs, cfg, cache=None, positions=None,
             return_cache=False, logits_mode="all", logits_index=None):
    dt = _dtype(cfg)
    if inputs.ndim == 2 and cfg.frontend == "none":
        # rows gathered from the master table, then cast: the gradient
        # sums a token's uses into its row in float32 (a bf16 scatter-add
        # loses the small terms of a frequent token's many uses)
        h = params["embed"][inputs].astype(dt)
        op_hook("embed.lookup", (inputs, params["embed"]), (h,))
    else:
        h = inputs.astype(dt)
    b, s = h.shape[0], h.shape[1]
    h = shard(h, "batch", "seq", "embed")
    if positions is None:
        if cache is not None:
            base = _cache_length(cache, cfg)
            positions = base[:, None] + jnp.arange(s)[None, :]
        else:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    aux: dict = {}
    if cfg.family in ("dense", "vlm", "audio", "moe"):
        h, new_cache, aux = _run_stacked_attn(params, h, cfg, positions,
                                              cache, return_cache)
    elif cfg.family == "conv_moe":
        if cache is not None or return_cache:
            raise NotImplementedError(
                "conv_moe runs training forwards only: the short "
                "convolution's state has no serving cache")
        h, aux = _run_pattern(params, h, cfg, positions)
        new_cache = None
    elif cfg.family == "ssm":
        h, new_cache = _run_stacked_ssm(params, h, cfg, cache, return_cache)
    else:
        h, new_cache = _run_hybrid(params, h, cfg, positions, cache,
                                   return_cache)

    h = L.rmsnorm(h, params["final_norm"], cfg.rmsnorm_eps)
    if logits_mode == "last":
        h = h[:, -1:, :]          # serving: lm_head on the new token only
    elif logits_mode == "index":
        if logits_index.ndim == 2:
            h = jnp.take_along_axis(h, logits_index[..., None], axis=1)
        else:
            h = h[jnp.arange(h.shape[0])[:, None], logits_index[:, None]]
    if cfg.tie_embeddings and "embed" in params:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"].astype(dt))
    elif "lm_head" in params:
        logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(dt))
    else:
        logits = h
    logits = shard(logits, "batch", "seq", "vocab")
    op_hook("lm_head", (h,), (logits,))
    return logits, new_cache, aux


def _cache_length(cache: dict, cfg: ModelConfig):
    if "kv" in cache:
        return cache["kv"]["length"]
    # pure ssm: track via a length entry added by the serve engine
    return cache.get("length", jnp.zeros((1,), jnp.int32))


def _run_stacked_attn(params, h, cfg, positions, cache, return_cache=False):
    layers = params["layers"]
    if cache is None and not return_cache and _instrumented_eager(h):
        n = jax.tree.leaves(layers)[0].shape[0]
        auxes = []
        for i in range(n):
            op_hook(f"layer{i}", (h,), ())
            h, _kv, aux = _attn_block(_tree_at(layers, i), h, cfg,
                                      positions, None)
            auxes.append(_one_layer(aux))
        return h, None, _reduce_aux(auxes)

    def body(carry, xs):
        hh = carry
        if cache is None:
            blk = xs
            hh2, kv, aux = _attn_block(blk, hh, cfg, positions, None)
            ys = {"k": kv["k"], "v": kv["v"]} if return_cache else aux
            return hh2, ys
        blk, kv_slice = xs
        hh2, new_kv, _aux = _attn_block(blk, hh, cfg, positions, kv_slice)
        if "rk" in new_kv:
            # two-tier: the frozen main cache is NOT re-emitted (no rewrite)
            return hh2, {"rk": new_kv["rk"], "rv": new_kv["rv"]}
        if "pk" in new_kv:
            # paged: only the block pool is per-layer state
            return hh2, {"pk": new_kv["pk"], "pv": new_kv["pv"]}
        return hh2, {"k": new_kv["k"], "v": new_kv["v"]}

    body = _remat(cfg, body)
    if cache is None:
        h, ys = jax.lax.scan(body, h, layers)
        if not return_cache:
            return h, None, _reduce_aux([ys])
        length = jnp.full((h.shape[0],), h.shape[1], jnp.int32)
        return h, {"kv": {"k": ys["k"], "v": ys["v"],
                          "length": length}}, {}
    kv = cache["kv"]
    if "pk" in kv:
        n_layers = kv["pk"].shape[0]
    else:
        n_layers = kv["k"].shape[0]
    bcast = lambda a: jnp.broadcast_to(a, (n_layers, *a.shape))  # noqa: E731
    if "pk" in kv:
        # paged: the block pool carries the layer axis; block tables and
        # lengths are shared across layers (broadcast like lengths below)
        per_layer = {"pk": kv["pk"], "pv": kv["pv"], "bt": bcast(kv["bt"]),
                     "length": bcast(kv["length"])}
        h, new_kv = jax.lax.scan(body, h, (layers, per_layer))
        return h, {"kv": {"pk": new_kv["pk"], "pv": new_kv["pv"],
                          "bt": kv["bt"],
                          "length": kv["length"] + h.shape[1]}}, {}
    per_layer = {"k": kv["k"], "v": kv["v"], "length": bcast(kv["length"])}
    if "rk" in kv:
        per_layer.update({"rk": kv["rk"], "rv": kv["rv"],
                          "main_len": bcast(kv["main_len"])})
    h, new_kv = jax.lax.scan(body, h, (layers, per_layer))
    if "rk" in kv:
        new_cache = {"kv": {"k": kv["k"], "v": kv["v"],
                            "rk": new_kv["rk"], "rv": new_kv["rv"],
                            "main_len": kv["main_len"],
                            "length": kv["length"] + h.shape[1]}}
    else:
        new_cache = {"kv": {"k": new_kv["k"], "v": new_kv["v"],
                            "length": kv["length"] + h.shape[1]}}
    return h, new_cache, {}


def _run_pattern(params, h, cfg, positions):
    """conv_moe layers: the unstacked prefix, a scan over whole periods (each
    layer checkpointed under ``remat``), the unstacked suffix.  Returns (h,
    the expert counters over every expert layer)."""
    layer = _remat(cfg, lambda blk, hh: _pattern_block(blk, hh, cfg,
                                                       positions))
    if _instrumented_eager(h):
        auxes = []
        for i, blk in enumerate(_pattern_layers(params)):
            op_hook(f"layer{i}", (h,), ())
            h, aux = _pattern_block(blk, h, cfg, positions)
            auxes.append(_one_layer(aux))
        return h, _reduce_aux(auxes)

    auxes = []
    for blk in params.get("prefix", []):
        h, aux = layer(blk, h)
        auxes.append(_one_layer(aux))
    if "period" in params:
        def body(hh, blks):
            ys = []
            for blk in blks:
                hh, aux = layer(blk, hh)
                ys.append(aux)
            return hh, ys
        h, ys = jax.lax.scan(body, h, params["period"])
        auxes += ys
    for blk in params.get("suffix", []):
        h, aux = layer(blk, h)
        auxes.append(_one_layer(aux))
    return h, _reduce_aux(auxes)


def _pattern_layers(params) -> list:
    """Every conv_moe layer's weights, in order."""
    out = list(params.get("prefix", []))
    if "period" in params:
        n = jax.tree.leaves(params["period"])[0].shape[0]
        out += [_tree_at(blk, i) for i in range(n)
                for blk in params["period"]]
    return out + list(params.get("suffix", []))


def _run_stacked_ssm(params, h, cfg, cache, return_cache=False):
    layers = params["layers"]
    if cache is None and not return_cache and _instrumented_eager(h):
        n = jax.tree.leaves(layers)[0].shape[0]
        for i in range(n):
            op_hook(f"layer{i}", (h,), ())
            h, _st = _mamba_block(_tree_at(layers, i), h, cfg, None)
        return h, None

    def body(carry, xs):
        hh = carry
        if cache is None:
            blk = xs
            hh2, st = _mamba_block(blk, hh, cfg, None)
            return hh2, (st if return_cache else None)
        blk, st = xs
        hh2, new_st = _mamba_block(blk, hh, cfg, st)
        return hh2, new_st

    body = _remat(cfg, body)
    if cache is None:
        h, states = jax.lax.scan(body, h, layers)
        if not return_cache:
            return h, None
        return h, {"ssm": states,
                   "length": jnp.full((h.shape[0],), h.shape[1], jnp.int32)}
    h, new_ssm = jax.lax.scan(body, h, (layers, cache["ssm"]))
    new_cache = {"ssm": new_ssm,
                 "length": cache.get("length", 0) + h.shape[1]}
    return h, new_cache


def _run_hybrid(params, h, cfg, positions, cache, return_cache=False):
    shared = params["shared"]
    if cache is None and not return_cache and _instrumented_eager(h):
        groups = params["groups"]
        n_g = jax.tree.leaves(groups)[0].shape[0]
        every = jax.tree.leaves(groups)[0].shape[1]
        for gi in range(n_g):
            for li in range(every):
                op_hook(f"group{gi}.layer{li}", (h,), ())
                h, _ = _mamba_block(_tree_at(_tree_at(groups, gi), li),
                                    h, cfg, None)
            op_hook(f"group{gi}.shared_attn", (h,), ())
            h, _kv, _aux = _attn_block(shared, h, cfg, positions, None)
        if "tail" in params:
            n_t = jax.tree.leaves(params["tail"])[0].shape[0]
            for ti in range(n_t):
                h, _ = _mamba_block(_tree_at(params["tail"], ti), h, cfg,
                                    None)
        return h, None

    def group_body(carry, xs):
        hh = carry
        if cache is None:
            grp = xs
            def inner(c, blk):
                c2, st = _mamba_block(blk, c, cfg, None)
                return c2, (st if return_cache else None)
            hh, states = jax.lax.scan(inner, hh, grp)
            hh, kv, _aux = _attn_block(shared, hh, cfg, positions, None)
            if return_cache:
                return hh, (states, {"k": kv["k"], "v": kv["v"]})
            return hh, None
        grp, sstates, kv_slice = xs
        def inner(c, blk_st):
            blk, st = blk_st
            c2, new_st = _mamba_block(blk, c, cfg, st)
            return c2, new_st
        hh, new_states = jax.lax.scan(inner, hh, (grp, sstates))
        hh, new_kv, _aux = _attn_block(shared, hh, cfg, positions, kv_slice)
        return hh, (new_states, {"k": new_kv["k"], "v": new_kv["v"]})

    group_body = _remat(cfg, group_body)
    if cache is None:
        h, ys = jax.lax.scan(group_body, h, params["groups"])
        new_cache = None
        if return_cache:
            states, kv = ys
            length = jnp.full((h.shape[0],), h.shape[1], jnp.int32)
            new_cache = {"kv": {"k": kv["k"], "v": kv["v"], "length": length},
                         "ssm_groups": states}
        if "tail" in params:
            def tail_body(c, blk):
                c2, st = _mamba_block(blk, c, cfg, None)
                return c2, (st if return_cache else None)
            tail_body = _remat(cfg, tail_body)
            h, tail_states = jax.lax.scan(tail_body, h, params["tail"])
            if return_cache:
                new_cache["ssm_tail"] = tail_states
        return h, new_cache

    kv = cache["kv"]
    n_groups = kv["k"].shape[0]
    per_group_kv = {"k": kv["k"], "v": kv["v"],
                    "length": jnp.broadcast_to(kv["length"],
                                               (n_groups,
                                                *kv["length"].shape))}
    h, (new_ssm_g, new_kv) = jax.lax.scan(
        group_body, h, (params["groups"], cache["ssm_groups"], per_group_kv))
    new_cache = {"kv": {"k": new_kv["k"], "v": new_kv["v"],
                        "length": kv["length"] + h.shape[1]},
                 "ssm_groups": new_ssm_g}
    if "tail" in params:
        def tail_body(c, blk_st):
            blk, st = blk_st
            c2, new_st = _mamba_block(blk, c, cfg, st)
            return c2, new_st
        tail_body = _remat(cfg, tail_body)
        h, new_tail = jax.lax.scan(tail_body, h,
                                   (params["tail"], cache["ssm_tail"]))
        new_cache["ssm_tail"] = new_tail
    return h, new_cache


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def cross_entropy(logits: jax.Array, labels: jax.Array,
                  z_loss: float = 1e-4):
    """Mean next-token CE in f32 (+ z-loss for logit drift)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    zl = z_loss * jnp.square(lse)
    return (nll + zl).mean(), {"ce": nll.mean(), "z": zl.mean()}
