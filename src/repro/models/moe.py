"""Mixture-of-Experts layer: a router over all experts and a dropless
expert share computed as grouped matmuls.

  1. router (float32): ``softmax(x Wg)``, or ``sigmoid(x Wg)`` with the
     experts chosen by ``top-k(sigmoid + b)`` for an expert bias ``b``
     (``router="sigmoid_bias"``); the gates are the chosen scores,
     renormalized over the k chosen;
  2. the layer holds experts ``[0, held)`` (``n_experts_held``; all by
     default).  The (T·k) token-expert assignments are sorted by expert, so
     those of held experts form one contiguous ragged run of groups;
  3. the held experts' SwiGLU runs as three grouped matmuls
     (``jax.lax.ragged_dot``) over those groups: each computes exactly the
     rows routed to it, and no assignment is dropped;
  4. the rows are gathered back into token order and summed with their
     gates.  An assignment to an expert held elsewhere contributes nothing
     here: on one chip of an expert-parallel group that is this chip's part
     of the layer's result.

Rows past the held groups are masked before and after every grouped matmul,
so whatever the matmul leaves there reaches neither the result nor a
gradient.  The selection takes no gradient, so the expert bias gets none.

Counters (the layer's ``aux``): ``expert_counts``, the assignments each held
expert computed (int32 [held]), and ``held_share``, the share of all
assignments that went to held experts.

Parallelism (:func:`sharded_experts`): the expert weights shard by
``moe_param_axes``, the hidden dim over ``model`` (TP), or the experts over
``model`` with ``moe_parallelism="ep"``, each device then holding a
contiguous share of them.  Each data shard sorts and computes its own
tokens' assignments; a device's output is a partial sum over ``model``,
which one all-reduce completes.  Shared experts (Kimi-style) run densely
for every token.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.core.instrument import op_hook
from repro.dist.sharding import get_mesh, logical, shard
from .config import ModelConfig


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    d, f, e, h = cfg.d_model, cfg.d_ff_expert, cfg.n_experts, \
        cfg.experts_held
    ks = jax.random.split(key, 5)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * s_in,
        "w_gate": jax.random.normal(ks[1], (h, d, f), dtype) * s_in,
        "w_up": jax.random.normal(ks[2], (h, d, f), dtype) * s_in,
        "w_down": jax.random.normal(ks[3], (h, f, d), dtype) * s_out,
    }
    if cfg.router == "sigmoid_bias":
        p["bias"] = jnp.zeros((e,), jnp.float32)
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["ws_gate"] = jax.random.normal(k1, (d, fs), dtype) * s_in
        p["ws_up"] = jax.random.normal(k2, (d, fs), dtype) * s_in
        p["ws_down"] = jax.random.normal(k3, (fs, d), dtype) * s_out
    return p


def moe_param_axes(cfg: ModelConfig) -> dict:
    if cfg.moe_parallelism == "ep":
        # experts over `model`, d_model over `data` (FSDP); hidden dim local
        axes = {
            "router": ("p_embed", None),
            "w_gate": ("p_experts_ep", "p_embed", None),
            "w_up": ("p_experts_ep", "p_embed", None),
            "w_down": ("p_experts_ep", None, "p_embed"),
        }
    else:
        axes = {
            "router": ("p_embed", None),
            "w_gate": ("p_experts", "p_embed", "p_expert_ff"),
            "w_up": ("p_experts", "p_embed", "p_expert_ff"),
            "w_down": ("p_experts", "p_expert_ff", "p_embed"),
        }
    if cfg.router == "sigmoid_bias":
        axes["bias"] = (None,)
    if cfg.n_shared_experts:
        axes.update({"ws_gate": ("p_embed", "p_expert_ff"),
                     "ws_up": ("p_embed", "p_expert_ff"),
                     "ws_down": ("p_expert_ff", "p_embed")})
    return axes


def route(p: dict, xt: jax.Array, cfg: ModelConfig):
    """(gates (T,k) f32, experts (T,k) int32) of tokens ``xt`` (T,d)."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.router == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(scores + p["bias"], cfg.n_experts_active)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
    elif cfg.router == "softmax":
        gates, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                       cfg.n_experts_active)
    else:
        raise ValueError(f"unknown router {cfg.router!r}")
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, experts.astype(jnp.int32)


def held_experts(p: dict, xt: jax.Array, gates, experts, lo=0):
    """The part of the layer of experts ``[lo, lo + held)`` for tokens ``xt``
    (T,d): (y (T,d), counts int32 [held], held assignments)."""
    dt = xt.dtype
    t, k = experts.shape
    held = p["w_gate"].shape[0]
    flat = experts.reshape(t * k) - lo
    # assignments of experts held elsewhere sort last, into a group of
    # their own that no grouped matmul visits
    key = jnp.where((flat >= 0) & (flat < held), flat, held)
    order = jnp.argsort(key, stable=True)
    counts = (key[:, None] == jnp.arange(held)[None, :]).sum(
        0, dtype=jnp.int32)
    n_held = counts.sum()
    live = (jnp.arange(t * k) < n_held)[:, None]

    def grouped(lhs, w):
        out = jax.lax.ragged_dot(jnp.where(live, lhs, 0), w.astype(dt),
                                 counts)
        return jnp.where(live, out, 0)

    # permutations only, so that every gather's transpose is a scatter of
    # unique rows
    xs = jnp.repeat(xt, k, axis=0).at[order].get(unique_indices=True)
    g = grouped(xs, p["w_gate"])
    u = grouped(xs, p["w_up"])
    ys = grouped(jax.nn.silu(g) * u, p["w_down"])
    # back to (token, slot) order; a slot whose expert is not held reads 0
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * k, dtype=order.dtype), unique_indices=True)
    y = ys.at[inv].get(unique_indices=True).reshape(t, k, -1)
    y = (y * gates.astype(dt)[..., None]).sum(axis=1)
    return y, counts, n_held


def _axis(spec):
    return spec[0] if len(spec) else None


def sharded_experts(w: dict, xt: jax.Array, gates, experts,
                    cfg: ModelConfig):
    """:func:`held_experts` over the registered mesh, in one ``shard_map``:
    each data shard sorts and computes its own tokens' assignments, so no
    row leaves its shard.  Over ``model``, TP splits every expert's hidden
    dim and EP (``moe_parallelism="ep"``) gives each device a contiguous
    share of the experts; either way a device's output is a partial sum
    that one ``psum`` over ``model`` completes.  Without a mesh of more than
    one device it is :func:`held_experts` itself."""
    mesh = get_mesh()
    if mesh is None or mesh.size == 1:
        return held_experts(w, xt, gates, experts)
    held = cfg.experts_held
    tok = _axis(logical("batch", dims=(xt.shape[0],)))
    ep = cfg.moe_parallelism == "ep"
    if ep:
        ax = _axis(logical("p_experts_ep", dims=(held,)))
        w_specs = {k: P(ax) for k in w}
    else:
        ax = _axis(logical("p_expert_ff", dims=(cfg.d_ff_expert,)))
        w_specs = {"w_gate": P(None, None, ax), "w_up": P(None, None, ax),
                   "w_down": P(None, ax, None)}
    if tok is None and ax is None:
        return held_experts(w, xt, gates, experts)
    n_ax = mesh.shape[ax] if ax else 1

    def local(w, xt, gates, experts):
        lo = 0
        if ep and ax:
            lo = jax.lax.axis_index(ax) * (held // n_ax)
            # each device reads the tokens for its own experts: marked as
            # varying over `model`, so that their gradient sums the shares
            xt = jax.lax.pcast(xt, ax, to="varying")
        y, counts, n = held_experts(w, xt, gates, experts, lo)
        if ax:
            y = jax.lax.psum(y, ax)
        if tok:
            counts, n = jax.lax.psum((counts, n), tok)
        if ep and ax:
            n = jax.lax.psum(n, ax)
        return y, counts, n

    t_spec = P(tok)
    return jax.shard_map(
        local, mesh=mesh, in_specs=(w_specs, t_spec, t_spec, t_spec),
        out_specs=(t_spec, P(ax) if ep else P(), P()))(
            w, xt, gates, experts)


def moe_layer(p: dict, x: jax.Array, cfg: ModelConfig):
    """x: (B,S,d). Returns (y, aux) with the expert counters."""
    dt = x.dtype
    b, s, d = x.shape
    xt = shard(x.reshape(b * s, d), "batch", "embed")
    with jax.named_scope("moe.route"):
        gates, experts = route(p, xt, cfg)
    with jax.named_scope("moe.experts"):
        y, counts, n_held = sharded_experts(
            {k: p[k] for k in ("w_gate", "w_up", "w_down")}, xt, gates,
            experts, cfg)
    op_hook("moe.experts", (x, p["w_gate"], p["w_up"], p["w_down"]), (y,))

    # ---- shared experts (dense) --------------------------------------------
    if cfg.n_shared_experts:
        sg = jnp.einsum("td,df->tf", xt, p["ws_gate"].astype(dt))
        su = jnp.einsum("td,df->tf", xt, p["ws_up"].astype(dt))
        sg = shard(sg, "batch", "expert_ff")
        su = shard(su, "batch", "expert_ff")
        y = y + jnp.einsum("tf,fd->td", jax.nn.silu(sg) * su,
                           p["ws_down"].astype(dt))

    aux = {"expert_counts": counts,
           "held_share": n_held.astype(jnp.float32) / experts.size}
    y = y.reshape(b, s, d)
    return shard(y, "batch", "seq", "embed"), aux
