"""Plain reference for LFM2-MoE (``lfm2-8b-a1b``), written from its
configuration file alone.

It imports nothing of the program; the shared pieces (the seed's key, the
rounding of the control, the float32 matmul at ``HIGHEST``, the
``(1 + scale)`` RMSNorm, rotary positions, the z-loss and AdamW) are the
dense reference's, ``dense_lm.py``.  The architecture, as the file's
``program`` block, ``assumed`` and ``departures`` state:

* a block is ``h = x + op(N1(x))``, ``out = h + ffn(N2(h))``, the final norm
  before the tied head;
* ``op`` is per ``layer_pattern``: the gated short convolution (``[B, C, x~]
  = split3(h W_in)``, ``u = B * x~``, ``c_t = sum_j w_j * u_(t-2+j)``,
  depthwise and causal, ``y = (C * c) W_out``) or GQA attention with an
  RMSNorm on each query and key head before rotary positions over the whole
  head, an exact softmax over all keys, computed over blocks of queries
  under ``jax.checkpoint`` so that one row of 8,192 fits a chip;
* ``ffn`` is the SwiGLU MLP in the first ``n_dense_layers`` layers and the
  experts after them: ``s = sigmoid(h W_r)`` over all experts, the experts
  chosen by ``top-k(s + b)``, their gates the chosen ``s`` renormalized over
  the k (the published ``norm_topk_prob``; ``routed_scaling_factor`` is 1);
  each held expert (``[0, n_experts_held)``) runs its SwiGLU on every token and its output is kept where the router chose it,
  weighted by its gate.  No sort, no grouping.  An expert held elsewhere
  contributes nothing.

The weights come from :func:`init_weights`, laid out as the program's tree:
``prefix`` (the dense layers, a list), ``period`` (a list over the positions
of the pattern's repeating unit, each stacked over its repeats) and
``suffix``; the reference runs the layers in order from that tree.  The
expert bias is seeded at ``BIAS_SCALE`` (the configuration's ``assumed``).
"""

from __future__ import annotations

import importlib.util
import math
import os

import jax
import jax.numpy as jnp


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location(
        "lfm2_moe_" + name,
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


D = _sibling("dense_lm")

HIGHEST = D.HIGHEST
seed_key, rounded, lr_at, adamw_step, _mm = (
    D.seed_key, D.rounded, D.lr_at, D.adamw_step, D._mm)
BIAS_SCALE = 0.1
#: the published ``conv_L_cache``
CONV_TAPS = 3
QUERY_BLOCK = 512


# ------------------------------------------------------------- the layout
def _period(c: dict) -> tuple:
    """(length, repeats) of the pattern's repeating unit after the dense
    layers: of the units repeated at least twice from the start, the one
    covering most layers (the shortest on a tie), else all of them once."""
    rest = c["layer_pattern"][c["n_dense_layers"]:]
    best, cover = (len(rest), 1 if rest else 0), 0
    for p in range(1, len(rest) // 2 + 1):
        n = 0
        while (n + 1) * p <= len(rest) and \
                rest[n * p:(n + 1) * p] == rest[:p]:
            n += 1
        if n >= 2 and n * p > cover:
            best, cover = (p, n), n * p
    return best


def _layer_shapes(c: dict, kind: str, dense: bool) -> dict:
    d, h, kv, hd = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    out = {"ln1": (d,), "ln2": (d,)}
    if kind == "attn":
        out["attn"] = {"wq": (d, h, hd), "wk": (d, kv, hd), "wv": (d, kv, hd),
                       "wo": (h, hd, d), "q_norm": (hd,), "k_norm": (hd,)}
    else:
        out["conv"] = {"w_in": (d, 3 * d), "conv": (CONV_TAPS, d),
                       "w_out": (d, d)}
    if dense:
        f = c["d_ff"]
        out["mlp"] = {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    else:
        e, f = c["n_experts"], c["d_ff_expert"]
        held = c.get("n_experts_held") or e
        out["moe"] = {"router": (d, e), "bias": (e,),
                      "w_gate": (held, d, f), "w_up": (held, d, f),
                      "w_down": (held, f, d)}
    return out


def _shapes(c: dict) -> dict:
    pat, n_dense, n = c["layer_pattern"], c["n_dense_layers"], c["n_layers"]
    per, reps = _period(c)
    out = {"embed": (c["vocab_size"], c["d_model"]),
           "final_norm": (c["d_model"],)}
    if n_dense:
        out["prefix"] = [_layer_shapes(c, pat[i], True)
                         for i in range(n_dense)]
    if reps:
        is_shape = lambda x: isinstance(x, tuple)            # noqa: E731
        out["period"] = [jax.tree.map(
            lambda s: (reps, *s), _layer_shapes(c, pat[n_dense + j], False),
            is_leaf=is_shape) for j in range(per)]
    first = n_dense + per * reps
    if first < n:
        out["suffix"] = [_layer_shapes(c, pat[i], i < n_dense)
                         for i in range(first, n)]
    return out


def _scale(name: str, shape: tuple, c: dict) -> float:
    """Init scale of one leaf: 0.02 for the embedding, fan-in for matrices,
    ``BIAS_SCALE`` for the expert bias, 0 for norm gains."""
    leaf = name.rsplit("/", 1)[-1]
    if leaf in ("ln1", "ln2", "final_norm", "q_norm", "k_norm"):
        return 0.0
    if leaf == "embed":
        return 0.02
    if leaf == "bias":
        return BIAS_SCALE
    if leaf == "conv":
        return 1.0 / math.sqrt(CONV_TAPS)
    if leaf == "wo":
        return 1.0 / math.sqrt(c["n_heads"] * c["head_dim"])
    if leaf in ("w_gate", "w_up", "w_down", "w_in", "w_out", "router"):
        return 1.0 / math.sqrt(shape[-2])
    return 1.0 / math.sqrt(c["d_model"])                 # wq, wk, wv


def make_weights(c: dict, key):
    """The weights of ``key`` (traceable), in the configuration's
    ``param_dtype``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        _shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    dt = jnp.dtype(c["param_dtype"])
    keys = jax.random.split(key, len(flat))
    leaves = []
    for k, (path, shape) in zip(keys, flat):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        s = _scale(name, shape, c)
        leaves.append(jnp.zeros(shape, dt) if s == 0.0 else
                      (jax.random.normal(k, shape, jnp.float32) * s)
                      .astype(dt))
    return treedef.unflatten(leaves)


def init_weights(c: dict, seed: int):
    """The weights for ``seed``, made on the device in one jitted call whose
    program is the same for every seed (the key is its argument)."""
    return jax.jit(lambda key: make_weights(c, key))(seed_key(seed))


def layers(w) -> list:
    """Every layer's weights, in order."""
    out = list(w.get("prefix", []))
    if "period" in w:
        reps = jax.tree.leaves(w["period"])[0].shape[0]
        out += [jax.tree.map(lambda a: a[r], blk) for r in range(reps)
                for blk in w["period"]]
    return out + list(w.get("suffix", []))


# ---------------------------------------------------------------- forward
def short_conv(x, p, mm_dtype):
    bcx = _mm("bsd,de->bse", x, p["w_in"], mm_dtype)
    gb, gc, xt = jnp.split(bcx, 3, axis=-1)
    u = gb * xt
    width, s = p["conv"].shape[0], x.shape[1]
    c = jnp.zeros_like(u)
    for j in range(width):                     # c_t += w_j * u_(t-width+1+j)
        lag = width - 1 - j
        shifted = jnp.pad(u, ((0, 0), (lag, 0), (0, 0)))[:, :s]
        c = c + p["conv"][j].astype(jnp.float32) * shifted
    return _mm("bsd,de->bse", gc * c, p["w_out"], mm_dtype)


def attention(x, a, c, pos, mm_dtype):
    eps = c["rmsnorm_eps"]
    q = _mm("bsd,dhk->bshk", x, a["wq"], mm_dtype)
    k = _mm("bsd,dhk->bshk", x, a["wk"], mm_dtype)
    v = _mm("bsd,dhk->bshk", x, a["wv"], mm_dtype)
    q = D._rope(D._norm(q, a["q_norm"], eps), pos, c["rope_theta"])
    k = D._rope(D._norm(k, a["k_norm"], eps), pos, c["rope_theta"])
    rep = c["n_heads"] // c["n_kv_heads"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    b, s = x.shape[:2]
    qb = min(QUERY_BLOCK, s)
    qs = q.reshape(b, s // qb, qb, *q.shape[2:]).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def block(args):
        qi, start = args
        sc = _mm("bshk,bthk->bhst", qi, k, mm_dtype) / math.sqrt(
            c["head_dim"])
        causal = jnp.arange(s)[None, :] <= (start + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(causal, sc, D.NEG_INF), axis=-1)
        return _mm("bhst,bthk->bshk", p, v, mm_dtype)

    o = jax.lax.map(block, (qs, jnp.arange(s // qb) * qb))
    o = o.transpose(1, 0, 2, 3, 4).reshape(b, s, *o.shape[3:])
    return _mm("bshk,hkd->bsd", o, a["wo"], mm_dtype)


def mlp(x, m, mm_dtype):
    g = _mm("bsd,df->bsf", x, m["w_gate"], mm_dtype)
    u = _mm("bsd,df->bsf", x, m["w_up"], mm_dtype)
    return _mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_down"], mm_dtype)


def route(x, m, c: dict):
    """(gates, chosen) over all experts: gates (B,S,E) are zero where an
    expert was not chosen."""
    s = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, m["router"],
                                  precision=HIGHEST))
    _, top = jax.lax.top_k(s + m["bias"], c["n_experts_active"])
    chosen = jax.nn.one_hot(top, s.shape[-1], dtype=jnp.float32).sum(-2)
    gates = s * chosen
    return gates / gates.sum(-1, keepdims=True), chosen


def experts(x, m, c: dict, mm_dtype):
    """The held experts' part of the layer: every held expert on every
    token, kept where the router chose it."""
    gates, _ = route(x, m, c)
    held = m["w_gate"].shape[0]
    g = _mm("bsd,edf->ebsf", x, m["w_gate"], mm_dtype)
    u = _mm("bsd,edf->ebsf", x, m["w_up"], mm_dtype)
    y = _mm("ebsf,efd->ebsd", jax.nn.silu(g) * u, m["w_down"], mm_dtype)
    weight = jnp.moveaxis(gates[..., :held], -1, 0)[..., None]
    return (y * weight).sum(0)


def block(x, w, c, pos, mm_dtype):
    eps = c["rmsnorm_eps"]
    h = D._norm(x, w["ln1"], eps)
    op = (attention(h, w["attn"], c, pos, mm_dtype) if "attn" in w
          else short_conv(h, w["conv"], mm_dtype))
    x = x + op
    h = D._norm(x, w["ln2"], eps)
    ffn = (mlp(h, w["mlp"], mm_dtype) if "mlp" in w
           else experts(h, w["moe"], c, mm_dtype))
    return x + ffn


def hidden(w, tokens, c: dict, mm_dtype=None):
    """Final-normed hidden states (B, S, d) in float32."""
    x = w["embed"].astype(jnp.float32)[tokens]
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None], tokens.shape)
    run = jax.checkpoint(lambda x, lw: block(x, lw, c, pos, mm_dtype))
    for lw in layers(w):
        x = run(x, lw)
    return D._norm(x, w["final_norm"], c["rmsnorm_eps"])


def logits(w, tokens, c: dict, mm_dtype=None):
    return _mm("bsd,vd->bsv", hidden(w, tokens, c, mm_dtype), w["embed"],
               mm_dtype)


def loss(w, inputs, labels, c: dict, mm_dtype=None):
    """Mean next-token cross entropy plus the z-loss."""
    lg = logits(w, inputs, c, mm_dtype)
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll + D.Z_LOSS * lse * lse)
