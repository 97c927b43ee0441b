"""Plain reference for the dense decoder LMs (``stablelm-1.6b``,
``paper-gpt2``), written from their configuration files alone.

It imports nothing of the program.  It is the architecture the program
runs, as the configuration files' ``program`` block and ``departures`` state:
pre-norm blocks of causal multi-head attention with rotary positions over
the whole head (first and second halves rotated as pairs) and a gated SiLU
MLP; RMSNorm with a ``(1 + scale)`` gain, statistics in float32; no biases;
the LM head is the embedding's transpose when tied.  Training minimizes the
mean next-token cross entropy plus ``1e-4 * logsumexp**2`` (z-loss) and
steps AdamW with global-norm clipping, bias correction, linear warm-up into
a cosine decay, and weight decay on matrices only.

Every matmul is float32 at ``HIGHEST`` precision.  ``mm_dtype`` rounds the
operands of every matmul to a lower type first, with one scale per tensor:
that is the control, the step below the configuration's own bf16 compute.

The weights come from :func:`init_weights`, one jitted program of the seed,
laid out as the program's parameter tree so the harness can hand the same
arrays to the system under test; the reference makes its own copy again from
the seed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Z_LOSS = 1e-4
NEG_INF = -1e30


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))


def _shapes(c: dict) -> dict:
    d, h, kv, hd, f = (c["d_model"], c["n_heads"], c["n_kv_heads"],
                       c["head_dim"], c["d_ff"])
    layer = {"ln1": (d,), "ln2": (d,),
             "attn": {"wq": (d, h, hd), "wk": (d, kv, hd),
                      "wv": (d, kv, hd), "wo": (h, hd, d)},
             "mlp": {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}}
    out = {"embed": (c["vocab_size"], d), "final_norm": (d,)}
    if not c["tie_embeddings"]:
        out["lm_head"] = (d, c["vocab_size"])
    out["layers"] = jax.tree.map(lambda s: (c["n_layers"], *s), layer,
                                 is_leaf=lambda x: isinstance(x, tuple))
    return out


def _scale(path: str, shape: tuple, c: dict) -> float:
    """Init scale of one leaf: 0.02 for the embedding, fan-in for matrices,
    and 0 for norm gains (a ``(1 + 0)`` gain)."""
    if path.endswith(("ln1", "ln2", "final_norm")):
        return 0.0
    if path == "embed":
        return 0.02
    if path.endswith("wo"):
        return 1.0 / math.sqrt(c["n_heads"] * c["head_dim"])
    if path == "lm_head":
        return 1.0 / math.sqrt(c["d_model"])
    return 1.0 / math.sqrt(shape[-2] if path.endswith(
        ("w_gate", "w_up", "w_down")) else shape[1])


def make_weights(c: dict, key):
    """The weights of ``key`` (traceable), in the configuration's
    ``param_dtype``."""
    shapes = _shapes(c)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    dt = jnp.dtype(c["param_dtype"])
    keys = jax.random.split(key, len(flat))
    leaves = []
    for k, (path, shape) in zip(keys, flat):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        s = _scale(name, shape, c)
        leaves.append(jnp.zeros(shape, dt) if s == 0.0 else
                      (jax.random.normal(k, shape, jnp.float32) * s)
                      .astype(dt))
    return treedef.unflatten(leaves)


def init_weights(c: dict, seed: int):
    """The weights for ``seed``, made on the device in one jitted call whose
    program is the same for every seed (the key is its argument)."""
    return jax.jit(lambda key: make_weights(c, key))(seed_key(seed))


# ---------------------------------------------------------------- forward
def rounded(x, dtype):
    """``x`` rounded to ``dtype`` with one scale for the whole tensor, as a
    low-precision matmul takes its operands (the largest magnitude maps to
    the type's largest finite value); the gradient passes straight
    through, so the backward pass sees the rounded forward's values and
    float32 cotangents."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    q = (x * scale).astype(dtype).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec: str, a, b, mm_dtype):
    if mm_dtype is not None:
        a, b = rounded(a, mm_dtype), rounded(b, mm_dtype)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _norm(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g.astype(jnp.float32))


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., None] * inv        # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(x, w, c, pos, mm_dtype):
    eps = c["rmsnorm_eps"]
    h = _norm(x, w["ln1"], eps)
    a = w["attn"]
    q = _rope(_mm("bsd,dhk->bshk", h, a["wq"], mm_dtype), pos, c["rope_theta"])
    k = _rope(_mm("bsd,dhk->bshk", h, a["wk"], mm_dtype), pos, c["rope_theta"])
    v = _mm("bsd,dhk->bshk", h, a["wv"], mm_dtype)
    rep = c["n_heads"] // c["n_kv_heads"]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = _mm("bshk,bthk->bhst", q, k, mm_dtype) / math.sqrt(c["head_dim"])
    t = s.shape[-1]
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("bhst,bthk->bshk", p, v, mm_dtype)
    x = x + _mm("bshk,hkd->bsd", o, a["wo"], mm_dtype)
    h = _norm(x, w["ln2"], eps)
    m = w["mlp"]
    g = _mm("bsd,df->bsf", h, m["w_gate"], mm_dtype)
    u = _mm("bsd,df->bsf", h, m["w_up"], mm_dtype)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_down"], mm_dtype)


def hidden(w, tokens, c: dict, mm_dtype=None):
    """Final-normed hidden states (B, S, d) in float32."""
    x = w["embed"].astype(jnp.float32)[tokens]
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None],
                           tokens.shape)

    def body(x, lw):
        return _block(x, lw, c, pos, mm_dtype), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, w["layers"])
    return _norm(x, w["final_norm"], c["rmsnorm_eps"])


def head(w, h, c: dict, mm_dtype=None):
    """Logits from hidden states."""
    if c["tie_embeddings"]:
        return _mm("bsd,vd->bsv", h, w["embed"], mm_dtype)
    return _mm("bsd,dv->bsv", h, w["lm_head"], mm_dtype)


def logits(w, tokens, c: dict, mm_dtype=None):
    return head(w, hidden(w, tokens, c, mm_dtype), c, mm_dtype)


def loss(w, inputs, labels, c: dict, mm_dtype=None):
    """Mean next-token cross entropy plus the z-loss."""
    lg = logits(w, inputs, c, mm_dtype)
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll + Z_LOSS * lse * lse)


# --------------------------------------------------------------- training
def lr_at(step: int, o: dict) -> float:
    warm = min(step / max(o["warmup_steps"], 1), 1.0)
    prog = min(max((step - o["warmup_steps"])
                   / max(o["total_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
    frac = o["min_lr_frac"] + (1 - o["min_lr_frac"]) * 0.5 * (
        1 + math.cos(math.pi * prog))
    return o["lr"] * warm * frac


def grads(w, inputs, labels, c: dict, rows: int, mm_dtype=None):
    """(loss, grads) of the whole batch, accumulated over blocks of
    ``rows`` rows so that float32 activations fit."""
    fn = jax.jit(jax.value_and_grad(
        lambda w, i, l: loss(w, i, l, c, mm_dtype)))
    n = inputs.shape[0]
    total, acc = 0.0, None
    for r in range(0, n, rows):
        l, g = fn(w, inputs[r:r + rows], labels[r:r + rows])
        share = min(rows, n - r) / n
        total += float(l) * share
        g = jax.tree.map(lambda x: x * share, g)
        acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
    return total, acc


def adamw_step(w, g, mu, nu, step: int, o: dict):
    """One AdamW step (``step`` counts from 1).  Returns the new weights,
    moments and the clipped gradient."""
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    clip = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    g = jax.tree.map(lambda x: x * clip, g)
    lr = lr_at(step, o)
    bc1, bc2 = 1 - o["b1"] ** step, 1 - o["b2"] ** step
    mu = jax.tree.map(lambda m, x: o["b1"] * m + (1 - o["b1"]) * x, mu, g)
    nu = jax.tree.map(lambda v, x: o["b2"] * v + (1 - o["b2"]) * x * x, nu, g)

    def upd(p, m, v):
        wd = o["weight_decay"] if p.ndim >= 2 else 0.0
        pf = p.astype(jnp.float32)
        u = (m / bc1) / (jnp.sqrt(v / bc2) + o["eps"])
        return (pf - lr * (u + wd * pf)).astype(p.dtype)

    return jax.tree.map(upd, w, mu, nu), mu, nu, g
