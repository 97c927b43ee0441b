"""Shared transformer layers: RMSNorm, RoPE/M-RoPE, GQA attention, GLU MLPs,
and the gated short convolution of the conv/attention hybrids (LFM2).

Numerics: matmuls in the config compute dtype (bf16), softmax/norm statistics
in f32.  Attention paths:

  * ``dense``   — full (S×T) scores; training and short prefill.
  * ``blocked`` — lax.scan over KV chunks with online softmax (flash-style);
    long sequences where S² scores would not fit.  The scan body is
    rematerialized, so the backward pass keeps only the per-chunk carries.
  * ``decode``  — one (or few) query tokens against a KV cache whose sequence
    dim may be sharded over the ``model`` mesh axis; softmax statistics
    reduce globally (GSPMD inserts the small all-reduces), which is the
    flash-decode/sequence-parallel pattern for long contexts.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core.instrument import op_hook
from repro.dist.sharding import shard
from .config import CONV_TAPS, ModelConfig

NEG_INF = -1e30


# --------------------------------------------------------------------- norms
def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------- rope
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               m_rope: bool = False) -> jax.Array:
    """x: (B, S, H, D). positions: (B, S) or (B, S, 3) for M-RoPE."""
    head_dim = x.shape[-1]
    half = head_dim // 2
    inv = rope_freqs(head_dim, theta)                       # (half,)
    if m_rope:
        if positions.ndim == 2:                             # text-only stub
            positions = jnp.broadcast_to(positions[..., None],
                                         (*positions.shape, 3))
        # sectioned rotary (temporal / height / width)
        s1 = half // 3
        s2 = (half - s1) // 2
        sections = [s1, s2, half - s1 - s2]
        parts = []
        off = 0
        for sec_i, sec in enumerate(sections):
            ang = positions[..., sec_i].astype(jnp.float32)[..., None] \
                * inv[off:off + sec]
            parts.append(ang)
            off += sec
        angles = jnp.concatenate(parts, axis=-1)            # (B, S, half)
    else:
        angles = positions.astype(jnp.float32)[..., None] * inv
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- attention
def init_attention(key, cfg: ModelConfig, dtype) -> dict:
    d, q, kv, hd = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": jax.random.normal(k1, (d, cfg.n_heads, hd), dtype) * s,
        "wk": jax.random.normal(k2, (d, cfg.n_kv_heads, hd), dtype) * s,
        "wv": jax.random.normal(k3, (d, cfg.n_kv_heads, hd), dtype) * s,
        "wo": jax.random.normal(k4, (cfg.n_heads, hd, d), dtype)
        * (1.0 / math.sqrt(cfg.q_dim)),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def attention_param_axes() -> dict:
    return {
        "wq": ("p_embed", "p_heads", None),
        "wk": ("p_embed", "p_kv_heads", None),
        "wv": ("p_embed", "p_kv_heads", None),
        "wo": ("p_heads", None, "p_embed"),
        "q_norm": (None,),
        "k_norm": (None,),
    }


def _qkv(p, x, cfg: ModelConfig, positions):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.rmsnorm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.rmsnorm_eps)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.m_rope)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.m_rope)
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")
    op_hook("attn.qkv_proj", (x, p["wq"], p["wk"], p["wv"]), (q, k, v))
    return q, k, v


def _group(q, n_kv: int):
    """(B,S,H,D) -> (B,S,Hkv,G,D) grouping query heads onto KV heads."""
    b, s, h, d_ = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d_)


def _sdpa_dense(q, k, v, causal: bool, q_offset=0,
                softmax_dtype=jnp.float32):
    """q:(B,S,Hkv,G,D) k/v:(B,T,Hkv,D). Full-scores attention.

    ``softmax_dtype=bfloat16`` keeps the (S×T) score tensors in bf16 (the
    row-max subtraction still stabilizes the exp) — halves the dominant
    HBM-traffic term of 4k-seq training at <1e-2 logit error (validated in
    tests); f32 is the paper-faithful default."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bshgd,bthd->bhgst", q, k).astype(softmax_dtype) \
        * jnp.asarray(scale, softmax_dtype)
    if causal:
        s_len, t_len = scores.shape[-2], scores.shape[-1]
        qi = jnp.arange(s_len)[:, None] + q_offset
        ki = jnp.arange(t_len)[None, :]
        scores = jnp.where(ki <= qi, scores,
                           jnp.asarray(NEG_INF, softmax_dtype))
    m = jax.lax.stop_gradient(scores.max(axis=-1, keepdims=True))
    p = jnp.exp(scores - m)
    w = (p / p.sum(axis=-1, keepdims=True)).astype(q.dtype)
    out = jnp.einsum("bhgst,bthd->bshgd", w, v)
    return out.reshape(*out.shape[:2], -1, out.shape[-1])    # (B,S,H,D)


def _sdpa_blocked(q, k, v, causal: bool, chunk: int = 1024):
    """Flash-style online-softmax scan over KV chunks. q:(B,S,Hkv,G,D)."""
    b, s, hkv, g, d_ = q.shape
    t = k.shape[1]
    chunk = min(chunk, t)
    while t % chunk:           # shapes are powers of two in practice
        chunk //= 2
    n_chunks = t // chunk
    scale = 1.0 / math.sqrt(d_)
    k_c = k.reshape(b, n_chunks, chunk, hkv, d_).transpose(1, 0, 2, 3, 4)
    v_c = v.reshape(b, n_chunks, chunk, hkv, d_).transpose(1, 0, 2, 3, 4)
    qi = jnp.arange(s)[:, None]

    def body(carry, kv_i):
        acc, m, l, ci = carry
        kc, vc = kv_i
        sc = jnp.einsum("bshgd,bthd->bhgst", q, kc).astype(jnp.float32) * scale
        if causal:
            ki = ci * chunk + jnp.arange(chunk)[None, :]
            sc = jnp.where(ki <= qi, sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgst,bthd->bhgsd", p.astype(q.dtype), vc).astype(jnp.float32)
        return (acc, m_new, l_new, ci + 1), None

    acc0 = jnp.zeros((b, hkv, g, s, d_), jnp.float32)
    m0 = jnp.full((b, hkv, g, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, s), jnp.float32)
    (acc, m, l, _), _ = jax.lax.scan(jax.checkpoint(body),
                                     (acc0, m0, l0, 0), (k_c, v_c))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    out = out.transpose(0, 3, 1, 2, 4)                       # (B,S,Hkv,G,D)
    return out.reshape(b, s, hkv * g, d_)


def _sdpa_decode_partial(q, k_cache, v_cache, lengths, base=None):
    """Partial-softmax decode stats over one KV segment.

    q:(B,S,Hkv,G,D), cache:(B,T,Hkv,D). Returns (acc, m, l) f32 where
    ``acc`` is the un-normalized weighted V sum — mergeable across segments
    (flash-decode two-tier / sequence-parallel merging).

    ``base`` (B,) int32: per-row sequence length *before* this append.  When
    given with S>1 (chunked suffix prefill against a cache, the serving
    prefix-reuse path), masking is causal per query token — query ``s`` sits
    at absolute position ``base+s`` and sees keys ``ki <= base+s``.  Without
    it all S queries share the (B,)-length mask (the pre-existing one-token
    decode semantics, which ``base`` reproduces exactly at S=1)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bshgd,bthd->bhgst", q, k_cache).astype(jnp.float32) \
        * scale
    t = k_cache.shape[1]
    s = q.shape[1]
    if base is None or s == 1:
        mask = jnp.arange(t)[None, :] < lengths[:, None]     # (B,T)
        scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)
    else:
        limit = base[:, None] + jnp.arange(1, s + 1)[None, :]   # (B,S)
        mask = jnp.arange(t)[None, None, :] < limit[:, :, None]  # (B,S,T)
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    m = scores.max(axis=-1)                                  # (B,H,G,S)
    p = jnp.exp(scores - m[..., None])
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhgst,bthd->bhgsd", p.astype(q.dtype),
                     v_cache).astype(jnp.float32)
    return acc, m, l


def _merge_partials(parts):
    """Merge flash-decode partials [(acc, m, l), ...] exactly."""
    accs, ms, ls = zip(*parts)
    m_all = jnp.stack(ms).max(axis=0)
    acc = sum(a * jnp.exp(m - m_all)[..., None] for a, m in zip(accs, ms))
    l_all = sum(l * jnp.exp(m - m_all) for l, m in zip(ls, ms))
    return acc / jnp.maximum(l_all, 1e-30)[..., None], m_all, l_all


def _sdpa_decode(q, k_cache, v_cache, lengths, base=None):
    """Decode: q:(B,S,Hkv,G,D), cache:(B,T,Hkv,D) possibly seq-sharded over
    the model axis; masked softmax over the cache with global statistics.
    ``base``: per-row pre-append lengths for causal multi-token appends."""
    acc, m, l = _sdpa_decode_partial(q, k_cache, v_cache, lengths, base=base)
    out = (acc / jnp.maximum(l, 1e-30)[..., None])
    out = out.astype(q.dtype).transpose(0, 3, 1, 2, 4)       # (B,S,Hkv,G,D)
    b, s = out.shape[:2]
    return out.reshape(b, s, -1, out.shape[-1])


def attention(p: dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array,
              cache: dict | None = None, blocked_threshold: int | None = None):
    """Returns (out, new_cache). ``cache``: {"k","v": (B,T,Hkv,D),
    "length": (B,) int32} — decode appends at ``length``."""
    if blocked_threshold is None:
        blocked_threshold = cfg.attn_blocked_threshold
    q, k, v = _qkv(p, x, cfg, positions)
    qg = _group(q, cfg.n_kv_heads)
    if cache is None:
        if x.shape[1] > blocked_threshold:
            out = _sdpa_blocked(qg, k, v, cfg.causal)
        else:
            out = _sdpa_dense(qg, k, v, cfg.causal,
                              softmax_dtype=jnp.dtype(cfg.attn_softmax_dtype))
        new_cache = {"k": k, "v": v,
                     "length": jnp.full((x.shape[0],), x.shape[1], jnp.int32)}
    elif "rk" in cache:
        # two-tier decode: the big seq-sharded main cache stays FROZEN (no
        # per-layer masked-select rewrite); new tokens append into a small
        # replicated recent buffer; partial softmaxes merge exactly.
        lengths = cache["length"] + x.shape[1]
        main_len = cache["main_len"]
        pos_r = (cache["length"] - main_len)[0]
        rk = jax.lax.dynamic_update_slice_in_dim(cache["rk"], k, pos_r, 1)
        rv = jax.lax.dynamic_update_slice_in_dim(cache["rv"], v, pos_r, 1)
        p_main = _sdpa_decode_partial(qg, cache["k"], cache["v"], main_len)
        p_rec = _sdpa_decode_partial(qg, rk, rv, lengths - main_len)
        norm, _m, _l = _merge_partials([p_main, p_rec])
        out = norm.astype(q.dtype).transpose(0, 3, 1, 2, 4)
        out = out.reshape(out.shape[0], out.shape[1], -1, out.shape[-1])
        new_cache = {"k": cache["k"], "v": cache["v"], "rk": rk, "rv": rv,
                     "length": lengths, "main_len": main_len}
    elif "pk" in cache:
        # paged decode/append: {"pk","pv": (n_blocks, bs, Hkv, D),
        # "bt": (B, blocks_per_seq) int32 block tables (sentinel = n_blocks),
        # "length": (B,)}.  New K/V scatter through each row's block table
        # at absolute positions base..base+S-1; the softmax then gathers the
        # row's window back as a contiguous (B, T) view — same values the
        # dense slot layout would hold, so numerics match it exactly.
        pk, pv, bt = cache["pk"], cache["pv"], cache["bt"]
        base = cache["length"]
        n_blocks, bs_blk = pk.shape[0], pk.shape[1]
        b, s = x.shape[0], x.shape[1]
        t = bt.shape[1] * bs_blk
        pos = base[:, None] + jnp.arange(s)[None, :]             # (B,S)
        col = jnp.minimum(pos // bs_blk, bt.shape[1] - 1)
        blk = jnp.take_along_axis(bt, col, axis=1)               # (B,S)
        # rows parked at length >= T (free slots, mid-prefill rows riding a
        # fused decode) resolve to the sentinel: their writes drop
        blk = jnp.where(pos < t, blk, n_blocks)
        off = pos % bs_blk
        pk = pk.at[blk, off].set(k, mode="drop")
        pv = pv.at[blk, off].set(v, mode="drop")
        k_cache = pk[bt].reshape(b, t, *pk.shape[2:])
        v_cache = pv[bt].reshape(b, t, *pv.shape[2:])
        lengths = base + s
        out = _sdpa_decode(qg, k_cache, v_cache, lengths,
                           base=base if s > 1 else None)
        new_cache = {"pk": pk, "pv": pv, "bt": bt, "length": lengths}
    else:
        base = cache["length"]
        if x.shape[1] == 1:
            # ragged-slot decode: each row appends at ITS OWN length (the
            # serving pool interleaves requests at different positions);
            # out-of-range rows (stale free slots at max_seq) drop the write
            b_idx = jnp.arange(k.shape[0])
            k_cache = cache["k"].at[b_idx, base].set(k[:, 0], mode="drop")
            v_cache = cache["v"].at[b_idx, base].set(v[:, 0], mode="drop")
        else:
            # multi-token append (chunked/suffix prefill, fused speculative
            # verify): each row writes at ITS OWN base — the verify batch
            # interleaves requests at different positions, and single-row
            # suffix prefill is just the B=1 case.  Out-of-range positions
            # (parked rows at max_seq, draft spill past the horizon) drop.
            b_idx = jnp.arange(k.shape[0])[:, None]
            pos = base[:, None] + jnp.arange(x.shape[1])[None, :]
            k_cache = cache["k"].at[b_idx, pos].set(k, mode="drop")
            v_cache = cache["v"].at[b_idx, pos].set(v, mode="drop")
        k_cache = shard(k_cache, "batch", "seq_sp", None, "head_dim")
        v_cache = shard(v_cache, "batch", "seq_sp", None, "head_dim")
        lengths = base + x.shape[1]
        out = _sdpa_decode(qg, k_cache, v_cache, lengths, base=base)
        new_cache = {"k": k_cache, "v": v_cache, "length": lengths}
    out = shard(out, "batch", "seq", "heads", "head_dim")
    op_hook("attn.sdpa", (q, k, v), (out,))
    y = jnp.einsum("bshd,hdm->bsm", out, p["wo"].astype(x.dtype))
    y = shard(y, "batch", "seq", "embed")
    op_hook("attn.out_proj", (out, p["wo"]), (y,))
    return y, new_cache


# ----------------------------------------------------------------------- mlp
def init_mlp(key, cfg: ModelConfig, dtype) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f)
    return {
        "w_gate": jax.random.normal(k1, (d, f), dtype) * s_in,
        "w_up": jax.random.normal(k2, (d, f), dtype) * s_in,
        "w_down": jax.random.normal(k3, (f, d), dtype) * s_out,
    }


def mlp_param_axes() -> dict:
    return {"w_gate": ("p_embed", "p_ff"), "w_up": ("p_embed", "p_ff"),
            "w_down": ("p_ff", "p_embed")}


def mlp(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    dt = x.dtype
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
    g = shard(g, "batch", "seq", "ff")
    u = shard(u, "batch", "seq", "ff")
    act = jax.nn.gelu(g) if cfg.mlp == "geglu" else jax.nn.silu(g)
    h = act * u
    y = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))
    y = shard(y, "batch", "seq", "embed")
    op_hook("mlp.glu", (x, p["w_gate"], p["w_up"], p["w_down"]), (g, u, y))
    return y


# ------------------------------------------------------ gated short conv
def init_short_conv(key, cfg: ModelConfig, dtype) -> dict:
    d = cfg.d_model
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_in": jax.random.normal(k1, (d, 3 * d), dtype) / math.sqrt(d),
        "conv": jax.random.normal(k2, (CONV_TAPS, d), dtype)
        / math.sqrt(CONV_TAPS),
        "w_out": jax.random.normal(k3, (d, d), dtype) / math.sqrt(d),
    }


def short_conv_param_axes() -> dict:
    return {"w_in": ("p_embed", None), "conv": (None, None),
            "w_out": (None, "p_embed")}


def short_conv(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """LFM2's gated short convolution over (B, S, d):
    ``[B, C, x~] = split3(x W_in)``, ``u = B * x~``, ``c_t = sum_j w_j * u_(t -
    L + 1 + j)`` (depthwise, causal, zero before the first token), ``y = (C
    * c) W_out``.  The taps accumulate in float32."""
    dt = x.dtype
    with jax.named_scope("conv.short"):
        bcx = jnp.einsum("bsd,de->bse", x, p["w_in"].astype(dt))
        gate_b, gate_c, xt = jnp.split(bcx, 3, axis=-1)
        u = (gate_b * xt).astype(jnp.float32)
        taps = p["conv"].astype(jnp.float32)
        width, s = taps.shape[0], x.shape[1]
        up = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
        c = sum(up[:, j:j + s] * taps[j] for j in range(width))
        y = jnp.einsum("bsd,de->bse",
                       (gate_c.astype(jnp.float32) * c).astype(dt),
                       p["w_out"].astype(dt))
        y = shard(y, "batch", "seq", "embed")
    op_hook("conv.short", (x, p["w_in"], p["conv"], p["w_out"]), (y,))
    return y
