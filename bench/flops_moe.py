"""Operations and bytes of the conv/attention models with experts
(``conv_moe``: LFM2), from shapes alone, by the rules of ``bench/flops.py``:
matmuls at 2 per multiply-add, causal attention over the keys each query
sees, the LM head, no recomputation, nothing masked out counted.

A token's experts count as the share held here: each token sends
``n_experts_active`` assignments, of which ``held / n_experts`` land on
this chip on average.
"""

from __future__ import annotations

BF16 = 2
#: the gated short convolution's taps (the published ``conv_L_cache``)
CONV_TAPS = 3


def _held(c: dict) -> int:
    return c.get("n_experts_held") or c["n_experts"]


def conv_moe_forward_flops_per_token(c: dict, seq: int) -> float:
    """Forward FLOPs per token of a causal sequence of ``seq`` tokens."""
    d, q = c["d_model"], c["n_heads"] * c["head_dim"]
    kv = c["n_kv_heads"] * c["head_dim"]
    conv = 2 * (3 * d * d + d * d) + 2 * CONV_TAPS * d
    attn = 2 * (d * q + 2 * d * kv + q * d) + 2 * 2 * q * (seq + 1) / 2
    dense_ffn = 2 * 3 * d * c["d_ff"]
    experts = (2 * d * c["n_experts"] + c["n_experts_active"] * _held(c)
               / c["n_experts"] * 2 * 3 * d * c["d_ff_expert"])
    total = 2.0 * d * c["vocab_size"]                       # the head
    for i, kind in enumerate(c["layer_pattern"][:c["n_layers"]]):
        total += attn if kind == "attn" else conv
        total += dense_ffn if i < c["n_dense_layers"] else experts
    return total


def conv_moe_train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and backward (twice the forward), recomputation excluded."""
    return 3.0 * conv_moe_forward_flops_per_token(c, seq)


def expert_matmul_cost(rows: float, c: dict) -> tuple:
    """(ops, bytes) of one grouped matmul of the held experts' SwiGLU over
    ``rows`` assignments: ``rows x d x d_ff_expert`` multiply-adds, the
    held experts' bf16 weights read once, the rows' operand and result
    read or written once."""
    d, f = c["d_model"], c["d_ff_expert"]
    ops = 2.0 * rows * d * f
    nbytes = BF16 * (_held(c) * d * f + rows * (d + f))
    return ops, float(nbytes)
