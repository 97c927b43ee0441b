"""Operations and bytes the benchmark's work requires, from shapes alone.

Model FLOPs count what the model needs, not what a program executes:
matmuls (2 per multiply-add), causal attention over the keys each query
sees, and the LM head.  Recomputation, padding and masked-out scores do not
count, so a utilization built on them cannot pass 100% by double counting.

Trace-kernel costs follow the padding rules of ``repro.kernels.ops`` (copied
here so that no program change can move the yardstick): a call reads every
padded record and table entry and writes its padded outputs.  A histogram
needs one increment per record, so the bytes bound it.
"""

from __future__ import annotations

# tile sizes of the trace kernels (repro.kernels.trace_aggregate / hotness)
HIST_BLOCK_T, HIST_BLOCK_K = 2048, 512
HOT_BLOCK_T, HOT_BLOCK_B = 1024, 512
FUSE_BLOCK_T, FUSE_BLOCK_K = 1024, 128
I32 = 4


def padded(n: int, tile: int) -> int:
    """``n`` rounded up to ``tile`` times a power of two."""
    return tile * (1 << max(-(-n // tile) - 1, 0).bit_length())


def _tbins_padded(n_tbins: int) -> int:
    return n_tbins + (-n_tbins) % 8


def _blocks_padded(n_blocks: int) -> int:
    return n_blocks + (-n_blocks) % HOT_BLOCK_B


# ------------------------------------------------------------ dense LMs
def dense_matmul_params(c: dict) -> int:
    """Weights that take part in a matmul per token, LM head included."""
    d, q, kv, f = (c["d_model"], c["n_heads"] * c["head_dim"],
                   c["n_kv_heads"] * c["head_dim"], c["d_ff"])
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return c["n_layers"] * per_layer + d * c["vocab_size"]


def dense_forward_flops_per_token(c: dict, seq: int) -> float:
    """Forward FLOPs per token of a causal sequence of ``seq`` tokens: the
    matmuls, plus QK^T and AV over the (seq + 1) / 2 keys a query sees on
    average."""
    q = c["n_heads"] * c["head_dim"]
    attn = 2 * 2 * q * (seq + 1) / 2 * c["n_layers"]
    return 2.0 * dense_matmul_params(c) + attn


def dense_train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and backward (twice the forward), recomputation excluded."""
    return 3.0 * dense_forward_flops_per_token(c, seq)


def dense_decode_flops(c: dict, context: int) -> float:
    """FLOPs of one decoded token that attends to ``context`` keys."""
    q = c["n_heads"] * c["head_dim"]
    return 2.0 * dense_matmul_params(c) + 2 * 2 * q * context * c["n_layers"]


def dense_kv_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    """Bytes of K and V one token holds in the cache, all layers."""
    return 2 * c["n_layers"] * c["n_kv_heads"] * c["head_dim"] * itemsize


# --------------------------------------------------------- trace kernels
def object_histogram_cost(n_records: int, n_objects: int) -> tuple:
    """(ops, bytes) of one ``object_histogram`` call."""
    n = padded(n_records, HIST_BLOCK_T)
    k = padded(n_objects, HIST_BLOCK_K)
    return float(n), float((n + 3 * k) * I32)


def hotness_cost(n_records: int, n_blocks: int, n_tbins: int) -> tuple:
    """(ops, bytes) of one ``hotness_histogram`` call: addresses and time
    bins in, the [tbins, blocks] map out."""
    n = padded(n_records, HOT_BLOCK_T)
    out = _tbins_padded(n_tbins) * _blocks_padded(n_blocks)
    return float(n), float((2 * n + out) * I32)


def trace_aggregate_cost(n_records: int, n_objects: int, n_blocks: int,
                         n_tbins: int) -> tuple:
    """(ops, bytes) of one fused ``trace_aggregate`` call."""
    n = padded(n_records, FUSE_BLOCK_T)
    k = padded(n_objects, FUSE_BLOCK_K)
    out = _tbins_padded(n_tbins) * _blocks_padded(n_blocks)
    return float(2 * n), float((2 * n + 3 * k + out) * I32)


def least_time(ops: float, nbytes: float, peak: dict) -> float:
    """The roofline's least time: the larger of the compute and memory
    bounds."""
    return max(ops / peak["flops_bf16"], nbytes / peak["hbm_bw"])
