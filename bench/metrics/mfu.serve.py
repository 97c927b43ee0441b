"""The serving step's share of the chip's peak: the sum over the window's
ticks of each tick's least time, over the window.  A tick's least time is
the larger of its FLOPs over the bf16 peak and the bytes it must read over
the HBM bandwidth: the bf16 weights once, plus the K/V of every context it
decodes against (``bench.flops``).  Prefill is counted by its tokens, its
attention against cached prefixes left out, so the share errs low."""
from bench import flops


def read(rec):
    w, peak = rec["window"]["record"], rec.get("peak")
    if not peak or not w["tick_work"] or w["window_s"] <= 0:
        return None
    c = rec["config"]["program"]
    weights = 2 * flops.dense_matmul_params(c)
    kv = flops.dense_kv_bytes_per_token(c)
    least = 0.0
    for ctxs, prefill, _dt in w["tick_work"]:
        ops = sum(flops.dense_decode_flops(c, n) for n in ctxs)
        if prefill:
            ops += prefill * flops.dense_forward_flops_per_token(c, prefill)
        nbytes = weights + kv * sum(ctxs)
        least += flops.least_time(ops, nbytes, peak)
    return 100.0 * least / w["window_s"]
