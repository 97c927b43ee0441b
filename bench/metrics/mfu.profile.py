"""The whole instrumented forward's share of the chip's bf16 peak: forward
FLOPs per token (``bench.flops``) times the window's profiled tokens per
second."""
from bench import flops
from bench.metrics._common import mfu


def read(rec):
    c, mix = rec["config"]["program"], rec["mix"]
    return mfu(rec, flops.dense_forward_flops_per_token(c, mix["seq"]))
