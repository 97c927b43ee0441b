"""Model FLOP/s utilization of the expert-share training step: training
FLOPs per token (``bench.flops_moe``: the dense parts, causal attention,
the held experts at ``k * held / E`` assignments a token, the head over the
vocabulary slice; recomputation excluded) times the window's tokens per
second, over chips times the bf16 peak."""
from bench import flops_moe
from bench.metrics._common import mfu


def read(rec):
    c, mix = rec["config"]["program"], rec["mix"]
    return mfu(rec, flops_moe.conv_moe_train_flops_per_token(c, mix["seq"]))
