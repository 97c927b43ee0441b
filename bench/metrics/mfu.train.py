"""Model FLOP/s utilization of training: training FLOPs per token
(``bench.flops``, recomputation excluded) times the window's tokens per
second, over chips times the bf16 peak."""
from bench import flops
from bench.metrics._common import mfu


def read(rec):
    c, mix = rec["config"]["program"], rec["mix"]
    return mfu(rec, flops.dense_train_flops_per_token(c, mix["seq"]))
