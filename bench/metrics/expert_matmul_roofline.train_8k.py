"""The held experts' grouped matmuls' share of their roofline: the least
time their calls need over the device time of those calls in the trace.

The device time: every op whose reduced name (``bench/trace.py``'s
``short``: opcode, custom-call target, result type) starts with
``custom-call ragged-dot``: the XLA TPU lowering of ``jax.lax.ragged_dot``
is a Mosaic ``tpu_custom_call`` named ``ragged-dot-none`` (the matmuls)
with ``ragged-dot-metadata`` (the group tiling, a few microseconds).

The least time (``bench.flops_moe.expert_matmul_cost``) is counted from
the window's steps and the step's counter of held assignments
(``expert_counts``, summed over the expert layers): per expert layer and
step, three grouped matmuls forward, the same three again where the layer
is rematerialized (``remat`` other than ``none``: the backward pass
recomputes them, and their time is in the trace), and six backward, each
over the layer's mean held assignments and reading the held experts'
weights.  Nothing where the trace or the counter has nothing to read.
"""
import re

from bench import flops_moe
from bench.flops import least_time

RAGGED = re.compile(r"^custom-call ragged-dot")


def read(rec):
    t, w = rec.get("trace"), rec["window"]["record"]
    counts = w.get("expert_counts")
    if not t or not counts or not rec.get("peak") or not w["steps"]:
        return None
    device_s = sum(v for k, v in t["op_time"].items() if RAGGED.search(k))
    if device_s <= 0:
        return None
    c = rec["config"]["program"]
    layers = c["n_layers"] - c["n_dense_layers"]
    calls = 3 + 6 + (3 if c.get("remat", "full") != "none" else 0)
    per_call = flops_moe.expert_matmul_cost(sum(counts) / layers, c)
    least = w["steps"] * layers * calls * least_time(*per_call, rec["peak"])
    return 100.0 * least / device_s
