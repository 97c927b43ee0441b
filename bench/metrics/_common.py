"""Arithmetic the per-layer readers share.  Each reader takes the run's
record (``window``: the driver's record of the measured window; ``trace``:
the reduced profiler trace; ``config``, ``mix``, ``peak``, ``chips``) and
returns the metric, or ``None`` where the run has nothing to read."""

from __future__ import annotations


def idle_share(rec) -> float | None:
    t = rec.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def mfu(rec, flops_per_token: float) -> float | None:
    w = rec["window"]["record"]
    if not rec.get("peak") or w["window_s"] <= 0:
        return None
    rate = w["tokens"] / w["window_s"]
    return 100.0 * flops_per_token * rate / (rec["chips"]
                                             * rec["peak"]["flops_bf16"])
