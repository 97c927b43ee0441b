"""The trace kernels' share of their roofline: the least time the window's
reduction calls need (``bench.flops``, from each trace buffer's padded
record count, object count and hotness map), over the device time of the
kernels' programs in the trace.  Each buffer is reduced either by the fused
``trace_aggregate`` kernel or by ``object_histogram`` plus
``hotness_histogram``; the trace's program counts say how many were fused,
and the fused ones are those with the fewest objects (the routing keeps the
fused kernel's object table within its VMEM budget)."""
import re

from bench import flops

HIST = re.compile(r"object_histogram")
HOT = re.compile(r"hotness_histogram")
FUSED = re.compile(r"trace_aggregate")


def read(rec):
    t, w = rec.get("trace"), rec["window"]["record"]
    if not t or not w["buffers"] or not rec.get("peak"):
        return None
    times, counts = t["module_time"], t["module_count"]

    def total(pat, d):
        return sum(v for k, v in d.items() if pat.search(k))
    device_s = sum(total(p, times) for p in (HIST, HOT, FUSED))
    if device_s <= 0:
        return None
    n_fused = int(total(FUSED, counts))
    hp = w["hotness"]
    least = 0.0
    for j, (n, k, _) in enumerate(sorted(w["buffers"], key=lambda b: b[1])):
        if j < n_fused:
            costs = [flops.trace_aggregate_cost(n, k, hp["n_blocks"],
                                                hp["n_tbins"])]
        else:
            costs = [flops.object_histogram_cost(n, k),
                     flops.hotness_cost(n, hp["n_blocks"], hp["n_tbins"])]
        least += sum(flops.least_time(o, b, rec["peak"]) for o, b in costs)
    return 100.0 * least / device_s
