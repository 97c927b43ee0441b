"""Share of the device's idle time in the window during which the host is
inside a span of PASTA's (``pasta.*``), over the devices used."""
from bench import spans, trace


def read(rec):
    hs = spans.host_spans(rec)
    if hs is None:
        return None
    idle = rec["trace"]["idle_intervals"]
    busy_host = spans.union_of(hs, ("pasta.*",))
    idle_s = sum(trace.total(d) for d in idle)
    if idle_s <= 0:
        return None
    outside = sum(trace.total(trace.subtract(d, busy_host)) for d in idle)
    return 100.0 * (idle_s - outside) / idle_s
