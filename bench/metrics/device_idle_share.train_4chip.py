"""Share of the window in which no operation ran on a device, from the
profiler trace, averaged over the four chips (four-chip training)."""
from bench.metrics._common import idle_share


def read(rec):
    return idle_share(rec)
