"""Share of the window in which no operation ran on the device, from the
profiler trace (fine-grained profile)."""
from bench.metrics._common import idle_share


def read(rec):
    return idle_share(rec)
