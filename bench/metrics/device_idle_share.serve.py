"""Share of the window in which no operation ran on the device, from the
profiler trace (chat serving)."""
from bench.metrics._common import idle_share


def read(rec):
    return idle_share(rec)
