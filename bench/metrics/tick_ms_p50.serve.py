"""Median serving tick: the harness's host clock around each
``engine.step()`` of the window, in milliseconds."""
from bench import stats


def read(rec):
    ticks = rec["window"]["record"]["ticks"]
    return 1000.0 * stats.percentile(ticks, 50) if ticks else None
