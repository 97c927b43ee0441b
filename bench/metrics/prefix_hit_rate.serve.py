"""Share of the window's admissions that found a cached prompt prefix: the
engine's prefix-cache counters (lookups, hits), differenced over the
window."""


def read(rec):
    w = rec["window"]["record"]
    if not w["prefix_lookups"]:
        return None
    return 100.0 * w["prefix_hits"] / w["prefix_lookups"]
