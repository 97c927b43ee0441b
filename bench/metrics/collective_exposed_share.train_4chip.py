"""Share of the window in which a collective ran on a device and no other
operation did (``bench/trace.py``'s ``collective_exposed_s``, averaged over
the chips), over the window."""


def read(rec):
    t = rec.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
