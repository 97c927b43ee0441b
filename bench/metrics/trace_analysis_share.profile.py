"""Share of the window spent in the event processor's trace reduction: the
sum of the ``analysis_s`` the processor stamps on each of the window's trace
buffers (host clock around each device reduction), over the window."""


def read(rec):
    w = rec["window"]["record"]
    if not w["buffers"]:
        return None
    return 100.0 * w["analysis_s"] / w["window_s"]
