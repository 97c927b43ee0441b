"""How many times longer an instrumented forward takes than a plain jitted
forward of the same shape; both on the host clock with
``block_until_ready``, the plain one timed in set-up."""


def read(rec):
    w = rec["window"]["record"]
    if not w.get("plain_forward_s") or not w["forwards"]:
        return None
    return (w["window_s"] / w["forwards"]) / w["plain_forward_s"]
