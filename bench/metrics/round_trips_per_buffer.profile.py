"""Blocking device round trips per trace buffer: read-backs of a trace
kernel's result (``pasta.kernel.fetch``) over trace buffers reduced
(``pasta.process.trace``), in the window.  Two where the processor runs
``object_histogram`` and ``hotness_histogram``, one where it fuses them."""
from bench import spans


def read(rec):
    hs = spans.host_spans(rec)
    if hs is None or "pasta.process.trace" not in hs:
        return None
    fetches = hs.get("pasta.kernel.fetch", {"count": 0})["count"]
    return fetches / hs["pasta.process.trace"]["count"]
