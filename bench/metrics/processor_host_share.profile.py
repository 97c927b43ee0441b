"""Share of the window in the event processor's host work: inside an event
dispatch (``pasta.dispatch``: normalisation, the trace reductions' padding
and launches, the tools) and outside the waits for the device's results
(``pasta.kernel.fetch``)."""
from bench import spans


def read(rec):
    return spans.share(rec, ("pasta.dispatch",), ("pasta.kernel.fetch",))
