"""Share of the window spent reading the trace kernels' results back
(``pasta.kernel.fetch``): the wait for the device, then the copy."""
from bench import spans


def read(rec):
    return spans.share(rec, ("pasta.kernel.fetch",))
