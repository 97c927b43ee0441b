"""Share of the window in PASTA's instrumentation: inside an operator
boundary (``pasta.op``) or a tensor's release (``pasta.free``), and outside
the event dispatch that they start (``pasta.dispatch``).  Record
generation, the sorted live-tensor table and the memory pool's bookkeeping
fall here."""
from bench import spans


def read(rec):
    return spans.share(rec, ("pasta.op", "pasta.free"), ("pasta.dispatch",))
