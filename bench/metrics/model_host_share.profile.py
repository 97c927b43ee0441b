"""Share of the window inside the harness's forwards (``bench.forward``)
and outside every span of PASTA's (``pasta.*``): the model's own eager
dispatch and its waits."""
from bench import spans


def read(rec):
    return spans.share(rec, ("bench.forward",), ("pasta.*",))
