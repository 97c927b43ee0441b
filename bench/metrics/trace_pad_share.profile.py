"""Share of the records the trace kernels process that are padding: one
less the records over the padded records, summed over every kernel call's
``pasta.kernel.pad`` span in the window."""
from bench import spans


def read(rec):
    hs = spans.host_spans(rec)
    pad = (hs or {}).get("pasta.kernel.pad")
    if not pad or not pad["stats"].get("padded"):
        return None
    return 100.0 * (1.0 - pad["stats"]["records"] / pad["stats"]["padded"])
