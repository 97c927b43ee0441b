"""One traced run of a cell, with PASTA's own spans read beside the
harness's reduction.

    python3 bench/split.py --workload <cell> --seed <n> --seconds <s>

Runs ``bench/run.py --trace 1`` in this process, which prints its result
line as always, with one addition: the reduction of the run's trace also
collects the program's ``pasta.*`` spans (``bench/spans.py``), which
``bench/trace.py`` does not read yet.  Then it prints one more JSON line:
the per-layer readers of the program's split (:data:`READERS`, under
``bench/metrics``) and two sums that test the spans' nesting:
``split_sum``, the first four shares added, which equals ``covered``, the
share of the window in ``bench.forward`` or any ``pasta.*`` span, when
every ``pasta.kernel.fetch`` lies in a ``pasta.dispatch`` and every program
span in ``pasta.op``, ``pasta.free`` or ``pasta.dispatch``; and
``process_trace``, the share of the window in ``pasta.process.trace``, to
set beside ``trace_analysis_share.profile`` (host clock).  Under ``spans``
it gives each program span's share of the window and count, for the spans
no reader reads (``pasta.kernel.launch``, ``pasta.tool``, ...).
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):    # run as a script
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run, spans, trace          # noqa: E402
from bench.registry import Registry          # noqa: E402

#: the four shares that split the window's host time, then the rest
READERS = ("instrument_share.profile", "processor_host_share.profile",
           "kernel_wait_share.profile", "model_host_share.profile",
           "round_trips_per_buffer.profile", "trace_pad_share.profile",
           "idle_in_pasta_share.profile")


def reduce_file(path: str, n_devices: int) -> dict:
    """``bench.trace.reduce_file``'s keys, unchanged, and
    ``bench.spans.reduce``'s beside them."""
    raw = trace.read(path)
    out = trace.reduce(raw, n_devices)
    out.update(spans.reduce(raw, spans.read(path), n_devices))
    return out


def split(reduced: dict, registry: Registry | None = None) -> dict:
    """For one reduced trace: the readers' values, ``split_sum`` and
    ``covered``, ``process_trace``, and each program span's share and
    count."""
    reg = registry or Registry()
    rec = {"trace": reduced}
    out = {name: reg.reader(name).read(rec) for name in READERS}
    shares = [out[n] for n in READERS[:4]]
    out["split_sum"] = None if None in shares else sum(shares)
    out["covered"] = spans.share(rec, ("bench.forward", "pasta.*"))
    out["process_trace"] = spans.share(rec, ("pasta.process.trace",))
    out["spans"] = {n: {"share": spans.share(rec, (n,)), "count": v["count"]}
                    for n, v in sorted((spans.host_spans(rec) or {}).items())
                    if n.startswith(spans.PREFIX)}
    return out


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--trace" in argv:
        raise SystemExit("split: every run is traced; give no --trace")
    caught: list = []

    def reduce_and_keep(path, n_devices):
        caught.append(reduce_file(path, n_devices))
        return caught[-1]

    trace.reduce_file = reduce_and_keep
    rc = run.main(argv + ["--trace", "1"])
    if rc == 0:
        print(json.dumps({"split": split(caught[-1])}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
