"""Spreads of the end-to-end metrics over sets of runs, and the bounds they
suggest.

    python bench/spread.py set1.jsonl set2.jsonl ...

Each file holds the result lines of one set of runs of one cell (the last
line of each run's standard output).  For each metric it prints each set's
median and spread (the distance between the first and third quartile of
Python's ``statistics.quantiles(values, n=4)``, over the median), the
widest spread, and five times it, the bound this benchmark's rule gives
(never under 1%).
"""

from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import stats     # noqa: E402


def main(paths) -> int:
    by_metric: dict = {}
    for p in paths:
        with open(p) as f:
            rows = [json.loads(line) for line in f if line.startswith("{")]
        for row in rows:
            for name, m in row["metrics"].items():
                by_metric.setdefault(name, {}).setdefault(p, []).append(
                    m["value"])
    for name, sets in by_metric.items():
        widest = 0.0
        for p, values in sets.items():
            sp = stats.spread(values) if len(values) > 1 else 0.0
            widest = max(widest, sp)
            print(f"{name} {os.path.basename(p)}: n={len(values)} "
                  f"median={statistics.median(values)!r} spread={sp!r}")
        print(f"{name}: widest spread {widest!r}, bound "
              f"{max(5 * widest, 0.01)!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
