"""Finds every piece of a cell by its name in ``BENCHMARK.json``.

Nothing here lists cells, mixes or metrics: a later change adds a cell by
adding files and entries, never by editing code.

* a cell: an entry of ``workloads``;
* its configuration: the ``file`` of its ``configs`` entry, whose
  ``reference`` names the plain reference ``bench/configs/<reference>.py``;
* its traffic: ``bench/traffic/<traffic>.json``, whose ``kind`` names the
  driver ``bench/drivers/<kind>.py``;
* its limits: ``bench/limits/<cell>.json``, one number per compared check;
* a per-layer metric: the reader ``bench/metrics/<metric>.py``.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        "bench_dyn_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Registry:
    """The benchmark rooted at ``root`` (a checkout, or a test's copy)."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "bench")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def _json(self, *parts) -> dict:
        with open(os.path.join(self.root, *parts)) as f:
            return json.load(f)

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return self._json(c["file"])
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return self._json("bench", "traffic", f"{name}.json")

    def limits(self, cell: str) -> dict:
        return self._json("bench", "limits", f"{cell}.json")

    def driver(self, kind: str):
        return _load_module(os.path.join(self.bench_dir, "drivers",
                                         f"{kind}.py"), "driver_" + kind)

    def reference(self, name: str):
        return _load_module(os.path.join(self.bench_dir, "configs",
                                         f"{name}.py"), "ref_" + name)

    def reader(self, metric: str):
        return _load_module(os.path.join(self.bench_dir, "metrics",
                                         f"{metric}.py"), "metric_" + metric)

    def metrics(self, section: str, cell: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries that ``cell``
        reports."""
        return [m for m in self.spec[section]
                if cell in m.get("workloads", [cell])]
