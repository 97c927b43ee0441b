"""A copy of the benchmark with tiny cells added by files alone, for the
CPU tests: ``tiny_root(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` into
``tmp``, drops in a tiny configuration, two tiny mixes and their limits,
and adds their cells and configuration as entries of ``BENCHMARK.json``.
No code is added."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(HERE, "data")

#: limits set, as the cells' are, between the tiny model's sound readings
#: on the CPU and its control's (loss 1.7e-4 / 2.4e-3, gradient norm
#: 1.5e-3 / 1.0e-2, change 8.7e-4 / 3.6e-3; logits RMS 0.012 / 0.147)
LIMITS = {
    "tiny-lm.profile": {"logit_rms_err": 0.05, "logit_max_err": 0.5,
                        "count_mismatch": 0, "hotness_mismatch": 0},
    "tiny-lm.serve": {"served_logit_gap": 0.05},
    "tiny-lm.train": {"loss_gap": 1e-3, "grad_norm_gap": 5e-3,
                      "update_norm_gap": 3e-3},
}


def tiny_root(tmp: str) -> str:
    root = os.path.join(tmp, "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "*.pb"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    b = os.path.join(root, "bench")
    shutil.copy(os.path.join(DATA, "tiny-lm.json"),
                os.path.join(b, "configs", "tiny-lm.json"))
    for mix in ("tiny_profile", "tiny_train", "tiny_serve"):
        shutil.copy(os.path.join(DATA, f"{mix}.json"),
                    os.path.join(b, "traffic", f"{mix}.json"))
    spec["configs"].append({"name": "tiny-lm", "source": "test",
                            "file": "bench/configs/tiny-lm.json",
                            "reduced": [], "why": "CPU tests"})
    for cell, mix in (("tiny-lm.profile", "tiny_profile"),
                      ("tiny-lm.train", "tiny_train"),
                      ("tiny-lm.serve", "tiny_serve")):
        spec["workloads"].append({"name": cell, "config": "tiny-lm",
                                  "traffic": mix, "chips": 1, "why": "test"})
        with open(os.path.join(b, "limits", f"{cell}.json"), "w") as f:
            json.dump(LIMITS[cell], f)
    for m in spec["end_to_end"] + spec["per_layer"]:
        for kind in ("profile", "train"):
            if any(w.endswith(f".{kind}") or w.endswith(f".{kind}_fine")
                   for w in m.get("workloads", [])):
                m["workloads"].append(f"tiny-lm.{kind}")
    # the serving kind has no cell in BENCHMARK.json yet: its metrics too
    # are added as entries
    for name in ("ttft_p90_s", "itl_p95_s"):
        spec["end_to_end"].insert(-1, {
            "name": name, "unit": "s", "better": "lower", "bound": 0.25,
            "source": "host_clock", "workloads": ["tiny-lm.serve"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f, indent=1)
    return root
