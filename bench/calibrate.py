"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--fault half_batch] [--seconds 1]

In one process, which starts no other: full runs of the cell (set-up, a
short window, the check) on each seed, then the control on each control
seed, the plain reference computed with the matmul operands in the next
precision below the configuration's (float8 e4m3 below bf16), in the
program's place; then each named fault planted in the program.  Prints one
JSON line per reading, and writes them all to ``chiprun_out/calibrate/``.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                              # noqa: E402
import gc                                                    # noqa: E402
import io                                                    # noqa: E402
import json                                                  # noqa: E402
import os                                                    # noqa: E402
import sys                                                   # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTROL_DTYPE = "float8_e4m3fn"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--root", default=ROOT,
                    help="the checkout whose BENCHMARK.json names the cell")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    from bench import harness, peaks
    from bench.faults import planted
    from bench.registry import Registry
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    ints = lambda s: [int(x) for x in s.split(",") if x]     # noqa: E731

    reg = Registry(args.root)
    cell = reg.cell(args.workload)
    devices = jax.devices()[:cell["chips"]]
    out_dir = os.path.join(ROOT, "chiprun_out", "calibrate")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, f"{args.workload}.jsonl"), "a")

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    def one_run(seed, what):
        t = time.perf_counter()
        log = io.StringIO()
        res = harness.run(args.workload, seed, args.seconds, False, devices,
                          t, registry=reg, log=log)
        emit({"what": what, "seed": seed,
              "checks": {k: v["value"] for k, v in res["checks"].items()},
              "correct": res["correct"], "metrics": res["metrics"],
              "memory_peak_bytes": res["device"]["memory_peak_bytes"],
              "run_s": time.perf_counter() - t,
              "log": log.getvalue()[-600:]})
        gc.collect()

    for seed in ints(args.seeds):
        one_run(seed, "program")
    mix = reg.traffic(cell["traffic"])
    config = reg.config(cell["config"])
    driver = reg.driver(mix["kind"])
    for seed in ints(args.control_seeds):
        ctx = harness.Context(
            cell=cell, config=config, mix=mix, limits={}, seed=seed,
            reference=reg.reference(config["reference"]), devices=devices,
            peak=peaks.peaks(devices[0].device_kind)
            if devices[0].platform == "tpu" else None)
        t = time.perf_counter()
        vals = driver.control(ctx, jnp.dtype(CONTROL_DTYPE))
        emit({"what": "control", "dtype": CONTROL_DTYPE, "seed": seed,
              "checks": vals, "run_s": time.perf_counter() - t})
        jax.clear_caches()
        gc.collect()
    for fault in args.fault:
        for seed in ints(args.fault_seeds):
            with planted(fault):
                one_run(seed, "fault:" + fault)
            jax.clear_caches()
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
