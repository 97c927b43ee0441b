"""Sweep of offered rates for a serving cell, to find the knee once.

    python bench/knee.py --workload <serving cell> --rates 1,2,4,8 \
        [--seconds 30] [--seed 7]

One process, one engine set up as the cell's run sets it up, then one
window per rate (the cell's mix with ``rate`` replaced), lowest first.
Prints one JSON line per rate: requests offered and finished, completed
output tokens per second, TTFT and inter-token percentiles, generator
lateness, and the share of requests that met TTFT <= 2 s and a p95
inter-token gap <= 0.2 s.  The knee is the highest rate whose requests all
finish in the window's drain and whose TTFT stays flat.  The benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TTFT_LIMIT, ITL_LIMIT = 2.0, 0.2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--root", default=ROOT,
                    help="the checkout whose BENCHMARK.json names the cell")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import harness, peaks, stats
    from bench.registry import Registry
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    reg = Registry(args.root)
    cell = reg.cell(args.workload)
    config = reg.config(cell["config"])
    mix = reg.traffic(cell["traffic"])
    driver = reg.driver(mix["kind"])
    devices = jax.devices()[:cell["chips"]]
    ctx = harness.Context(cell=cell, config=config, mix=dict(mix), limits={},
                          seed=args.seed,
                          reference=reg.reference(config["reference"]),
                          devices=devices,
                          peak=peaks.peaks(devices[0].device_kind)
                          if devices[0].platform == "tpu" else None)
    state = driver.setup(ctx)
    for rate in [float(r) for r in args.rates.split(",")]:
        ctx.mix = dict(mix, rate=rate)
        ctx.log = io.StringIO()
        win = driver.window(ctx, state, args.seconds)
        rec = win["record"]
        s = win["summary"]
        met = sum(t <= TTFT_LIMIT and g <= ITL_LIMIT
                  for t, g in rec["per_request"])
        out = {"rate": rate, "offered": rec["requests"],
               "finished": rec["finished"], "window_s": rec["window_s"],
               "tokens_per_s": s["tokens"] / rec["window_s"],
               "ttft_p50_s": s["ttft_p50_s"],
               "ttft_p90_s": win["e2e"].get("ttft_p90_s"),
               "itl_p50_s": s["itl_p50_s"],
               "itl_p95_s": win["e2e"].get("itl_p95_s"),
               "tick_ms_p50": 1000 * stats.percentile(rec["ticks"], 50),
               "late_p50_s": s["late_p50_s"], "late_max_s": s["late_max_s"],
               "attainment": met / rec["requests"],
               "prefix_hit_rate": s["prefix_hit_rate"]}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
