"""The chip benchmark's one command.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` in this process, which starts no other.
It exits non-zero and prints no result unless JAX's first device is a TPU
and there are as many devices as the cell asks for.  JAX's persistent
compilation cache lives in ``.jax_cache`` at the checkout's root, so only
the first run of a cell in a checkout compiles.  The last line of standard
output is the result: one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``),
and last ``checks``, each compared number beside its limit.  Everything
else goes to standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                              # noqa: E402
import json                                                  # noqa: E402
import os                                                    # noqa: E402
import sys                                                   # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: fixed, inside the checkout: the path is part of the cache's key
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import harness
    from bench.registry import Registry
    from repro.launch.compile_cache import setup_compile_cache

    reg = Registry(ROOT)
    cell = reg.cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    setup_compile_cache()
    out = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), devices[:cell["chips"]], T_START,
                      registry=reg)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
