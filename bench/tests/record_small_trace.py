"""Record the small profiler trace that ``test_trace.py`` reduces.

    python bench/tests/record_small_trace.py [out_dir]

Run on a TPU host.  Under one profiler window it runs, in this order, each
inside a ``TraceAnnotation`` of the harness's own naming:

* ``bench.matmul``: a jitted bf16 matmul (2048 x 2048 x 2048), three times;
* ``bench.kernels``: the ``object_histogram`` and ``hotness_histogram``
  Pallas kernels through ``repro.kernels.ops`` on 2**16 records;
* ``bench.host_gap``: 50 ms of host sleep with nothing on the device.

It copies the ``.xplane.pb`` to ``out_dir`` (default
``bench/tests/data``) as ``small_trace.xplane.pb`` and prints the planes,
lines and the most frequent event names, so the reduction can be written
against what the chip really records.
"""

from __future__ import annotations

import collections
import glob
import os
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(REPO, "src"))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        print("record_small_trace: needs a TPU", file=sys.stderr)
        return 2
    mm = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    rng = np.random.default_rng(0)
    addrs = (2 << 20) + rng.integers(0, 64 << 20, 2 ** 16)
    starts = (2 << 20) + np.arange(64, dtype=np.int64) * (1 << 20)
    ends = starts + (512 << 10)
    times = rng.random(2 ** 16)
    # warm every program first: nothing compiles inside the window
    mm(a, a).block_until_ready()
    ops.object_histogram(addrs, starts, ends)
    ops.hotness_histogram(addrs, times, 2 << 20, 32, 8, 1.0)
    tmp = tempfile.mkdtemp(prefix="small_trace_")
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.matmul"):
        for _ in range(3):
            mm(a, a).block_until_ready()
    with jax.profiler.TraceAnnotation("bench.kernels"):
        ops.object_histogram(addrs, starts, ends)
        ops.hotness_histogram(addrs, times, 2 << 20, 32, 8, 1.0)
    with jax.profiler.TraceAnnotation("bench.host_gap"):
        time.sleep(0.05)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                     recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    dest = os.path.join(out_dir, "small_trace.xplane.pb")
    shutil.copy(path, dest)
    shutil.rmtree(tmp)
    print(f"wrote {dest} ({os.path.getsize(dest)} bytes)")
    pd = jax.profiler.ProfileData.from_file(dest)
    for plane in pd.planes:
        print("PLANE", plane.name, dict(plane.stats))
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            print("  LINE", repr(line.name), len(evs),
                  names.most_common(12))
            for e in evs[:2]:
                print("     ev", e.name, e.start_ns, e.duration_ns,
                      dict(e.stats))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else os.path.join(HERE, "data")))
