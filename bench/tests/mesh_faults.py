"""A ``train_mesh`` cell on a 2 x 2 data x model mesh of four virtual CPU
devices: the sound run and each fault, one JSON line apiece.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python bench/tests/mesh_faults.py <checkout> <cell> <seed> [fault ...]

A fault is one of ``bench/faults.py``'s or ``exchange_left_out`` (below);
``sound`` runs the cell as it is.  Run by ``test_train_mesh.py`` in a
process of its own, so that the device count never reaches the test
process."""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@contextlib.contextmanager
def exchange_left_out():
    """The gradient exchange between data shards left out: under FSDP each
    data shard's rows then train only the parameter and moment shards that
    it holds.  Each step runs once per data shard on that shard's rows, and
    every leaf takes each shard's slice from that shard's step (a leaf that
    no data shard splits takes the first shard's)."""
    import jax
    import jax.numpy as jnp
    import repro.train as train
    from repro.dist.sharding import get_mesh, logical
    from repro.models import param_axes
    from repro.train.optimizer import opt_state_axes

    make = train.make_train_step
    is_ax = lambda x: isinstance(x, tuple)                   # noqa: E731

    def patched(cfg, opt_cfg, **kw):
        step = make(cfg, opt_cfg, **kw)
        n = get_mesh().shape["data"]
        p_ax = param_axes(cfg)
        o_ax = opt_state_axes(p_ax, opt_cfg)

        def own(ax, *leaves):
            spec = logical(*ax, dims=leaves[0].shape)
            for a, s in enumerate(spec):
                if s == "data" or (isinstance(s, tuple) and "data" in s):
                    return jnp.concatenate(
                        [jnp.split(x, n, axis=a)[r]
                         for r, x in enumerate(leaves)], axis=a)
            return leaves[0]

        def local(p, o, b):
            rows = b["inputs"].shape[0] // n
            outs = [step(p, o, {k: v[r * rows:(r + 1) * rows]
                                for k, v in b.items()}) for r in range(n)]
            return (jax.tree.map(own, p_ax, *[x[0] for x in outs],
                                 is_leaf=is_ax),
                    jax.tree.map(own, o_ax, *[x[1] for x in outs],
                                 is_leaf=is_ax),
                    outs[0][2])
        return local

    train.make_train_step = patched
    try:
        yield
    finally:
        train.make_train_step = make


def main(argv) -> int:
    root, cell, seed = argv[0], argv[1], int(argv[2])
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import harness
    from bench.faults import planted
    from bench.registry import Registry
    reg = Registry(root)
    devices = jax.devices()[:reg.cell(cell)["chips"]]
    for fault in argv[3:]:
        if fault == "sound":
            ctx = contextlib.nullcontext()
        elif fault == "exchange_left_out":
            ctx = exchange_left_out()
        else:
            ctx = planted(fault)
        with ctx:
            out = harness.run(cell, seed, 0.3, False, devices,
                              time.perf_counter(), registry=reg,
                              log=io.StringIO())
        print(json.dumps({"fault": fault, "correct": out["correct"],
                          "checks": {k: v["value"]
                                     for k, v in out["checks"].items()},
                          "devices": len(devices)}), flush=True)
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
