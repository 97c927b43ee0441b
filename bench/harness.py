"""One run of one cell: set up, measure, check, report.

``run.py`` checks for the chips and calls :func:`run`; the tests call it
directly at a tiny size on the CPU.  A run goes through these stages:

1. the driver of the cell's traffic kind makes the weights from the seed on
   the device, builds the system under test and warms every shape the
   cell's traffic uses (``setup_s`` ends here);
2. the window: the driver runs the traffic for ``seconds`` and returns the
   end-to-end numbers, taken on the host clock;
3. with ``trace=1`` the window runs under the JAX profiler, and the
   per-layer readers of ``bench/metrics`` read the reduced trace and the
   driver's record instead;
4. the device's memory peak is read, then the driver frees the program's
   state and compares what the window produced with the plain reference;
   each compared number is printed beside its limit.
"""

from __future__ import annotations

import dataclasses
import gc
import glob
import os
import shutil
import sys
import tempfile
import time

from . import peaks as peaks_mod
from . import trace as trace_mod
from .registry import Registry


@dataclasses.dataclass
class Context:
    """What a driver is given."""
    cell: dict
    config: dict            # the configuration file
    mix: dict               # the traffic file
    limits: dict
    seed: int
    reference: object       # the configuration's plain reference module
    devices: list
    peak: dict | None
    log: object = sys.stderr

    def say(self, *parts) -> None:
        print(*parts, file=self.log, flush=True)


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache, so a
    run can show that nothing compiled inside its window."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")
    count = 0
    _listening = False

    @classmethod
    def listen(cls) -> None:
        if not cls._listening:
            import jax
            jax.monitoring.register_event_duration_secs_listener(cls._on)
            cls._listening = True

    @classmethod
    def _on(cls, event: str, secs: float, **kw) -> None:
        if event in cls.EVENTS:
            cls.count += 1


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the backend
    keeps no statistics, as the CPU)."""
    out = 0
    for d in devices:
        stats = d.memory_stats() or {}
        out = max(out, int(stats.get("peak_bytes_in_use", 0)))
    return out


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        devices, t_start: float, registry: Registry | None = None,
        log=sys.stderr) -> dict:
    """One run; returns the result line's object."""
    reg = registry or Registry()
    cell = reg.cell(cell_name)
    config = reg.config(cell["config"])
    mix = reg.traffic(cell["traffic"])
    on_chip = devices[0].platform == "tpu"
    ctx = Context(cell=cell, config=config, mix=mix,
                  limits=reg.limits(cell_name), seed=seed,
                  reference=reg.reference(config["reference"]),
                  devices=devices,
                  peak=peaks_mod.peaks(devices[0].device_kind)
                  if on_chip else None, log=log)
    driver = reg.driver(mix["kind"])
    CompileCounter.listen()

    state = driver.setup(ctx, trace=trace)
    setup_s = time.perf_counter() - t_start
    ctx.say(f"[bench] setup_s={setup_s!r}")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        import jax
        # host spans (TraceAnnotation) yes, the Python call tracer no: it
        # would slow the host side of the traced window
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiled_before = CompileCounter.count
    win = driver.window(ctx, state, seconds)
    win["summary"]["compiles_in_window"] = \
        CompileCounter.count - compiled_before
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        reduced = trace_mod.reduce_file(paths[0], n_devices=len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
    mem_peak = memory_peak(devices)
    ctx.say(f"[bench] window: {win['summary']}")

    checks = driver.check(ctx, state, win)
    del state
    gc.collect()

    metrics = {}
    if trace:
        rec = {"window": win, "trace": reduced, "config": config,
               "mix": mix, "peak": ctx.peak, "chips": len(devices)}
        for m in reg.metrics("per_layer", cell_name):
            value = reg.reader(m["name"]).read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        values = dict(win["e2e"], setup_s=setup_s)
        for m in reg.metrics("end_to_end", cell_name):
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}

    correct = all(c["value"] <= c["limit"] for c in checks)
    device = dict(device_info(devices), memory_peak_bytes=mem_peak)
    out = {"correct": correct, "attempted": win["attempted"],
           "failed": win["failed"], "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        out["breakdown"] = {"device_ops": reduced["top_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    for c in checks:
        ctx.say(f"[check] {c['name']} = {c['value']!r} "
                f"(limit {c['limit']!r})")
    return out
