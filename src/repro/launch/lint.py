"""Static-analysis lint over the configs grid: ``python -m repro.launch.lint``.

Compiles each cell of the grid (reduced smoke configs on a virtual 2x2x2
pod x data x model mesh by default, the full production dry-run grid with
``--full``) and runs the :mod:`repro.analysis` pass suite over every
compiled artifact.  Exit status is the CI gate: non-zero when any
unsuppressed finding at or above ``--fail-on`` severity fires, so a green
baseline stays at **zero unsuppressed findings** and a sharding/overlap
regression turns the job red before it burns hardware.

Seeded-defect self-check (the lint analogue of a mutation test)::

    python -m repro.launch.lint --seed-defect reshard   # must exit non-zero
    python -m repro.launch.lint --seed-defect blocking  # must exit non-zero

``reshard`` patches the rule table to shard between-layer activations over
the tensor axis (every layer boundary then all-gathers activations the
table never intended — implicit-reshard fires); ``blocking`` compiles the
explicit blocking cross-pod gradient sync (exposed-collectives fires where
the bucketed overlap pipeline stays quiet).

Usage:
  python -m repro.launch.lint [--archs qwen3-32b,mamba2-2.7b,dbrx-132b]
  python -m repro.launch.lint --passes 'exposed-collectives:threshold_frac=0.5'
  python -m repro.launch.lint --baseline lint_baseline.json --json out.json
  python -m repro.launch.lint --write-baseline lint_baseline.json
"""

import os
import sys


def _early_devices(argv) -> int:
    """--devices must take effect before jax initializes its backend."""
    for i, a in enumerate(argv):
        if a == "--devices" and i + 1 < len(argv):
            return int(argv[i + 1])
        if a.startswith("--devices="):
            return int(a.split("=", 1)[1])
    return 8


N_DEVICES = _early_devices(sys.argv)
# a CPU virtual mesh: pinned to the host so it never contends for a chip
# that the parent process holds
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={N_DEVICES}")

import argparse      # noqa: E402
import json          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

import repro.configs as configs                             # noqa: E402
from repro import analysis                                  # noqa: E402
from repro.dist.sharding import (DEFAULT_RULES, ShardingRules,  # noqa: E402
                                 get_rules, set_mesh)
from repro.launch.mesh import make_mesh                     # noqa: E402
from repro.train import OptConfig, make_train_step, train_shardings  # noqa: E402
from repro.train.trainer import batch_shardings             # noqa: E402

#: one representative per assigned architecture family (dense / ssm / moe)
SMOKE_ARCHS = ("qwen3-32b", "mamba2-2.7b", "dbrx-132b")

#: pass spec calibrated for the reduced smoke grid.  At smoke scale every
#: individual collective looks exposed (there is almost no compute to hide
#: behind), so exposed-collectives gates on the *aggregate* DCI exposure
#: instead, counting every message (``min_bytes=0``): per-message alpha
#: latency is what bucketing removes.  On jax 0.9's HLO the modelled DCI
#: exposure of the three archs is 14.7-17.9 us with the bucketed overlap
#: pipeline and 53-60 us with the blocking sync — the 30 us budget sits
#: between them.  dtype-promotion's jaxpr floor is raised above the
#: ~32k-element dequantize upcasts the compressed sync performs on
#: purpose (a real f32 activation leak is megabytes, not kilobytes).
SMOKE_SPEC = ("exposed-collectives:link=dci,threshold_frac=1.1,"
              "min_bytes=0,total_budget_s=3e-05,"
              "implicit-reshard,"
              "dtype-promotion:min_numel_jaxpr=65536,"
              "peak-memory,host-sync")

#: rule-table patch for ``--seed-defect reshard``: sharding the
#: between-layer activations over the tensor axis forces the partitioner
#: to all-gather them at every layer boundary — traffic the default table
#: never intends, which implicit-reshard must flag
DEFECT_RULES = {"embed": "model"}


def smoke_cell(arch: str, *, overlap_sync=True, rules_patch=None,
               seq: int = 64, batch: int = 8, spec=None, baseline=None,
               label: str = "") -> analysis.Findings:
    """Compile one reduced train cell on the virtual mesh and lint it."""
    cfg = configs.reduced(configs.get(arch))
    opt_cfg = OptConfig()
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    rules = None
    if rules_patch:
        rules = ShardingRules({**DEFAULT_RULES, **rules_patch})
    set_mesh(mesh, rules)
    # compressed 4-bucket sync: at smoke scale this is the schedule where
    # blocking vs overlapped cross-pod sync separate on aggregate DCI
    # exposure (the plain schedule's ratio is too close to 1 to gate on)
    step = make_train_step(cfg, opt_cfg, overlap_sync=overlap_sync,
                           sync_compressed=True, sync_buckets=4)
    p_sh, o_sh, p_shapes, o_shapes = train_shardings(mesh, cfg, opt_cfg)
    specs = {"inputs": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
             "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    b_sh = batch_shardings(mesh, specs, include_pod=overlap_sync is None)
    fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                 out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1))
    args = (p_shapes, o_shapes, specs)
    jaxprs = []
    try:
        jaxprs.append((label or arch, fn.trace(*args).jaxpr))
    except Exception:                                       # noqa: BLE001
        pass
    compiled = fn.lower(*args).compile()
    meta = {}
    try:
        mem = compiled.memory_analysis()
        meta["measured_peak_bytes"] = float(
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0))
    except Exception:                                       # noqa: BLE001
        pass
    return analysis.run_passes(
        compiled.as_text(), spec, baseline=baseline, emit_events=False,
        mesh_axes=dict(mesh.shape), rules=get_rules(), kind="train",
        default_trip=cfg.n_layers, pods=mesh.shape.get("pod", 1),
        n_devices=N_DEVICES, jaxprs=jaxprs, meta=meta,
        label=label or f"{arch}.train.smoke")


def run_grid(archs, *, overlap_sync=True, rules_patch=None, spec=None,
             baseline=None) -> list:
    """[(label, Findings-or-None, error-or-None)] over the smoke grid."""
    out = []
    for arch in archs:
        label = f"{arch}.train.smoke"
        try:
            lint = smoke_cell(arch, overlap_sync=overlap_sync,
                              rules_patch=rules_patch, spec=spec,
                              baseline=baseline, label=label)
            out.append((label, lint, None))
        except Exception:                                   # noqa: BLE001
            out.append((label, None, traceback.format_exc()[-2000:]))
    return out


def run_full_grid(spec=None, baseline=None) -> list:
    """Lint every (arch x shape) production cell via the dry-run compiler.
    Expensive — minutes per cell at 512 virtual devices."""
    from repro.launch import dryrun                         # noqa: PLC0415
    from repro.configs.shapes import SHAPES                 # noqa: PLC0415
    out = []
    for arch in configs.ASSIGNED:
        for shape in SHAPES:
            label = f"{arch}.{shape}"
            try:
                _, lint = dryrun.run_cell(arch, shape, multi_pod=True,
                                          lint_spec=spec,
                                          lint_baseline=baseline)
                out.append((label, lint, None))
            except Exception:                               # noqa: BLE001
                out.append((label, None, traceback.format_exc()[-2000:]))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.lint",
        description="static-analysis lint over the configs grid")
    ap.add_argument("--archs", default=",".join(SMOKE_ARCHS),
                    help="comma list of archs for the smoke grid")
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual host devices (read before jax init)")
    ap.add_argument("--passes", default=None,
                    help="pass spec (default: the full suite) — e.g. "
                         "'exposed-collectives:threshold_frac=0.3,"
                         "peak-memory:budget_frac=0.8'")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON suppressing known-accepted findings")
    ap.add_argument("--write-baseline", default=None,
                    help="write a baseline accepting everything that fired, "
                         "then exit 0 (brownfield adoption)")
    ap.add_argument("--json", default=None,
                    help="write the full findings report to this path")
    ap.add_argument("--fail-on", default="warn",
                    choices=analysis.SEVERITIES,
                    help="exit non-zero on unsuppressed findings at or "
                         "above this severity")
    ap.add_argument("--overlap", default="overlap",
                    choices=("overlap", "blocking", "auto"),
                    help="cross-pod gradient sync variant to compile")
    ap.add_argument("--seed-defect", default=None,
                    choices=("reshard", "blocking"),
                    help="inject a known defect; the run MUST go red "
                         "(CI uses this to prove the lint can fail)")
    ap.add_argument("--full", action="store_true",
                    help="lint the production dry-run grid instead of the "
                         "reduced smoke grid")
    args = ap.parse_args()

    print(f"[lint] platform={jax.devices()[0].platform} with "
          f"{jax.device_count()} virtual devices (JAX_PLATFORMS=cpu)")
    overlap = {"overlap": True, "blocking": False, "auto": None}[args.overlap]
    rules_patch = None
    if args.seed_defect == "reshard":
        rules_patch = dict(DEFECT_RULES)
    elif args.seed_defect == "blocking":
        overlap = False

    if args.full:
        results = run_full_grid(spec=args.passes, baseline=args.baseline)
    else:
        results = run_grid([a.strip() for a in args.archs.split(",")
                            if a.strip()],
                           overlap_sync=overlap, rules_patch=rules_patch,
                           spec=args.passes or SMOKE_SPEC,
                           baseline=args.baseline)

    report = {"cells": [], "errors": {}}
    n_unsup = 0
    worst = None
    for label, lint, err in results:
        if lint is None:
            report["errors"][label] = err
            print(f"[lint] {label}: COMPILE ERROR\n{err}")
            continue
        cell = lint.as_dict()
        report["cells"].append(cell)
        hits = lint.unsuppressed(args.fail_on)
        n_unsup += len(hits)
        sev = lint.max_severity()
        if sev and (worst is None
                    or analysis.severity_rank(sev)
                    > analysis.severity_rank(worst)):
            worst = sev
        print(f"[lint] {label}: {len(lint.findings)} finding(s), "
              f"{len(hits)} unsuppressed >= {args.fail_on} "
              f"(suppressed {cell['n_suppressed']})")
        for f in hits:
            print(f"  [{f.severity}] {f.pass_name}: {f.message}")
            if f.fix_hint:
                print(f"      fix: {f.fix_hint}")
    report["n_unsuppressed"] = n_unsup
    report["max_severity"] = worst
    report["fail_on"] = args.fail_on

    if args.write_baseline:
        merged = analysis.Findings()
        for _, lint, _err in results:
            if lint is not None:
                merged.extend(lint.findings)
        merged.write_baseline(args.write_baseline,
                              reason="accepted by --write-baseline")
        print(f"[lint] baseline written: {args.write_baseline}")
        return 0

    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1, default=str)
        print(f"[lint] report written: {args.json}")

    if report["errors"]:
        print(f"[lint] FAIL: {len(report['errors'])} cell(s) failed to "
              f"compile")
        return 2
    if n_unsup:
        print(f"[lint] FAIL: {n_unsup} unsuppressed finding(s) at or above "
              f"{args.fail_on!r}")
        return 1
    print("[lint] OK: zero unsuppressed findings")
    return 0


if __name__ == "__main__":
    sys.exit(main())
