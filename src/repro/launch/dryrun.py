import os
# a CPU virtual mesh: pinned to the host so it never contends for a chip
# that the parent process holds
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell and
extract the roofline terms from the compiled artifact.

For each cell this:
  1. builds the production mesh (16×16 single-pod / 2×16×16 multi-pod);
  2. eval_shape's params/optimizer/caches (no allocation — 1T params OK);
  3. ``jax.jit(step, in_shardings, out_shardings).lower(**input_specs)``
     then ``.compile()`` — sharding mismatches / unsupported collectives
     fail HERE, which is the point of the dry-run;
  4. prints ``memory_analysis()`` / ``cost_analysis()`` and walks the
     compiled HLO with the PASTA hlo module (kernels, collectives ×
     known_trip_count multipliers);
  5. writes results/dryrun/<arch>__<shape>__<mesh>.json for EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.configs as configs
from repro.configs.shapes import get_shape
import repro.core as pasta
from repro.core.tools import roofline as RL
from repro.dist.sharding import set_mesh
from repro.launch.mesh import make_production_mesh, mesh_name, n_chips
from repro.models.config import ModelConfig
from repro.train.optimizer import OptConfig
from repro.train import trainer

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


# ---------------------------------------------------------------------------
# input specs
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of the cell (the
    paper-workflow analogue: weak-type-correct, shardable, no allocation)."""
    b, s = shape.global_batch, shape.seq_len
    if cfg.frontend == "embed":
        mk = lambda bb, ss: jax.ShapeDtypeStruct(   # noqa: E731
            (bb, ss, cfg.d_model), jnp.bfloat16)
    else:
        mk = lambda bb, ss: jax.ShapeDtypeStruct(   # noqa: E731
            (bb, ss), jnp.int32)
    if shape.kind == "train":
        return {"inputs": mk(b, s),
                "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    if shape.kind == "prefill":
        return {"inputs": mk(b, s)}
    return {"tokens": mk(b, 1)}          # decode: one new token, cache of s


def _opt_cfg(cfg: ModelConfig) -> OptConfig:
    return OptConfig(moment_dtype=cfg.opt_moment_dtype)


def _sharded_bytes(shapes_tree, shardings_tree) -> int:
    """Exact per-device bytes of a sharded abstract tree."""
    total = 0
    for leaf, sh in zip(jax.tree.leaves(shapes_tree),
                        jax.tree.leaves(shardings_tree,
                                        is_leaf=lambda x: isinstance(
                                            x, NamedSharding))):
        n = 1
        for d in leaf.shape:
            n *= d
        shard_n = n
        if isinstance(sh, NamedSharding):
            denom = 1
            for ax in sh.spec:
                if ax is None:
                    continue
                axs = ax if isinstance(ax, tuple) else (ax,)
                for a in axs:
                    denom *= sh.mesh.shape[a]
            shard_n = n // max(denom, 1)
        total += shard_n * leaf.dtype.itemsize
    return total


# ---------------------------------------------------------------------------
# cell builders
# ---------------------------------------------------------------------------

def build_cell(cfg: ModelConfig, shape, mesh, overlap_sync=None):
    """Returns (jitted_fn, kwargs_of_ShapeDtypeStructs, meta).

    ``overlap_sync``: ``None`` keeps the partitioner-implicit pod
    reduction; ``False``/``True`` compile the explicit blocking / bucketed-
    overlap cross-pod sync (batch replicated across pods — see
    :mod:`repro.train.trainer`)."""
    set_mesh(mesh)
    meta = {"microbatches": 1}
    include_pod = overlap_sync is None
    if shape.kind == "train":
        opt_cfg = _opt_cfg(cfg)
        micro = shape.microbatches
        # keep per-microbatch batch divisible by the dp axes
        dp = mesh.shape["data"]
        if include_pod:
            dp *= mesh.shape.get("pod", 1)
        while micro > 1 and (shape.global_batch // micro) % dp:
            micro //= 2
        meta["microbatches"] = micro
        meta["overlap_sync"] = overlap_sync
        step = trainer.make_train_step(cfg, opt_cfg, microbatches=micro,
                                       overlap_sync=overlap_sync)
        p_sh, o_sh, p_shapes, o_shapes = trainer.train_shardings(
            mesh, cfg, opt_cfg)
        specs = input_specs(cfg, shape)
        b_sh = trainer.batch_shardings(mesh, specs,
                                       include_pod=include_pod)
        fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, None),
                     donate_argnums=(0, 1))
        args = (p_shapes, o_shapes, specs)
        meta["state_bytes_per_device"] = (
            _sharded_bytes(p_shapes, p_sh) + _sharded_bytes(o_shapes, o_sh))
        meta["default_trip"] = cfg.n_layers
        return fn, args, meta
    if shape.kind == "prefill":
        step = trainer.make_prefill_step(cfg)
        p_sh, c_sh, p_shapes, _c = trainer.serve_shardings(
            mesh, cfg, shape.global_batch, shape.seq_len)
        specs = input_specs(cfg, shape)
        b_sh = trainer.batch_shardings(mesh, specs)
        fn = jax.jit(step, in_shardings=(p_sh, b_sh["inputs"]),
                     out_shardings=None)
        args = (p_shapes, specs["inputs"])
        meta["state_bytes_per_device"] = _sharded_bytes(p_shapes, p_sh)
        meta["default_trip"] = cfg.n_layers
        return fn, args, meta
    # decode
    step = trainer.make_decode_step(cfg)
    p_sh, c_sh, p_shapes, c_shapes = trainer.serve_shardings(
        mesh, cfg, shape.global_batch, shape.seq_len)
    specs = input_specs(cfg, shape)
    b_sh = trainer.batch_shardings(mesh, specs)
    fn = jax.jit(step, in_shardings=(p_sh, c_sh, b_sh["tokens"]),
                 out_shardings=(None, c_sh), donate_argnums=(1,))
    args = (p_shapes, c_shapes, specs["tokens"])
    meta["state_bytes_per_device"] = (
        _sharded_bytes(p_shapes, p_sh) + _sharded_bytes(c_shapes, c_sh))
    meta["default_trip"] = cfg.n_layers
    return fn, args, meta


# ---------------------------------------------------------------------------
# run one cell
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rules_patch: dict | None = None, tag: str = "",
             cfg_overrides: dict | None = None,
             microbatches: int | None = None,
             overlap_sync: bool | None = None,
             lint_spec: str | None = None,
             lint_baseline=None) -> dict:
    import dataclasses
    cfg = configs.get(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = get_shape(shape_name)
    if microbatches is not None:
        shape = dataclasses.replace(shape, microbatches=microbatches)
    if shape_name == "long_500k" and not cfg.supports_long_context:
        return ({"arch": arch, "shape": shape_name, "status": "skipped",
                 "reason": "pure full-attention arch; 0.5M-token quadratic "
                           "attention out of assigned scope (DESIGN.md §4)"},
                None)
    mesh = make_production_mesh(multi_pod=multi_pod)
    set_mesh(mesh)
    if rules_patch:
        from repro.dist.sharding import DEFAULT_RULES, ShardingRules
        rules = ShardingRules({**DEFAULT_RULES, **rules_patch})
        set_mesh(mesh, rules)
    chips = n_chips(mesh)
    t0 = time.time()
    fn, args, meta = build_cell(cfg, shape, mesh, overlap_sync=overlap_sync)
    if isinstance(args, tuple):
        lowered = fn.lower(*args)
    else:
        lowered = fn.lower(**args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    # --- analyses ----------------------------------------------------------
    try:
        mem = compiled.memory_analysis()
        mem_d = {k: int(getattr(mem, k)) for k in
                 ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes") if hasattr(mem, k)}
    except Exception as e:                                  # noqa: BLE001
        mem_d = {"error": str(e)}
    try:
        cost = compiled.cost_analysis() or {}
        cost_d = {k: float(v) for k, v in cost.items()
                  if isinstance(v, (int, float)) and k in
                  ("flops", "bytes accessed", "transcendentals")}
    except Exception as e:                                  # noqa: BLE001
        cost_d = {"error": str(e)}

    # the traced jaxpr (pre-lowering) feeds the dtype-promotion lint pass;
    # optional — some step fns may not trace standalone
    jaxprs = []
    try:
        traced = fn.trace(*args) if isinstance(args, tuple) \
            else fn.trace(**args)
        jaxprs.append((f"{arch}.{shape_name}", traced.jaxpr))
    except Exception:                                       # noqa: BLE001
        pass

    # scoped capture: the compiled artifact flows through a per-cell Session
    # (kernel/collective events -> kernel_freq tool), no ambient state;
    # static lint runs inside the same session so findings land as events
    from repro import analysis
    from repro.dist.sharding import get_rules
    text = compiled.as_text()
    with pasta.Session(tools="kernel_freq:top_k=5",
                       name=f"dryrun/{arch}/{shape_name}") as sess:
        stats = sess.capture_compiled(text, label=f"{arch}.{shape_name}",
                                      default_trip=meta["default_trip"])
        lint = analysis.run_passes(
            text, lint_spec, stats=stats, session=sess,
            baseline=lint_baseline,
            mesh_axes=dict(mesh.shape), rules=get_rules(),
            kind=shape.kind, default_trip=meta["default_trip"],
            pods=mesh.shape.get("pod", 1), n_devices=chips,
            jaxprs=jaxprs, label=f"{arch}.{shape_name}")
    kernel_freq = sess.reports()["kernel_freq"].data

    n_tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                     else 1)
    mf = RL.model_flops(cfg.n_params, n_tokens,
                        training=shape.kind == "train",
                        n_active_params=cfg.n_active_params
                        if cfg.family in ("moe", "conv_moe") else None)
    # the analytic model's target chip is named, not inherited
    rl = RL.roofline(stats.flops, stats.hbm_bytes,
                     stats.total_collective_bytes,
                     RL.peaks(RL.ANALYTIC_TARGET),
                     model_flops_per_chip=mf / chips)
    out = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name(mesh),
        "chips": chips, "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "microbatches": meta["microbatches"],
        "state_bytes_per_device": meta.get("state_bytes_per_device"),
        "memory_analysis": mem_d, "cost_analysis": cost_d,
        "hlo": {
            "flops_per_device": stats.flops,
            "hbm_bytes_per_device": stats.hbm_bytes,
            "collective_bytes_per_device": stats.collective_bytes,
            "collective_total_bytes": stats.total_collective_bytes,
            "collective_wire_bytes_per_device": stats.collective_wire_bytes,
            "collective_wire_total_bytes": stats.total_wire_bytes,
            "exposed_collective_bytes": stats.exposed_collective_bytes,
            "exposed_collective_s": stats.exposed_collective_s,
            "hidden_collective_s": stats.hidden_collective_s,
            "n_kernels": len(stats.kernel_counts),
            "n_collectives": len(stats.collective_instances),
            "top_kernels": kernel_freq["top"],
        },
        "overlap_sync": overlap_sync,
        "lint": lint.summary(),
        "model_flops_total": mf,
        "roofline": rl.as_dict(),
        "tag": tag,
    }
    return out, lint


def _print_lint(lint, min_severity: str = "info") -> None:
    for f in lint.unsuppressed(min_severity):
        print(f"  [{f.severity}] {f.pass_name}: {f.message}")
        if f.fix_hint:
            print(f"      fix: {f.fix_hint}")


def save_cell(out: dict) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    tag = f"__{out['tag']}" if out.get("tag") else ""
    name = f"{out['arch']}__{out['shape']}__{out.get('mesh', 'skip')}{tag}.json"
    path = os.path.join(RESULTS_DIR, name)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--micro", type=int, default=None,
                    help="override train microbatch count")
    ap.add_argument("--overlap-sync", default="auto",
                    choices=("auto", "blocking", "overlap"),
                    help="cross-pod gradient sync: partitioner-implicit "
                         "(auto), explicit blocking all-reduce, or the "
                         "bucketed psum_start/psum_wait overlap pipeline")
    ap.add_argument("--set", action="append", default=[],
                    help="ModelConfig override key=value (perf knobs)")
    ap.add_argument("--lint", action="store_true",
                    help="print static-analysis findings per cell (the "
                         "lint section lands in the JSON either way)")
    ap.add_argument("--lint-spec", default=None,
                    help="pass spec, e.g. "
                         "'exposed-collectives:threshold_frac=0.3,"
                         "peak-memory'")
    ap.add_argument("--lint-baseline", default=None,
                    help="baseline JSON of accepted findings to suppress")
    args = ap.parse_args()
    print(f"[dryrun] platform={jax.devices()[0].platform} with "
          f"{jax.device_count()} virtual devices (JAX_PLATFORMS=cpu): "
          f"compiles and analytic models only, no chip")

    overrides = {}
    if args.set:
        import dataclasses as _dc
        from repro.models.config import ModelConfig as _MC
        ftypes = {f.name: f.type for f in _dc.fields(_MC)}
        for kv in args.set:
            k, v = kv.split("=", 1)
            t = ftypes.get(k, "str")
            if t in ("bool", bool):
                overrides[k] = v.lower() in ("1", "true", "yes")
            elif t in ("int", int):
                overrides[k] = int(v)
            elif t in ("float", float):
                overrides[k] = float(v)
            else:
                overrides[k] = v

    cells = []
    archs = configs.ASSIGNED if args.arch is None else [args.arch]
    shapes = (["train_4k", "prefill_32k", "decode_32k", "long_500k"]
              if args.shape is None else [args.shape])
    for a in archs:
        for s in shapes:
            cells.append((a, s))

    mesh_tag = "2x16x16" if args.multi_pod else "16x16"
    for arch, shape in cells:
        tag = f"__{args.tag}" if args.tag else ""
        path = os.path.join(RESULTS_DIR,
                            f"{arch}__{shape}__{mesh_tag}{tag}.json")
        skip_path = os.path.join(RESULTS_DIR, f"{arch}__{shape}__skip{tag}.json")
        if args.skip_existing and (os.path.exists(path)
                                   or os.path.exists(skip_path)):
            print(f"[dryrun] {arch} {shape}: cached")
            continue
        lint = None
        try:
            out, lint = run_cell(
                arch, shape, args.multi_pod, tag=args.tag,
                cfg_overrides=overrides or None,
                microbatches=args.micro,
                overlap_sync={"auto": None, "blocking": False,
                              "overlap": True}[args.overlap_sync],
                lint_spec=args.lint_spec,
                lint_baseline=args.lint_baseline)
        except Exception as e:                              # noqa: BLE001
            out = {"arch": arch, "shape": shape, "mesh": mesh_tag,
                   "status": "error", "error": str(e),
                   "traceback": traceback.format_exc()[-2000:],
                   "tag": args.tag}
        p = save_cell(out)
        if out["status"] == "ok":
            r = out["roofline"]
            lt = out.get("lint", {})
            print(f"[dryrun] {arch} {shape} {out['mesh']}: OK "
                  f"compile={out['compile_s']}s "
                  f"compute={r['compute_s']:.4f}s mem={r['memory_s']:.4f}s "
                  f"coll={r['collective_s']:.4f}s -> {r['bottleneck']} "
                  f"frac={r['roofline_fraction']:.3f} "
                  f"lint={lt.get('n_unsuppressed', 0)} ({p})")
            if args.lint and lint is not None:
                _print_lint(lint)
        else:
            print(f"[dryrun] {arch} {shape}: {out['status']} "
                  f"{out.get('reason', out.get('error', ''))[:200]}")


if __name__ == "__main__":
    main()
