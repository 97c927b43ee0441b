"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Runs end-to-end training with the PASTA tool stack attached: AOT-compiled
train step (the compiled artifact feeds the kernel/collective event source),
step-indexed data, elastic checkpoint/restart, straggler watchdog.

``--devices N`` forces N host platform devices (debug meshes on CPU) — it is
parsed and applied to XLA_FLAGS *before* jax is imported.
"""

import argparse
import os
import sys
import time


def _parse():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-gpt2")
    ap.add_argument("--reduced", action="store_true",
                    help="family-preserving tiny config (CPU demo)")
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--mesh", default="1x1",
                    help="DxM data×model mesh (e.g. 2x4) or PxDxM "
                         "pod×data×model (e.g. 2x2x2)")
    ap.add_argument("--overlap-sync", default="auto",
                    choices=("auto", "blocking", "overlap"),
                    help="cross-pod gradient sync on a PxDxM mesh: "
                         "partitioner-implicit (auto), explicit blocking "
                         "all-reduce at step end, or the bucketed "
                         "psum_start/psum_wait overlap pipeline")
    ap.add_argument("--sync-compressed", action="store_true",
                    help="int8 quantized reduce-scatter + all-gather for "
                         "the explicit cross-pod sync")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--pasta-tools", default="kernel_freq,timeline",
                    help="tool spec, e.g. 'kernel_freq,timeline'; knobs via "
                         "'name:knob=val'; '' disables")
    ap.add_argument("--inject-failure-at", type=int, default=None)
    ap.add_argument("--data", default="", help="token .bin file (synthetic "
                                               "if empty)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args()


def main():
    args = _parse()
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax
    import numpy as np

    from repro.launch.compile_cache import setup_compile_cache
    # a restarted run (same config, same mesh) skips the train-step compile
    cache_dir = setup_compile_cache()

    import repro.configs as configs
    import repro.core as pasta
    from repro.dist.sharding import set_mesh
    from repro.launch.mesh import make_mesh
    from repro.train import (OptConfig, make_train_step, train_shardings,
                             DataConfig, make_source, LoopConfig, TrainLoop,
                             checkpoint as ckpt)
    from repro.train.optimizer import init_opt_state
    from repro.train.trainer import batch_shardings
    from repro.models import init_params

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg)

    dims = tuple(int(x) for x in args.mesh.split("x"))
    axes = ("pod", "data", "model")[-len(dims):]
    mesh = make_mesh(dims, axes) if np.prod(dims) > 1 else None
    set_mesh(mesh)
    overlap_sync = {"auto": None, "blocking": False,
                    "overlap": True}[args.overlap_sync]

    with pasta.Session(tools=args.pasta_tools, name="train") as session:
        opt_cfg = OptConfig(lr=args.lr, total_steps=args.steps,
                            moment_dtype=cfg.opt_moment_dtype,
                            warmup_steps=max(2, args.steps // 20))
        step_fn = make_train_step(cfg, opt_cfg,
                                  microbatches=args.microbatches,
                                  overlap_sync=overlap_sync,
                                  sync_compressed=args.sync_compressed)

        key = jax.random.PRNGKey(args.seed)
        with pasta.region("init"):
            params = init_params(key, cfg)
            opt_state = init_opt_state(params, opt_cfg)
        if mesh is not None:
            p_sh, o_sh, _, _ = train_shardings(mesh, cfg, opt_cfg)
            params = jax.device_put(params, p_sh)
            opt_state = jax.device_put(opt_state, o_sh)
            jitted = jax.jit(step_fn, in_shardings=(p_sh, o_sh, None),
                             out_shardings=(p_sh, o_sh, None),
                             donate_argnums=(0, 1))
        else:
            jitted = jax.jit(step_fn, donate_argnums=(0, 1))

        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                          global_batch=args.global_batch, seed=args.seed,
                          frontend=cfg.frontend, d_model=cfg.d_model)
        source = make_source(dcfg, args.data or None)

        def place_batch(b):
            return {k: jax.numpy.asarray(v) for k, v in b.items()}

        start = 0
        if args.resume and args.ckpt_dir:
            last = ckpt.latest_step(args.ckpt_dir)
            if last is not None:
                start, state = ckpt.restore(args.ckpt_dir,
                                            {"params": params,
                                             "opt": opt_state})
                params, opt_state = state["params"], state["opt"]
                print(f"[train] resumed from step {start}")

        loop = TrainLoop(LoopConfig(total_steps=args.steps,
                                    ckpt_every=args.ckpt_every,
                                    ckpt_dir=args.ckpt_dir,
                                    inject_failure_at=args.inject_failure_at),
                         jitted, source, place_batch)

        def metrics_cb(step, mx):
            print(f"[train] step {step:5d} loss {mx['loss']:.4f} "
                  f"gnorm {mx['grad_norm']:.3f} lr {mx['lr']:.2e} "
                  f"({mx['tokens']:.0f} tok)")

        with pasta.region("train"):
            params, opt_state, step = loop.run(params, opt_state, start,
                                               metrics_cb)

        # post-run: capture the compiled artifact into the event stream
        # (timed: against the persistent cache, the warm-vs-cold signal)
        example = place_batch(source.batch_at(0))
        t_c = time.perf_counter()
        compiled = jitted.lower(params, opt_state, example).compile()
        compile_s = time.perf_counter() - t_c
        session.capture_compiled(compiled, label="train_step",
                                 default_trip=cfg.n_layers,
                                 steps=step - start)
        reports = session.reports()
    print("[pasta] tool reports:")
    for name, rep in reports.items():
        short = {k: v for k, v in rep.data.items()
                 if k not in ("series", "top", "by_label")}
        print(f"  {name}: {short}")
    if loop.stragglers:
        print(f"[train] straggler steps detected: {loop.stragglers}")
    print(f"[train] train_step compile_s={compile_s:.3f} "
          f"(compile cache: {cache_dir})")
    print(f"[train] done at step {step}; restarts={loop.restarts}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
