"""Chip smoke test: PASTA's main path on a TPU, at full model width.

    python chip_smoke.py              # one chip: kernels, pasta, serve, train
    python chip_smoke.py --chips 4    # four chips: the sharded train step only

One process that starts no children.  It exits non-zero, and prints no
``ok`` line, unless ``jax.devices()[0].platform == "tpu"`` and every phase
passes.  Each phase prints its checks, wall time, compile time and the
device's ``peak_bytes_in_use`` on a line of its own; the last line is
``{"ok": true, "device": {"platform", "kind", "count"}}`` and nothing else.

Phases (one chip):

* ``kernels`` — the four Pallas kernels through ``repro.kernels.ops`` on
  2**20 trace records, compiled (backend ``pallas``), equal to the jnp
  oracles;
* ``pasta`` — one eager, fully instrumented stablelm-1.6b forward (published
  widths, random weights) under a fine-grained ``Session``; every trace
  buffer is reduced on the device and its per-object counts equal the
  host-resident baseline's on the same records;
* ``serve`` — ``ServeEngine`` on the same params through the calls of
  ``repro.launch.serve``; greedy tokens agree with a plain ``forward``;
* ``train`` — five paper-gpt2 (124M) train steps at sequence 1024, plus the
  HLO walker's kernel_freq on the compiled step.

Four chips: paper-gpt2 on a 2x2 data x model mesh against the same steps on
one device.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

#: trace records per kernel call in the kernels phase
N_RECORDS = 2 ** 20
#: 2 MiB hotness blocks covering stablelm-1.6b's f32 parameters (~6.6 GB)
STABLELM_BLOCKS = 3328
#: the four-chip check: per-step losses of the 2x2 mesh and of one device
#: agree within this (bf16 matmuls, f32 loss; a third of a bf16 ulp at 8-16)
SHARDED_LOSS_TOL = 0.02
#: a served greedy token that differs from the plain forward's argmax is
#: excused only where that forward's top-2 logit margin is below this
#: (bf16 activations: the serve and reference paths round differently)
BF16_MARGIN = 0.1

_compile_s = [0.0]


def _check(ok, what) -> None:
    """A smoke check that also holds under ``python -O``."""
    if not ok:
        raise AssertionError(what)


def _on_event(event: str, secs: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += secs


def init_params(cfg, seed: int = 0):
    """Random weights from ``seed``, built in one compiled program (eager
    initialisation of 1.6B parameters dispatches op by op)."""
    from repro.models import init_params as init
    return jax.jit(init, static_argnums=1)(jax.random.PRNGKey(seed), cfg)


def _peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _phase(name: str, fn, *args, **kw) -> dict:
    """Run one phase; print its result line with wall, compile and peak."""
    c0, t0 = _compile_s[0], time.perf_counter()
    out = fn(*args, **kw)
    out.update(wall_s=time.perf_counter() - t0,
               compile_s=_compile_s[0] - c0,
               peak_bytes_in_use=_peak_bytes())
    print(f"[{name}] " + json.dumps(out, default=str), flush=True)
    gc.collect()
    return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_kernels(n: int = N_RECORDS, k: int = 1024,
                  hot_blocks: int = STABLELM_BLOCKS, fused_k: int = 256,
                  fused_blocks: int = 512, n_tbins: int = 8, mm: int = 1024,
                  expect: str = "pallas", seed: int = 0) -> dict:
    """The four kernels through ``ops``, each equal to its oracle."""
    from repro.kernels import ops, ref
    from repro.kernels.instrumented_matmul import matmul_traced_ref

    be = ops.backend()
    _check(be == expect, f"kernel backend {be!r}, expected {expect!r}")
    rng = np.random.default_rng(seed)
    block = 512 << ops.BLOCK_SHIFT
    base = block
    units = lambda x: (np.asarray(x) >> ops.UNIT_SHIFT).astype(np.int32)  # noqa: E731

    def objects(kk):
        sizes = rng.integers(1, 64, kk) * 512
        starts = base + np.cumsum(np.concatenate([[0], sizes[:-1] + 4096]))
        return starts, starts + sizes

    def hits(starts, ends, nn):
        i = rng.integers(0, len(starts), nn)
        a = starts[i] + rng.integers(0, ends[i] - starts[i])
        a[::13] = ends[-1] + 4096                          # misses
        return a

    out = {"backend": be}
    t = time.perf_counter()
    starts, ends = objects(k)
    addrs = hits(starts, ends, n)
    got = ops.object_histogram(addrs, starts, ends)
    want = np.asarray(jax.jit(ref.object_histogram_ref)(
        units(addrs), units(starts), units(ends)))
    np.testing.assert_array_equal(got, want)
    out["object_histogram"] = {"n": n, "k": k, "hits": int(got.sum()),
                               "s": time.perf_counter() - t}

    t = time.perf_counter()
    addrs = base + rng.integers(0, hot_blocks * block, n)
    times = rng.random(n)
    got = ops.hotness_histogram(addrs, times, base, hot_blocks, n_tbins, 1.0)
    tb = np.minimum((times * n_tbins).astype(np.int32), n_tbins - 1)
    want = np.asarray(jax.jit(ref.hotness_histogram_ref, static_argnums=(
        3, 4, 5))(units(addrs), tb, np.int32(base >> ops.UNIT_SHIFT),
                  hot_blocks, n_tbins, ops.BLOCK_SHIFT))
    np.testing.assert_array_equal(got, want)
    _check(got.sum() == n, "hotness lost records")
    out["hotness_histogram"] = {"n": n, "n_blocks": hot_blocks,
                                "s": time.perf_counter() - t}

    t = time.perf_counter()
    _check(ops.can_fuse(fused_k, fused_blocks, n_tbins), "not fusable")
    starts, ends = objects(fused_k)
    addrs = hits(starts, ends, n)
    counts, hot = ops.trace_aggregate(addrs, times, starts, ends, base,
                                      fused_blocks, n_tbins, 1.0)
    w_counts, w_hot = jax.jit(ref.trace_aggregate_ref, static_argnums=(
        5, 6, 7))(units(addrs), tb, units(starts), units(ends),
                  np.int32(base >> ops.UNIT_SHIFT), fused_blocks, n_tbins,
                  ops.BLOCK_SHIFT)
    np.testing.assert_array_equal(counts, np.asarray(w_counts))
    np.testing.assert_array_equal(hot, np.asarray(w_hot))
    out["trace_aggregate"] = {"n": n, "k": fused_k, "n_blocks": fused_blocks,
                              "hits": int(counts.sum()),
                              "s": time.perf_counter() - t}

    t = time.perf_counter()
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (mm, mm), jnp.bfloat16)
    w = jax.random.normal(kw, (mm, mm), jnp.bfloat16)
    y, trace = ops.matmul_traced(x, w)
    y_ref, trace_ref = matmul_traced_ref(x, w)
    np.testing.assert_array_equal(np.asarray(trace), np.asarray(trace_ref))
    err = float(jnp.abs(y - y_ref).max())
    _check(err <= 1e-3 * float(jnp.abs(y_ref).max()), err)
    out["matmul_traced"] = {"shape": [mm, mm], "trace_rows": len(trace),
                            "max_abs_err": err, "s": time.perf_counter() - t}
    return out


def phase_pasta(cfg, params, seq: int = 256,
                n_blocks: int = STABLELM_BLOCKS, n_tbins: int = 8) -> dict:
    """One eager instrumented forward; device-mode trace reduction checked
    against the host-resident baseline record for record."""
    import itertools

    import repro.core as pasta
    from repro.core.events import EventKind
    from repro.core.handler import EventHandler
    from repro.core.pool import CHUNK_ALIGN
    from repro.core.processor import _host_analyze
    from repro.models import forward

    handler = EventHandler()
    kept = []

    def keep(batch):
        # subscribed before the session's processor, which drops the raw
        # records once it has reduced them
        for i in batch.rows(EventKind.TRACE_BUFFER):
            a = batch.attrs_at(int(i))
            kept.append((a, np.array(a["records"])))
    handler.subscribe_batch(keep)
    op_index = itertools.count()
    t_max = 8.0 * cfg.n_layers
    hotness = {"base": CHUNK_ALIGN, "n_blocks": n_blocks, "n_tbins": n_tbins,
               "t_max": t_max}
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, seq), 0,
                                cfg.vocab_size)
    with pasta.Session(tools="workingset,hotness,kernel_freq",
                       handler=handler, instrument=True, fine=True,
                       hotness=hotness, name="chip_smoke.pasta",
                       time_source=lambda: float(next(op_index))) as session:
        logits, _ = forward(params, tokens, cfg)
        logits = np.asarray(logits.astype(jnp.float32))
    reports = session.reports()
    session.close()
    _check(logits.shape == (1, seq, cfg.vocab_size), logits.shape)
    _check(np.isfinite(logits).all(), "non-finite logits")
    _check(bool(kept), "no trace buffers were emitted")
    n_records = 0
    for attrs, records in kept:
        _check(attrs["analysis_mode"] == "device", attrs.get("analysis_mode"))
        starts = np.asarray([o[0] for o in attrs["objects"]], np.int64)
        ends = np.asarray([o[1] for o in attrs["objects"]], np.int64)
        np.testing.assert_array_equal(attrs["object_counts"],
                                      _host_analyze(records, starts, ends))
        n_records += len(records)
    hot = reports["hotness"].data
    _check(hot["total_accesses"] > 0, "no hotness accesses")
    return {"arch": cfg.name, "tokens": seq, "trace_buffers": len(kept),
            "records": n_records,
            "max_objects": max(len(a["objects"]) for a, _ in kept),
            "hotness_accesses": hot["total_accesses"],
            "working_set_mb": reports["workingset"].data.get(
                "working_set_mb")}


def phase_serve(cfg, params, n_requests: int = 8, prompt_len=(128, 512),
                shared_prefix: int = 64, new_tokens: int = 32,
                max_slots: int = 4, seed: int = 0) -> dict:
    """Paged ``ServeEngine`` through the ``repro.launch.serve`` calls."""
    import repro.core as pasta
    from repro.launch.serve import capture_decode, drive, make_trace
    from repro.models import forward
    from repro.serve import ServeEngine, traffic
    from repro.serve.scheduler import RequestState

    trace_args = argparse.Namespace(
        seed=seed, num_requests=n_requests, rate=0.0,
        shared_prefix=shared_prefix,
        prompt_len_min=prompt_len[0] - shared_prefix,
        prompt_len=prompt_len[1] - shared_prefix)
    prompts, arrivals = make_trace(trace_args, cfg.vocab_size)
    trace = [traffic.TraceRequest(arrival_s=float(a), prompt=p,
                                  max_new_tokens=new_tokens, slo=None)
             for a, p in zip(arrivals, prompts)]
    max_seq = prompt_len[1] + new_tokens
    with pasta.Session(tools="serving,kernel_freq", name="serve") as session:
        engine = ServeEngine(cfg, params, max_seq=max_seq,
                             max_slots=max_slots, session=session,
                             request_tools="serving", rng_seed=seed)
        _check(engine.paged, "stablelm serves from the paged pool")
        wu = engine.warmup(prompt_lens=[len(p) for p in prompts])
        rids, outputs, dt = drive(engine, trace)
        capture_decode(session, engine, params)
        reports = session.reports()
    _check("serving" in reports, list(reports))
    for rid in rids:
        _check(engine.requests[rid].state is RequestState.FINISHED, rid)
        _check(len(outputs[rid]) == new_tokens, (rid, len(outputs[rid])))

    # one request against a plain forward over prompt + served output
    rid = rids[0]
    prompt = np.asarray(prompts[0])
    served = np.asarray(outputs[rid])
    full = np.concatenate([prompt, served]).astype(np.int32)[None]
    logits, _ = jax.jit(lambda p, x: forward(p, x, cfg))(params, full)
    lg = np.asarray(logits[0, len(prompt) - 1:-1].astype(jnp.float32))
    top2 = np.sort(lg, axis=-1)[:, -2:]
    margin = top2[:, 1] - top2[:, 0]
    mismatch = lg.argmax(-1) != served
    near_ties = int((mismatch & (margin < BF16_MARGIN)).sum())
    hard = np.nonzero(mismatch & (margin >= BF16_MARGIN))[0]
    _check(hard.size == 0,
           f"served tokens differ from the reference at positions "
           f"{hard.tolist()} (margins {margin[hard].tolist()})")
    n_tok = sum(len(v) for v in outputs.values())
    serving = reports["serving"].data
    return {"arch": cfg.name, "requests": len(rids), "tokens": n_tok,
            "warmup_compile_s": wu["compile_s"], "trace_wall_s": dt,
            "ttft_s": serving.get("ttft_s"), "tpot_s": serving.get("tpot_s"),
            "prefix_hit_rate": serving.get("prefix_cache", {}).get(
                "hit_rate"),
            "reference_positions": int(len(served)),
            "near_ties_excused": near_ties,
            "min_margin": float(margin.min())}


def _train_setup(cfg, seq: int, batch: int, steps: int, seed: int):
    from repro.train import DataConfig, OptConfig, make_source
    from repro.train.optimizer import init_opt_state

    opt_cfg = OptConfig(total_steps=steps, moment_dtype=cfg.opt_moment_dtype,
                        warmup_steps=2)
    params = init_params(cfg, seed)
    opt = init_opt_state(params, opt_cfg)
    source = make_source(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                    global_batch=batch, seed=seed))
    batches = [{k: jnp.asarray(v) for k, v in source.batch_at(s).items()}
               for s in range(steps)]
    return opt_cfg, params, opt, batches


def _run_steps(compiled, params, opt, batches) -> tuple:
    losses, times = [], []
    for b in batches:
        t = time.perf_counter()
        params, opt, m = compiled(params, opt, b)
        losses.append(float(m["loss"]))       # blocks until the step ends
        times.append(time.perf_counter() - t)
    return params, opt, losses, times


def phase_train(cfg, seq: int = 1024, batch: int = 8, steps: int = 5,
                seed: int = 0) -> dict:
    """``make_train_step`` for a few steps, then the compiled step through
    the HLO walker (kernel_freq)."""
    import repro.core as pasta
    from repro.core.tools.roofline import model_flops, peaks
    from repro.dist.sharding import set_mesh
    from repro.train import make_train_step

    set_mesh(None)
    opt_cfg, params, opt, batches = _train_setup(cfg, seq, batch, steps, seed)
    step = make_train_step(cfg, opt_cfg)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt, batches[0]).compile()
    params, opt, losses, times = _run_steps(compiled, params, opt, batches)
    _check(all(math.isfinite(x) for x in losses), losses)
    ln_v = math.log(cfg.vocab_size)
    _check(abs(losses[0] - ln_v) <= 1.0, (losses[0], ln_v))
    with pasta.Session(tools="kernel_freq", name="train") as session:
        stats = session.capture_compiled(compiled, label="train_step",
                                         default_trip=cfg.n_layers,
                                         steps=steps)
        kf = session.reports()["kernel_freq"].data
    _check(kf["total_invocations"] > 0, kf)
    _check(not stats.warnings, stats.warnings)
    step_s = float(np.median(times[1:])) if len(times) > 1 else times[0]
    tok_s = batch * seq / step_s
    out = {"arch": cfg.name, "seq": seq, "batch": batch, "losses": losses,
           "ln_vocab": ln_v, "kernel_freq_invocations":
               kf["total_invocations"], "hlo_flops": stats.flops,
           "median_step_s": step_s, "tokens_per_s": tok_s}
    kind = jax.devices()[0].device_kind
    if jax.devices()[0].platform == "tpu":
        out["mfu"] = (model_flops(cfg.n_params, tok_s) /
                      peaks(kind)["peak_flops"])
    return out


def phase_sharded(cfg, seq: int = 1024, batch: int = 8, steps: int = 5,
                  seed: int = 0, mesh_shape=(2, 2)) -> dict:
    """The same steps on one device and on a data x model mesh."""
    from repro.dist.sharding import set_mesh
    from repro.launch.mesh import make_mesh
    from repro.train import make_train_step, train_shardings
    from repro.train.trainer import batch_shardings

    n = mesh_shape[0] * mesh_shape[1]
    devices = jax.devices()[:n]
    _check(len(devices) == n, f"needs {n} devices, found {len(devices)}")
    set_mesh(None)
    opt_cfg, params, opt, batches = _train_setup(cfg, seq, batch, steps, seed)
    compiled = jax.jit(make_train_step(cfg, opt_cfg)).lower(
        params, opt, batches[0]).compile()
    *_, ref_losses, _ = _run_steps(compiled, params, opt, batches)
    del params, opt, compiled
    gc.collect()

    mesh = make_mesh(mesh_shape, ("data", "model"), devices=devices)
    set_mesh(mesh)
    try:
        p_sh, o_sh, _, _ = train_shardings(mesh, cfg, opt_cfg)
        _, params, opt, _ = _train_setup(cfg, seq, batch, steps, seed)
        params = jax.device_put(params, p_sh)
        opt = jax.device_put(opt, o_sh)
        b_sh = batch_shardings(mesh, batches[0])
        batches = [jax.device_put(b, b_sh) for b in batches]
        gc.collect()
        in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                  for d in devices]
        step = make_train_step(cfg, opt_cfg)
        compiled = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                           out_shardings=(p_sh, o_sh, None)).lower(
            params, opt, batches[0]).compile()
        *_, losses, _ = _run_steps(compiled, params, opt, batches)
    finally:
        set_mesh(None)
    diff = [abs(a - b) for a, b in zip(losses, ref_losses)]
    _check(max(diff) <= SHARDED_LOSS_TOL, (losses, ref_losses))
    return {"arch": cfg.name, "mesh": list(mesh_shape), "losses": losses,
            "one_device_losses": ref_losses, "max_abs_diff": max(diff),
            "tolerance": SHARDED_LOSS_TOL,
            "bytes_in_use_per_device": in_use}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded train step on a 2x2 mesh")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import setup_compile_cache
    import repro.configs as configs

    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={jax.device_count()} compile_cache={setup_compile_cache()}",
          flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    if args.chips == 4:
        _phase("sharded", phase_sharded, configs.get("paper-gpt2"))
        count = 4
    else:
        _phase("kernels", phase_kernels)
        cfg = configs.get("stablelm-1.6b")
        params = init_params(cfg)
        _phase("pasta", phase_pasta, cfg, params)
        _phase("serve", phase_serve, cfg, params)
        del params
        gc.collect()
        _phase("train", phase_train, configs.get("paper-gpt2"))
        count = jax.device_count()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
