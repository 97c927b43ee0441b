"""Training under PASTA's coarse tools: the program's jitted train step in a
``Session`` that records kernel frequencies and the timeline, as
``repro.launch.train`` runs it.

Set-up builds one object, the compiled step with its state, captures the
compiled program into the session (the HLO walker), and drives it from the
seed through the first ``check_steps`` steps through the window's own call
and feed, on token rows that all differ.  It reads from them what the check
compares: each step's loss, the per-leaf norm of the first gradient as the
optimizer got it (Adam's first moment after one step, over ``1 - b1``), and
the per-leaf norm of the parameters' change after the last of them.  The
window then carries on with the same state: whole steps until its time has
passed, one step in flight while the host makes the next batch.

The check runs the plain reference through the same steps on the same rows
and compares, each by its worst case:

* ``loss_gap``: |loss - reference loss| / reference loss, over the steps;
* ``grad_norm_gap``: |norm - reference norm| / max(reference norm, median
  reference leaf norm), over the leaves;
* ``update_norm_gap``: the same for the parameters' change, over the leaves
  whose reference gradient is at least a thousandth of the median leaf's
  (a leaf with none moves by round-off alone).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import stats, traffic

MOVED = 1e-3            # a leaf counts for the change at this share of the
                        # median leaf's reference gradient norm


def _batch(ctx, i: int) -> dict:
    import jax.numpy as jnp
    mix = ctx.mix
    t = traffic.token_batch(ctx.seed, i, mix["batch"], mix["seq"] + 1,
                            ctx.config["program"]["vocab_size"])
    return {"inputs": jnp.asarray(t[:, :-1]), "labels": jnp.asarray(t[:, 1:])}


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def setup(ctx, trace: bool = False) -> dict:
    import jax
    import repro.core as pasta
    from repro.models.config import ModelConfig
    from repro.train import OptConfig, make_train_step
    from repro.train.optimizer import init_opt_state

    c, mix = ctx.config["program"], ctx.mix
    cfg = ModelConfig(**c)
    opt_cfg = OptConfig(**mix["optimizer"], moment_dtype=c["opt_moment_dtype"])
    params = ctx.reference.init_weights(c, ctx.seed)
    opt = jax.jit(lambda p: init_opt_state(p, opt_cfg))(params)
    session = pasta.Session(tools=mix["tools"], name="bench.train")
    session.__enter__()
    step = jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=(0, 1))
    compiled = step.lower(params, opt, _batch(ctx, 0)).compile()
    session.capture_compiled(compiled, label="train_step",
                             default_trip=cfg.n_layers)
    mem = compiled.memory_analysis()
    ctx.say(f"[train] compiled step: arguments {mem.argument_size_in_bytes}"
            f" B, temporaries {mem.temp_size_in_bytes} B")

    norms = jax.jit(_leaf_norms)
    # the first weights are made again from the key, an argument, so that
    # one program serves every seed
    change = jax.jit(lambda p, key: _leaf_norms(jax.tree.map(
        lambda a, b: a.astype("float32") - b.astype("float32"), p,
        ctx.reference.make_weights(c, key))))
    losses = []
    for i in range(mix["check_steps"]):
        params, opt, m = compiled(params, opt, _batch(ctx, i))
        losses.append(float(m["loss"]))
        if i == 0:
            grad_norms = np.asarray(norms(opt["mu"])) / (1 - opt_cfg.b1)
    change_norms = np.asarray(change(params,
                                     ctx.reference.seed_key(ctx.seed)))
    ctx.say(f"[train] losses={losses}")
    return {"compiled": compiled, "params": params, "opt": opt,
            "session": session, "losses": losses, "grad_norms": grad_norms,
            "change_norms": change_norms, "next": mix["check_steps"]}


def window(ctx, state, seconds: float) -> dict:
    import jax
    mix = ctx.mix
    compiled, params, opt = state.pop("compiled"), state.pop("params"), \
        state.pop("opt")
    i = state["next"]
    n, bad, pending = 0, 0, None
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation("bench.feed"):
                b = _batch(ctx, i)
            with jax.profiler.TraceAnnotation("bench.step"):
                params, opt, m = compiled(params, opt, b)
            if pending is not None:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    bad += not np.isfinite(float(pending["loss"]))
            pending, i, n = m, i + 1, n + 1
            if time.perf_counter() - t0 >= seconds:
                break
        bad += not np.isfinite(float(pending["loss"]))
    t1 = time.perf_counter()
    state.update(compiled=compiled, params=params, opt=opt)
    tokens = n * mix["batch"] * mix["seq"]
    elapsed = t1 - t0
    record = {"steps": n, "tokens": tokens, "window_s": elapsed,
              "batch": mix["batch"], "seq": mix["seq"]}
    return {"e2e": {"train_tokens_per_s": stats.rate(tokens, t0, t1)},
            "attempted": n, "failed": bad, "record": record,
            "summary": {"steps": n, "tokens": tokens, "window_s": elapsed,
                        "nonfinite": bad}}


def reference_readings(ctx, steps: int, mm_dtype=None, rows=None) -> dict:
    """The plain reference's losses, first-gradient leaf norms, and leaf
    norms of the change after ``steps`` steps on the window's first rows."""
    import jax
    import jax.numpy as jnp
    ref, c, mix = ctx.reference, ctx.config["program"], ctx.mix
    o = dict(mix["optimizer"])
    fn = jax.jit(jax.value_and_grad(
        lambda w, i, l: ref.loss(w, i, l, c, mm_dtype)))
    rows = rows or mix["reference_rows"]
    w = ref.init_weights(c, ctx.seed)
    mu = jax.tree.map(jnp.zeros_like, w)
    nu = jax.tree.map(jnp.zeros_like, w)
    losses = []
    for s in range(steps):
        b = _batch(ctx, s)
        n = b["inputs"].shape[0]
        total, acc = 0.0, None
        for r in range(0, n, rows):
            l, g = fn(w, b["inputs"][r:r + rows], b["labels"][r:r + rows])
            share = min(rows, n - r) / n
            total += float(l) * share
            g = jax.tree.map(lambda x: x * share, g)
            acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
        losses.append(total)
        w, mu, nu, gclip = ref.adamw_step(w, acc, mu, nu, s + 1, o)
        if s == 0:
            grad_norms = np.asarray(_leaf_norms(gclip))
        del acc, gclip
    w0 = ref.init_weights(c, ctx.seed)
    change = np.asarray(_leaf_norms(jax.tree.map(jnp.subtract, w, w0)))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def gaps(prog: dict, ref: dict) -> dict:
    """The three compared numbers of ``prog`` against ``ref`` readings."""
    k = len(ref["losses"])
    loss = max(abs(a - b) / abs(b) for a, b in
               zip(prog["losses"][:k], ref["losses"]))
    rg, pg = np.asarray(ref["grad_norms"]), np.asarray(prog["grad_norms"])
    grad = float(np.max(np.abs(pg - rg) / np.maximum(rg, np.median(rg))))
    moved = rg >= MOVED * np.median(rg)
    rc, pc = np.asarray(ref["change_norms"]), np.asarray(prog["change_norms"])
    rc, pc = rc[moved], pc[moved]
    upd = float(np.max(np.abs(pc - rc) / np.maximum(rc, np.median(rc))))
    return {"loss_gap": float(loss), "grad_norm_gap": grad,
            "update_norm_gap": upd}


def check(ctx, state, win) -> list:
    import jax
    prog = {k: state[k] for k in ("losses", "grad_norms", "change_norms")}
    state["session"].__exit__(None, None, None)
    state["session"].close()
    for k in ("compiled", "params", "opt", "session"):
        state.pop(k, None)
    gc.collect()
    ref = reference_readings(ctx, ctx.mix["check_steps"])
    jax.clear_caches()
    g = gaps(prog, ref)
    ctx.say(f"[train] program losses {prog['losses']} reference "
            f"{ref['losses']}")
    return [{"name": k, "value": v, "limit": ctx.limits[k]}
            for k, v in g.items()]


def control(ctx, dtype) -> dict:
    """The checks' numbers with the reference at ``dtype`` matmul operands
    in the program's place."""
    steps = ctx.mix["check_steps"]
    return gaps(reference_readings(ctx, steps, mm_dtype=dtype),
                reference_readings(ctx, steps))
