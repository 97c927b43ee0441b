"""Training on a data x model mesh over the cell's chips: the program's
jitted train step with its state placed by ``train_shardings``, under
PASTA's coarse tools, as ``repro.launch.train`` runs it on a mesh.

The mix names the mesh (``mesh``: [data, model]) over ``ctx.devices``.
Set-up makes the weights from the seed directly in their shards, compiles
the step, captures it into the session, and drives it through the first
``check_steps`` steps through the window's own call and feed, reading what
the check compares, as ``train.py`` does.  The window runs whole steps
until its time has passed, one step in flight while the host makes the next
batch; where the step returns the expert counters (``expert_counts``,
``held_share``), the record keeps their means over the window's steps.

The check runs the plain reference through the same steps on the same
rows, with its weights, gradient accumulator and moments placed over the
same mesh (the reference's float32 state of a 1.6 B model would not fit one
chip), and compares the three numbers of ``train.py``'s :func:`gaps`.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import stats
from bench.drivers.train import _batch, _leaf_norms, gaps

AXES = ("data", "model")
COUNTERS = ("expert_counts", "held_share")


def _mesh(ctx):
    from repro.launch.mesh import make_mesh
    dims = tuple(ctx.mix["mesh"])
    n = int(np.prod(dims))
    if n != len(ctx.devices):
        raise ValueError(f"mesh {dims} needs {n} devices, the cell has "
                         f"{len(ctx.devices)}")
    return make_mesh(dims, AXES, devices=list(ctx.devices))


def _shardings(ctx, mesh):
    """(model config, optimizer config, parameter, moment and batch
    shardings) of the cell on ``mesh``."""
    import jax
    from repro.dist.sharding import set_mesh
    from repro.models.config import ModelConfig
    from repro.train import OptConfig, train_shardings
    from repro.train.trainer import batch_shardings
    c, mix = ctx.config["program"], ctx.mix
    set_mesh(mesh)
    cfg = ModelConfig(**c)
    opt_cfg = OptConfig(**mix["optimizer"],
                        moment_dtype=c["opt_moment_dtype"])
    p_sh, o_sh, _, _ = train_shardings(mesh, cfg, opt_cfg)
    rows = jax.ShapeDtypeStruct((mix["batch"], mix["seq"]), np.int32)
    b_sh = batch_shardings(mesh, {"inputs": rows, "labels": rows})
    return cfg, opt_cfg, p_sh, o_sh, b_sh


def _weights(ctx, p_sh):
    """The seed's weights, made in their shards."""
    import jax
    c, ref = ctx.config["program"], ctx.reference
    return jax.jit(lambda key: ref.make_weights(c, key),
                   out_shardings=p_sh)(ref.seed_key(ctx.seed))


def setup(ctx, trace: bool = False) -> dict:
    import jax
    import repro.core as pasta
    from repro.train import make_train_step
    from repro.train.optimizer import init_opt_state

    mix = ctx.mix
    mesh = _mesh(ctx)
    cfg, opt_cfg, p_sh, o_sh, b_sh = _shardings(ctx, mesh)
    params = _weights(ctx, p_sh)
    opt = jax.jit(lambda p: init_opt_state(p, opt_cfg),
                  out_shardings=o_sh)(params)
    session = pasta.Session(tools=mix["tools"], name="bench.train_mesh")
    session.__enter__()
    feed = lambda i: jax.device_put(_batch(ctx, i), b_sh)  # noqa: E731
    step = jax.jit(make_train_step(cfg, opt_cfg),
                   in_shardings=(p_sh, o_sh, b_sh),
                   out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1))
    compiled = step.lower(params, opt, feed(0)).compile()
    session.capture_compiled(compiled, label="train_step",
                             default_trip=cfg.n_layers)
    mem = compiled.memory_analysis()
    ctx.say(f"[train_mesh] mesh {dict(mesh.shape)}; compiled step per "
            f"device: arguments {mem.argument_size_in_bytes} B, "
            f"temporaries {mem.temp_size_in_bytes} B")

    norms = jax.jit(_leaf_norms)
    # the first weights are made again from the key, an argument, so that
    # one program serves every seed
    change = jax.jit(lambda p, key: _leaf_norms(jax.tree.map(
        lambda a, b: a.astype("float32") - b.astype("float32"), p,
        ctx.reference.make_weights(ctx.config["program"], key))))
    losses = []
    for i in range(mix["check_steps"]):
        params, opt, m = compiled(params, opt, feed(i))
        losses.append(float(m["loss"]))
        if i == 0:
            grad_norms = np.asarray(norms(opt["mu"])) / (1 - opt_cfg.b1)
    change_norms = np.asarray(change(params,
                                     ctx.reference.seed_key(ctx.seed)))
    ctx.say(f"[train_mesh] losses={losses}")
    return {"compiled": compiled, "params": params, "opt": opt,
            "feed": feed, "session": session, "losses": losses,
            "grad_norms": grad_norms, "change_norms": change_norms,
            "next": mix["check_steps"]}


def window(ctx, state, seconds: float) -> dict:
    import jax
    mix = ctx.mix
    compiled, params, opt = state.pop("compiled"), state.pop("params"), \
        state.pop("opt")
    feed = state["feed"]
    i = state["next"]
    n, bad, pending = 0, 0, None
    sums: dict = {}

    def retire(m):
        got = jax.device_get({k: m[k] for k in ("loss", *COUNTERS)
                              if k in m})
        for k in COUNTERS:
            if k in got:
                sums[k] = sums.get(k, 0) + np.asarray(got[k], np.float64)
        return not np.isfinite(float(got["loss"]))

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation("bench.feed"):
                b = feed(i)
            with jax.profiler.TraceAnnotation("bench.step"):
                params, opt, m = compiled(params, opt, b)
            if pending is not None:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    bad += retire(pending)
            pending, i, n = m, i + 1, n + 1
            if time.perf_counter() - t0 >= seconds:
                break
        bad += retire(pending)
    t1 = time.perf_counter()
    state.update(compiled=compiled, params=params, opt=opt)
    tokens = n * mix["batch"] * mix["seq"]
    elapsed = t1 - t0
    record = {"steps": n, "tokens": tokens, "window_s": elapsed,
              "batch": mix["batch"], "seq": mix["seq"]}
    record.update({k: (v / n).tolist() for k, v in sums.items()})
    return {"e2e": {"train_tokens_per_s": stats.rate(tokens, t0, t1)},
            "attempted": n, "failed": bad, "record": record,
            "summary": {"steps": n, "tokens": tokens, "window_s": elapsed,
                        "nonfinite": bad}}


def reference_readings(ctx, steps: int, mm_dtype=None, rows=None) -> dict:
    """The plain reference's losses, first-gradient leaf norms, and leaf
    norms of the change after ``steps`` steps on the window's first rows,
    with every array of its state placed over the cell's mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist.sharding import set_mesh
    from repro.train.trainer import batch_spec
    ref, c, mix = ctx.reference, ctx.config["program"], ctx.mix
    o = dict(mix["optimizer"])
    rows = rows or mix["reference_rows"]
    mesh = _mesh(ctx)
    _, _, p_sh, _, _ = _shardings(ctx, mesh)
    set_mesh(None)              # the reference takes no sharding hints
    # a block of rows shards over `data` where it divides, as the batch does
    row_sh = batch_spec(mesh, rows)
    fn = jax.jit(jax.value_and_grad(
        lambda w, i, l: ref.loss(w, i, l, c, mm_dtype)),
        in_shardings=(p_sh, row_sh, row_sh),
        out_shardings=(NamedSharding(mesh, P()), p_sh))
    add = jax.jit(lambda acc, g, share: jax.tree.map(
        lambda a, x: a + share * x, acc, g), donate_argnums=(0,),
        out_shardings=p_sh)
    zeros = jax.jit(lambda w: jax.tree.map(jnp.zeros_like, w),
                    out_shardings=p_sh)
    w = _weights(ctx, p_sh)
    mu, nu = zeros(w), zeros(w)
    losses = []
    for s in range(steps):
        b = _batch(ctx, s)
        n = b["inputs"].shape[0]
        total, acc = 0.0, zeros(w)
        for r in range(0, n, rows):
            l, g = fn(w, b["inputs"][r:r + rows], b["labels"][r:r + rows])
            share = min(rows, n - r) / n
            total += float(l) * share
            acc = add(acc, g, share)
            del g
        losses.append(total)
        step = jax.jit(lambda w, g, mu, nu, s=s: ref.adamw_step(
            w, g, mu, nu, s + 1, o), donate_argnums=(0, 1, 2, 3),
            out_shardings=(p_sh,) * 4)
        w, mu, nu, gclip = step(w, acc, mu, nu)
        if s == 0:
            grad_norms = np.asarray(_leaf_norms(gclip))
        del acc, gclip
    w0 = _weights(ctx, p_sh)
    change = np.asarray(_leaf_norms(jax.tree.map(jnp.subtract, w, w0)))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def check(ctx, state, win) -> list:
    import jax
    from repro.dist.sharding import set_mesh
    prog = {k: state[k] for k in ("losses", "grad_norms", "change_norms")}
    state["session"].__exit__(None, None, None)
    state["session"].close()
    for k in ("compiled", "params", "opt", "session", "feed"):
        state.pop(k, None)
    gc.collect()
    try:
        ref = reference_readings(ctx, ctx.mix["check_steps"])
    finally:
        set_mesh(None)
    jax.clear_caches()
    g = gaps(prog, ref)
    ctx.say(f"[train_mesh] program losses {prog['losses']} reference "
            f"{ref['losses']}")
    return [{"name": k, "value": v, "limit": ctx.limits[k]}
            for k, v in g.items()]


def control(ctx, dtype) -> dict:
    """The checks' numbers with the reference at ``dtype`` matmul operands
    in the program's place."""
    from repro.dist.sharding import set_mesh
    steps = ctx.mix["check_steps"]
    try:
        return gaps(reference_readings(ctx, steps, mm_dtype=dtype),
                    reference_readings(ctx, steps))
    finally:
        set_mesh(None)
