"""A fine-grained PASTA profile: back-to-back eager forwards under an
instrumenting ``Session``, closed loop with one client.

Every operator boundary emits a trace buffer of sampled access records, and
the session's event processor reduces each one on the device (per-object
counts, and a time x block hotness map over the memory pool's extent) before
its tools see it.  The window's rate is the tokens of every whole forward
over the time up to the end of the last one.

The check compares, once the window has closed:

* the logits of one forward of the window, drawn from the seed, with the
  plain reference's float32 forward over the same tokens: the RMS error and
  the largest error, each over the reference logits' RMS;
* the device-reduced per-object counts and hotness maps of trace buffers
  drawn from the seed with a plain numpy reduction of the same records:
  entries that differ (exact, limit 0).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import stats, traffic

UNIT_SHIFT, BLOCK_SHIFT = 9, 12          # 512 B units, 2 MiB blocks
BLOCK_BYTES = 1 << (UNIT_SHIFT + BLOCK_SHIFT)
POOL_BASE = 2 * 1024 * 1024              # the pool's first address
#: token batches of set-up are drawn apart from the window's
SIZING, PLAIN, WARM = 1 << 20, (1 << 20) + 1, (1 << 20) + 2


class _Clock:
    """The deterministic time source: the operator index within the
    current forward."""

    def __init__(self):
        self.n = 0

    def __call__(self) -> float:
        self.n += 1
        return float(self.n - 1)


class _Keeper:
    """Reservoir-samples trace buffers (from the seed) before the processor
    drops their records, and tallies every buffer after it."""

    def __init__(self, seed: int, k: int):
        self.rng = traffic.rng(seed, traffic.SAMPLE, 1)
        self.k = k
        self.kept: list = []
        self.seen = 0
        self.active = False
        self.buffers: list = []     # (n_records, n_objects, analysis_s)
        self._n: dict = {}

    def before(self, batch) -> None:
        from repro.core.events import EventKind
        if not self.active:
            return
        for i in batch.rows(EventKind.TRACE_BUFFER):
            attrs = batch.attrs_at(int(i))
            self._n[id(attrs)] = len(attrs["records"])
            self.seen += 1
            j = (len(self.kept) if len(self.kept) < self.k
                 else int(self.rng.integers(0, self.seen)))
            if j < self.k:
                item = (attrs, np.array(attrs["records"]),
                        float(attrs["time"]))
                if j == len(self.kept):
                    self.kept.append(item)
                else:
                    self.kept[j] = item

    def after(self, batch) -> None:
        from repro.core.events import EventKind
        if not self.active:
            return
        for i in batch.rows(EventKind.TRACE_BUFFER):
            a = batch.attrs_at(int(i))
            self.buffers.append((self._n.pop(id(a)),
                                 len(a.get("objects") or ()),
                                 float(a["analysis_s"])))


def _session(ctx, handler, hotness, clock):
    import repro.core as pasta
    mix = ctx.mix
    s = pasta.Session(tools=mix["tools"], handler=handler, instrument=True,
                      fine=True, stride=mix["stride"], hotness=hotness,
                      device_analysis=mix["device_analysis"],
                      name="bench.profile", time_source=clock)
    s.instrumenter.max_records = mix["max_records_per_op"]
    return s


def setup(ctx, trace: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.core.handler import EventHandler
    from repro.models import forward
    from repro.models.config import ModelConfig

    c, mix, seed = ctx.config["program"], ctx.mix, ctx.seed
    cfg = ModelConfig(**c)
    params = ctx.reference.init_weights(c, seed)
    jax.block_until_ready(params)
    shape = (mix["batch"], mix["seq"])

    def tokens(i):
        return jnp.asarray(traffic.token_batch(seed, i, *shape,
                                               c["vocab_size"]))

    clock = _Clock()

    # sizing forward: warms every eager op, and measures the address extent
    # of the pool that the hotness map covers
    sizing = _session(ctx, EventHandler(), None, clock)
    with sizing:
        jax.block_until_ready(forward(params, tokens(SIZING), cfg)[0])
    extent = max(o.base + o.size for o in sizing.pool.objects.values())
    n_ops = clock.n
    sizing.close()
    del sizing
    gc.collect()
    hotness = {"base": POOL_BASE,
               "n_blocks": -(-(extent - POOL_BASE) // BLOCK_BYTES),
               "n_tbins": mix["hotness_tbins"], "t_max": float(n_ops)}

    handler = EventHandler()
    keeper = _Keeper(seed, mix["check_buffers"])
    handler.subscribe_batch(keeper.before)
    session = _session(ctx, handler, hotness, clock)
    handler.subscribe_batch(keeper.after)
    state = {"cfg": cfg, "params": params, "tokens": tokens,
             "session": session, "keeper": keeper, "hotness": hotness,
             "clock": clock, "forward": forward}
    # warm forwards under the window's own session: the reduction kernels
    # compile here for this hotness map.  From its second forward on, the
    # window holds one forward's logits (the check's sample) while the next
    # runs, one more live tensor in the pool and so maybe a longer padded
    # object table; the second warm forward runs in that state too
    held = _forward(state, WARM)
    _forward(state, WARM + 1)
    del held
    if trace:
        state["plain_s"] = _plain_forward_s(state)
    ctx.say(f"[profile] hotness={hotness} ops_per_forward={n_ops}")
    return state


def _forward(state, i):
    import jax
    state["clock"].n = 0        # every forward bins its operators alike
    with state["session"]:
        logits, _ = state["forward"](state["params"], state["tokens"](i),
                                     state["cfg"])
        jax.block_until_ready(logits)
    return logits


def _plain_forward_s(state, reps: int = 5) -> float:
    """Seconds per plain jitted forward of the same shape (``reps`` timed
    together on the host clock)."""
    import jax
    fwd = jax.jit(lambda p, t: state["forward"](p, t, state["cfg"])[0])
    x = state["tokens"](PLAIN)
    jax.block_until_ready(fwd(state["params"], x))
    t = time.perf_counter()
    for _ in range(reps):
        out = fwd(state["params"], x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps


def window(ctx, state, seconds: float) -> dict:
    import jax
    mix, keeper = ctx.mix, state["keeper"]
    pick = traffic.rng(ctx.seed, traffic.SAMPLE, 2)
    keeper.active = True
    n, kept = 0, None
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation("bench.forward"):
                logits = _forward(state, n)
            n += 1
            if pick.random() * n < 1.0:     # reservoir of one, from the seed
                kept = (n - 1, logits)
            del logits
            t1 = time.perf_counter()
            if t1 - t0 >= seconds:
                break
    keeper.active = False
    state["kept_logits"] = kept
    tokens = n * mix["batch"] * mix["seq"]
    elapsed = t1 - t0
    analysis_s = sum(b[2] for b in keeper.buffers)
    record = {"forwards": n, "tokens": tokens, "window_s": elapsed,
              "analysis_s": analysis_s, "buffers": keeper.buffers,
              "plain_forward_s": state.get("plain_s"),
              "batch": mix["batch"], "seq": mix["seq"],
              "hotness": state["hotness"]}
    return {"e2e": {"profiled_tokens_per_s": stats.rate(tokens, t0, t1)},
            "attempted": n, "failed": 0, "record": record,
            "summary": {"forwards": n, "tokens": tokens,
                        "window_s": elapsed, "trace_buffers":
                        len(keeper.buffers), "analysis_s": analysis_s,
                        "kept_forward": kept[0]}}


def reduce_records(records, times, objects, hotness) -> tuple:
    """Plain numpy reduction of access records: per-object counts over the
    half-open ranges ``objects``, and the [time bin, 2 MiB block] map."""
    a = np.asarray(records, np.int64)
    starts = np.asarray([o[0] for o in objects], np.int64)
    ends = np.asarray([o[1] for o in objects], np.int64)
    idx = np.searchsorted(starts, a, side="right") - 1
    hit = (idx >= 0) & (a < ends[np.maximum(idx, 0)])
    counts = np.bincount(idx[hit], minlength=len(starts))
    nt, nb = hotness["n_tbins"], hotness["n_blocks"]
    tb = np.minimum((np.asarray(times) / hotness["t_max"] * nt)
                    .astype(np.int64), nt - 1)
    blk = (a - hotness["base"]) // BLOCK_BYTES
    ok = (blk >= 0) & (blk < nb)
    hot = np.zeros((nt, nb), np.int64)
    np.add.at(hot, (tb[ok], blk[ok]), 1)
    return counts, hot


def logit_errors(ref, c, weights, tokens, logits=None, control=None):
    """(RMS error, largest error), each over the reference logits' RMS, of
    ``logits`` against the reference forward, one row at a time.  With
    ``logits=None`` the reference itself computed with matmul operands in
    ``control`` (a lower type) stands in for the program."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def row(w, t, got):
        want = ref.logits(w, t, c)
        if got is None:
            got = ref.logits(w, t, c, control)
        err = got.astype(jnp.float32) - want
        return (jnp.sum(err * err), jnp.sum(want * want),
                jnp.max(jnp.abs(err)))

    sse = ssr = mx = 0.0
    for r in range(tokens.shape[0]):
        got = None if logits is None else logits[r:r + 1]
        a, b, m = row(weights, tokens[r:r + 1], got)
        sse, ssr, mx = sse + float(a), ssr + float(b), max(mx, float(m))
    n = tokens.size * c["vocab_size"]
    scale = np.sqrt(ssr / n)
    return float(np.sqrt(sse / n) / scale), float(mx / scale)


def check(ctx, state, win) -> list:
    import jax
    c, keeper, hp = ctx.config["program"], state["keeper"], state["hotness"]
    count_bad = hot_bad = 0
    for attrs, records, t in keeper.kept:
        counts, hot = reduce_records(records, np.full(len(records), t),
                                     attrs["objects"], hp)
        count_bad += int(np.sum(np.asarray(attrs["object_counts"]) != counts))
        hot_bad += int(np.sum(np.asarray(attrs["hotness_map"]) != hot))
    if not keeper.kept:                 # nothing reduced: nothing is right
        count_bad = hot_bad = float("inf")
    i, logits = state.pop("kept_logits")
    tokens = state["tokens"](i)
    state["session"].close()
    for k in ("params", "session", "keeper"):
        state.pop(k)
    gc.collect()
    weights = ctx.reference.init_weights(c, ctx.seed)
    rms, mx = logit_errors(ctx.reference, c, weights, tokens, logits)
    del weights, logits
    gc.collect()
    jax.clear_caches()
    lim = ctx.limits
    return [
        {"name": "logit_rms_err", "value": rms, "limit": lim["logit_rms_err"]},
        {"name": "logit_max_err", "value": mx, "limit": lim["logit_max_err"]},
        {"name": "count_mismatch", "value": count_bad,
         "limit": lim["count_mismatch"]},
        {"name": "hotness_mismatch", "value": hot_bad,
         "limit": lim["hotness_mismatch"]},
    ]


def control(ctx, dtype) -> dict:
    """The checks' numbers with the reference at ``dtype`` matmul operands
    in the program's place, on the tokens of the window's first forward."""
    c, mix = ctx.config["program"], ctx.mix
    weights = ctx.reference.init_weights(c, ctx.seed)
    import jax.numpy as jnp
    tokens = jnp.asarray(traffic.token_batch(ctx.seed, 0, mix["batch"],
                                             mix["seq"], c["vocab_size"]))
    rms, mx = logit_errors(ctx.reference, c, weights, tokens, None, dtype)
    return {"logit_rms_err": rms, "logit_max_err": mx}
