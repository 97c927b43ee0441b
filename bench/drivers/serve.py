"""Chat serving: the program's paged ``ServeEngine`` under a ``Session``
with the serving and kernel-frequency tools and a child session per request,
driven open loop.

Requests arrive as a Poisson process at the mix's fixed ``rate``.  Every
prompt is one system prompt shared by all requests plus a user part; user
and output lengths are lognormal and clipped.  The set of sizes and
inter-arrival gaps is the same for every seed (drawn from stream 0), and the
seed shuffles them and draws the tokens, so every seed offers the same work.
Outputs are greedy.

The window admits every request due within ``seconds``, ticking
``engine.step()`` and stamping each new token with the host time the tick
returned; it then keeps ticking, at most ``drain_s`` more, until every
request it admitted has finished.  Time to first token is measured from
each request's due time, so a stall that delays later submissions counts.
The tails are over every request and every inter-token gap.

The check takes a sample of finished requests drawn from the seed, the
longest among them, and runs the plain reference once over each prompt with
its served tokens: ``served_logit_gap`` is the widest gap by which a served
token's reference logit lies below the reference's best at that position.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import stats, traffic


def plan(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """(due_s, prompt, max_new_tokens) of every request due in the window."""
    n = max(int(round(mix["rate"] * seconds)), 1)
    fixed = traffic.rng(0, traffic.LENGTHS, n)
    user = traffic.lognormal_lengths(fixed, n, mix["user_median"],
                                     mix["user_sigma"], mix["user_min"],
                                     mix["user_max"])
    out = traffic.lognormal_lengths(fixed, n, mix["out_median"],
                                    mix["out_sigma"], mix["out_min"],
                                    mix["out_max"])
    gaps = fixed.exponential(1.0 / mix["rate"], n)
    g = traffic.rng(seed, traffic.ARRIVALS, n)
    order = g.permutation(n)
    user, out = user[order], out[g.permutation(n)]
    due = np.cumsum(gaps[g.permutation(n)])
    due = due * (seconds / max(due[-1], 1e-9)) * (n - 0.5) / n
    toks = traffic.rng(seed, traffic.PROMPTS, n)
    system = traffic.zipf_tokens(toks, (mix["system_prompt"],), vocab)
    reqs = []
    for i in range(n):
        p = np.concatenate([system, traffic.zipf_tokens(toks, (int(user[i]),),
                                                        vocab)])
        reqs.append((float(due[i]), p, int(out[i])))
    return reqs


def _max_seq(mix) -> int:
    return mix["system_prompt"] + mix["user_max"] + mix["out_max"]


def _warm_traffic(engine, mix: dict, vocab: int) -> None:
    """Runs the paths the engine's own warm-up leaves cold (the scatter of a
    cold prefill into the block pool, suffix prefill against a cached
    prefix) once for each power-of-two bucket of the mix's user lengths:
    a prompt that misses the prefix store, then one that hits it."""
    from repro.serve import SamplingParams
    g = traffic.rng(0, traffic.PROMPTS, 1 << 20)
    system = traffic.zipf_tokens(g, (mix["system_prompt"],), vocab)
    n = 8
    while n // 2 < mix["user_max"]:
        user = min(max(n, mix["user_min"]), mix["user_max"])
        other = traffic.zipf_tokens(g, (mix["system_prompt"],), vocab)
        for head in (other, system):
            prompt = np.concatenate([head, traffic.zipf_tokens(g, (user,),
                                                               vocab)])
            engine.submit(prompt, SamplingParams(max_new_tokens=2,
                                                 temperature=0.0))
            while engine.has_work:
                engine.step()
        n *= 2


def setup(ctx, trace: bool = False) -> dict:
    import jax
    import repro.core as pasta
    from repro.models.config import ModelConfig
    from repro.serve import ServeEngine

    c, mix = ctx.config["program"], ctx.mix
    cfg = ModelConfig(**c)
    params = ctx.reference.init_weights(c, ctx.seed)
    jax.block_until_ready(params)
    session = pasta.Session(tools=mix["tools"], name="bench.serve")
    session.__enter__()
    engine = ServeEngine(cfg, params, max_seq=_max_seq(mix),
                         max_slots=mix["max_slots"], session=session,
                         request_tools=mix["request_tools"],
                         rng_seed=ctx.seed & 0x7FFFFFFF,
                         prefix_block=mix["block_size"],
                         n_blocks=mix["n_blocks"],
                         prefill_chunk=mix.get("prefill_chunk"))
    lo = mix["user_min"]            # a prefix hit leaves the user part
    hi = mix["system_prompt"] + mix["user_max"]
    wu = engine.warmup(prompt_lens=range(lo, hi + 1, 16))
    _warm_traffic(engine, mix, c["vocab_size"])
    ctx.say(f"[serve] warmup {wu['compile_s']:.3f} s, "
            f"{len(wu['warmed'])} programs; pool {engine.pool_stats()}")
    return {"engine": engine, "session": session, "params": params}


def window(ctx, state, seconds: float) -> dict:
    import jax
    from repro.serve import SamplingParams
    mix, engine = ctx.mix, state["engine"]
    reqs = plan(mix, ctx.seed, seconds, ctx.config["program"]["vocab_size"])
    pc = engine.prefix_cache
    lookups0, hits0 = (pc.lookups, pc.hits) if pc is not None else (0, 0)
    rid_of, times, toks, lateness, ticks = {}, {}, {}, [], []
    tick_work = []              # (decode contexts, prefill tokens) per tick
    i = 0
    t0 = time.perf_counter()
    deadline = seconds + mix["drain_s"]
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            now = time.perf_counter() - t0
            while i < len(reqs) and reqs[i][0] <= now:
                due, prompt, n_out = reqs[i]
                with jax.profiler.TraceAnnotation("bench.submit"):
                    rid = engine.submit(prompt, SamplingParams(
                        max_new_tokens=n_out, temperature=0.0))
                rid_of[rid] = i
                times[rid], toks[rid] = [], []
                lateness.append(time.perf_counter() - t0 - due)
                i += 1
            if engine.has_work:
                ts = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.tick"):
                    out = engine.step()
                te = time.perf_counter()
                ticks.append(te - ts)
                t = te - t0
                ctxs = []
                for rid, tok in out["new_tokens"]:
                    if rid in times:
                        times[rid].append(t)
                        toks[rid].append(int(tok))
                    req = engine.requests.get(rid)
                    if req is not None:
                        ctxs.append(len(req.prompt) + len(req.tokens))
                pre = sum(len(engine.requests[r].prompt)
                          - engine.requests[r].cached_tokens
                          for r in out["admitted"]
                          if r in engine.requests) if out["admitted"] else 0
                tick_work.append((ctxs, pre, te - ts))
            elif i < len(reqs):
                with jax.profiler.TraceAnnotation("bench.idle"):
                    time.sleep(min(max(reqs[i][0] - now, 0.0), 0.01))
            else:
                break
            if now > deadline:
                break
    t_end = time.perf_counter() - t0
    lookups = (pc.lookups - lookups0) if pc is not None else 0
    hits = (pc.hits - hits0) if pc is not None else 0
    done = {rid for rid, ts in times.items()
            if len(ts) == reqs[rid_of[rid]][2]}
    # every request counts: one still without a token when the drain ends
    # counts its whole wait so far
    ttft = stats.ttfts_from_due(
        [reqs[rid_of[r]][0] for r in times] + [due for due, _, _ in reqs[i:]],
        [times[r][0] if times[r] else t_end for r in times]
        + [t_end] * (len(reqs) - i))
    itl = [g for r in times for g in stats.gaps(times[r])]
    per_request = [(times[r][0] - reqs[rid_of[r]][0],
                    stats.percentile(stats.gaps(times[r]), 95)
                    if len(times[r]) > 1 else 0.0) for r in done]
    failed = len(reqs) - len(done)
    state["done"] = {r: (reqs[rid_of[r]][1], toks[r]) for r in done}
    e2e = {}
    if ttft:
        e2e["ttft_p90_s"] = stats.percentile(ttft, 90)
    if itl:
        e2e["itl_p95_s"] = stats.percentile(itl, 95)
    record = {"requests": len(reqs), "finished": len(done),
              "window_s": t_end, "ticks": ticks, "tick_work": tick_work,
              "prefix_lookups": lookups, "prefix_hits": hits,
              "lateness_s": lateness, "per_request": per_request}
    late = sorted(lateness)
    summary = {"requests": len(reqs), "finished": len(done),
               "window_s": t_end, "ticks": len(ticks),
               "tokens": sum(len(times[r]) for r in times),
               "late_p50_s": stats.percentile(late, 50) if late else None,
               "late_max_s": late[-1] if late else None,
               "ttft_p50_s": stats.percentile(ttft, 50) if ttft else None,
               "itl_p50_s": stats.percentile(itl, 50) if itl else None,
               "prefix_hit_rate": hits / lookups if lookups else None}
    ctx.say(f"[serve] generator lateness p50 {summary['late_p50_s']!r} s, "
            f"max {summary['late_max_s']!r} s")
    return {"e2e": e2e, "attempted": len(reqs), "failed": failed,
            "record": record, "summary": summary}


def sample_requests(ctx, done: dict) -> list:
    """The finished requests the check compares, drawn from the seed, the
    longest among them."""
    rids = sorted(done)
    if not rids:
        return []
    longest = max(range(len(rids)),
                  key=lambda j: len(done[rids[j]][0]) + len(done[rids[j]][1]))
    pick = traffic.sample(ctx.seed, len(rids), ctx.mix["check_requests"],
                          always=[longest])
    return [done[rids[j]] for j in pick]


def served_gaps(ctx, weights, sample, control=None) -> list:
    """Per sampled request, the gaps below the reference's best logit: of
    the served tokens, or with ``control`` (a lower matmul type) of the
    token that the reference at that type puts first."""
    import jax
    import jax.numpy as jnp
    ref, c = ctx.reference, ctx.config["program"]

    @jax.jit
    def gaps(w, seq, served):
        want = ref.logits(w, seq[None], c)[0]
        if control is not None:
            served = jnp.argmax(ref.logits(w, seq[None], c, control)[0], -1)
        return jnp.max(want, -1) - jnp.take_along_axis(
            want, served[:, None], -1)[:, 0]

    # one shape for every request: causal attention leaves the positions
    # before the zero padding untouched
    span = _max_seq(ctx.mix)
    out = []
    for prompt, tokens in sample:
        seq = np.zeros(span, np.int32)
        seq[:len(prompt) + len(tokens) - 1] = np.concatenate(
            [prompt, tokens[:-1]])
        # logits at position p predict token p + 1: served token j sits at
        # len(prompt) + j and is predicted at len(prompt) + j - 1
        served = np.zeros(span, np.int32)
        lo = len(prompt) - 1
        served[lo:lo + len(tokens)] = tokens
        g = np.asarray(gaps(weights, jnp.asarray(seq), jnp.asarray(served)))
        out.append(g[lo:lo + len(tokens)])
    return out


def check(ctx, state, win) -> list:
    import jax
    c = ctx.config["program"]
    sample = sample_requests(ctx, state.pop("done"))
    state["session"].__exit__(None, None, None)
    state["session"].close()
    for k in ("engine", "session", "params"):
        state.pop(k)
    gc.collect()
    weights = ctx.reference.init_weights(c, ctx.seed)
    g = served_gaps(ctx, weights, sample)
    del weights
    jax.clear_caches()
    widest = float(max(x.max() for x in g)) if g else float("inf")
    n_tok = int(sum(len(x) for x in g))
    ctx.say(f"[serve] checked {len(g)} requests, {n_tok} served tokens")
    return [{"name": "served_logit_gap", "value": widest,
             "limit": ctx.limits["served_logit_gap"]}]


def control(ctx, dtype) -> dict:
    """A short program window for the sample, then the gap of the token
    that the reference at ``dtype`` matmul operands puts first."""
    st = setup(ctx)
    win = window(ctx, st, ctx.mix.get("control_seconds", 5.0))
    sample = sample_requests(ctx, st.pop("done"))
    st["session"].__exit__(None, None, None)
    st["session"].close()
    del st, win
    gc.collect()
    weights = ctx.reference.init_weights(ctx.config["program"], ctx.seed)
    g = served_gaps(ctx, weights, sample, control=dtype)
    return {"served_logit_gap": float(max(x.max() for x in g))}
