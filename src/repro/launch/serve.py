"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Open-loop request-trace driver over the request-lifecycle ``ServeEngine``:
``--num-requests`` ragged prompts (optionally sharing a ``--shared-prefix``)
arrive as a Poisson process at ``--rate`` req/s (0 = all at once) and are
``submit()``-ed into the continuous-batching scheduler; the loop ticks
``engine.step()`` until the trace drains.  PASTA instrumentation is two-level:
the fleet session carries the registered ``serving`` tool (TTFT/TPOT
percentiles, batch-occupancy timeline, prefix-cache hit rate) plus whatever
``--pasta-tools`` names, and each request's child session carries
``--request-tools``.

Multi-tenant traffic: ``--traffic <preset>`` swaps the uniform Poisson
trace for a ``repro.serve.traffic`` preset (mixed lengths, bursty
arrivals, per-tenant SLO tags), ``--policy`` picks the scheduling policy
(fcfs/priority/edf/fair), and traces are reproducible artifacts —
``--save-trace out.jsonl`` writes the materialized trace,
``--trace-file in.jsonl`` replays one exactly (so two policies can be
compared on the *same* arrivals).

Chaos + fault tolerance: ``--chaos <preset> --chaos-seed N`` arms a
deterministic :class:`repro.serve.faults.FaultPlan` (tick errors,
poisoned requests, NaN logits, stalls, pool pressure, host preemptions);
the engine recovers by blame-and-retry — only blamed requests end
``failed``, innocents are re-queued losslessly.  ``--deadline-s`` stamps
a hard per-request deadline onto every trace request's SLO (status
``timeout`` on expiry).  The JSON summary gains the serving tool's
``health`` section plus a top-level ``request_states`` map, so a chaos
run's outcome is machine-checkable against its fault-free twin.

The persistent XLA compilation cache is always on: in
``JAX_COMPILATION_CACHE_DIR`` when that is set, else in ``<repo>/.jax_cache``
(cold run compiles and populates; warm runs skip XLA) — ``compile_s`` in
the JSON summary shows the cold-vs-warm difference.

``--json <path>`` writes the structured results (per-request + fleet
reports, token throughput, latency/SLO/goodput summaries, trace seed and
policy name) in the same one-dict-per-run contract as the dryrun driver.
"""

import argparse
import json
import os
import sys
import time


def _parse():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-gpt2")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--num-requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop Poisson arrival rate in req/s "
                         "(0 = submit the whole trace up front)")
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="max prompt length; ragged prompts draw uniformly "
                         "from [prompt-len-min, prompt-len]")
    ap.add_argument("--prompt-len-min", type=int, default=8)
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="tokens shared by every prompt (prefix-cache "
                         "reuse workload)")
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--no-prefix-cache", action="store_true")
    ap.add_argument("--prefix-block", type=int, default=16,
                    help="prefix-cache key granularity (tokens); in paged "
                         "mode this is also the KV block size")
    ap.add_argument("--no-paged", action="store_true",
                    help="use the legacy dense (slots, max_seq) KV pool "
                         "instead of the paged block pool")
    ap.add_argument("--block-size", type=int, default=None,
                    help="paged KV block width in tokens "
                         "(default: --prefix-block)")
    ap.add_argument("--n-blocks", type=int, default=None,
                    help="paged pool capacity in blocks (default: per-slot "
                         "parity + 2 sequences of prefix-store headroom)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="per-tick prefill token budget shared across "
                         "mid-prefill requests (bounds decode stalls; "
                         "paged mode only)")
    ap.add_argument("--spec-decode", type=int, default=0, metavar="K",
                    help="speculative decode: draft K tokens per active "
                         "slot per tick, verify in one fused forward "
                         "(0 = off)")
    ap.add_argument("--draft", default="ngram",
                    choices=("ngram", "model"),
                    help="draft source: n-gram prompt-lookup self-draft, "
                         "or a draft model (--draft-arch)")
    ap.add_argument("--draft-arch", default=None,
                    help="arch id for --draft model (reduced to match; "
                         "default: the target model itself)")
    ap.add_argument("--policy", default="fcfs",
                    choices=("fcfs", "priority", "edf", "fair"),
                    help="scheduling policy: fcfs (default), priority / "
                         "edf (preemptive: evict-and-requeue via the "
                         "prefix store), fair (least-served tenant first)")
    ap.add_argument("--interleave", default="chunked",
                    choices=("chunked", "decode"),
                    help="prefill/decode arbitration per tick: spend the "
                         "chunk budget every tick, or defer prefill while "
                         "any slot can decode (needs --prefill-chunk)")
    ap.add_argument("--traffic", default=None,
                    choices=("two-tenant-bursty",),
                    help="multi-tenant traffic preset from "
                         "repro.serve.traffic (overrides the uniform "
                         "Poisson trace flags)")
    ap.add_argument("--trace-file", default=None, metavar="PATH",
                    help="replay a JSONL trace (from --save-trace) "
                         "instead of generating one")
    ap.add_argument("--save-trace", default=None, metavar="PATH",
                    help="write the materialized trace as JSONL for "
                         "exact replay")
    ap.add_argument("--chaos", default=None,
                    choices=("one-poison", "transient", "storm", "pressure"),
                    help="arm a deterministic fault-injection preset "
                         "(repro.serve.faults); recovery is asserted, not "
                         "hoped for")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the chaos preset's fault schedule")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="hard per-request deadline stamped onto every "
                         "trace request's SLO (status 'timeout' on expiry)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip pre-trace jit warmup (TTFT/TPOT will then "
                         "include compile time)")
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--pasta-tools", default="serving,kernel_freq")
    ap.add_argument("--request-tools", default="serving")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write structured per-request + fleet results")
    ap.add_argument("--seed", type=int, default=0)
    # deprecated generate()-era spelling, kept for muscle memory
    ap.add_argument("--batch", type=int, default=None,
                    help=argparse.SUPPRESS)
    return ap.parse_args()


def make_trace(args, vocab: int):
    """Ragged prompts (+ optional shared prefix) and Poisson arrival times."""
    import numpy as np
    rng = np.random.default_rng(args.seed)
    lo = min(args.prompt_len_min, args.prompt_len)
    lens = rng.integers(lo, args.prompt_len + 1, args.num_requests)
    prefix = rng.integers(0, vocab, (args.shared_prefix,), dtype=np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(0, vocab, (int(n),),
                                            dtype=np.int32)])
               for n in lens]
    if args.rate > 0:
        arrivals = np.cumsum(rng.exponential(1.0 / args.rate,
                                             args.num_requests))
    else:
        arrivals = np.zeros(args.num_requests)
    return prompts, arrivals


def drive(engine, trace, temperature: float = 0.0):
    """Open-loop replay: submit each ``TraceRequest`` at its arrival time
    and tick ``engine.step()`` until the trace drains.  Returns ``(rids,
    {rid: generated tokens}, wall seconds)``."""
    from repro.serve import SamplingParams
    t0 = time.perf_counter()
    pending = [(t.arrival_s, t) for t in trace]
    rids = []
    outputs = {}            # collected at retirement (pruning-safe)
    while pending or engine.has_work:
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            t = pending.pop(0)[1]
            rids.append(engine.submit(
                t.prompt,
                SamplingParams(max_new_tokens=t.max_new_tokens,
                               temperature=temperature),
                slo=t.slo))
        if engine.has_work:
            for rid in engine.step()["finished"]:
                outputs[rid] = list(engine.requests[rid].tokens)
        elif pending:
            time.sleep(min(pending[0][0] - now, 0.05))
    return rids, outputs, time.perf_counter() - t0


def capture_decode(session, engine, params) -> None:
    """Feed the fused decode (or speculative verify) step's compiled HLO
    into the fleet session, so kernel_freq and the roofline see it."""
    import jax.numpy as jnp
    import numpy as np
    slots = engine.pool.slots
    if engine.paged:
        span = engine.pool.blocks_per_seq * engine.pool.block_size
        cache = engine.pool.cache_view(np.full((slots,), span, np.int32))
    else:
        cache = engine.pool.cache
    if engine.spec_k:
        compiled = engine._verify.lower(
            params, cache, jnp.zeros((slots, engine.spec_k + 1), jnp.int32),
            jnp.asarray(engine._verify_idx)).compile()
    else:
        compiled = engine._decode.lower(
            params, cache, jnp.zeros((slots, 1), jnp.int32)).compile()
    session.capture_compiled(compiled, label="serve.decode",
                             steps=max(engine.decode_steps, 1))


def _short(data: dict) -> dict:
    return {k: v for k, v in data.items()
            if k not in ("series", "top", "by_label", "by_request")}


def main():
    args = _parse()
    if args.batch is not None:
        print("[serve] note: --batch is deprecated; the trace driver uses "
              "--num-requests/--max-slots", file=sys.stderr)
        args.num_requests = args.batch
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax
    import numpy as np

    from repro.launch.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()

    import dataclasses

    import repro.configs as configs
    import repro.core as pasta
    from repro.dist.sharding import set_mesh
    from repro.launch.mesh import make_mesh
    from repro.models import init_params
    from repro.serve import ServeEngine, SLOSpec, traffic

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg)
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model")) if d * m > 1 else None
    set_mesh(mesh)

    vocab = max(cfg.vocab_size, 2)
    trace_meta = {"seed": args.seed}
    if args.trace_file:
        trace, trace_meta = traffic.load_trace(args.trace_file)
        print(f"[serve] replaying {len(trace)} requests from "
              f"{args.trace_file} (meta={trace_meta})")
    elif args.traffic:
        trace = traffic.PRESETS[args.traffic](vocab, seed=args.seed)
    else:
        prompts, arrivals = make_trace(args, vocab)
        trace = [traffic.TraceRequest(arrival_s=float(a), prompt=p,
                                      max_new_tokens=args.max_new_tokens,
                                      slo=None)
                 for a, p in zip(arrivals, prompts)]
    if args.save_trace:
        traffic.save_trace(args.save_trace, trace, seed=args.seed,
                           meta={"preset": args.traffic,
                                 "arch": args.arch})
        print(f"[serve] wrote trace {args.save_trace}")
    if args.deadline_s is not None:
        # stamp the hard deadline onto every request's SLO (engines cancel
        # with status 'timeout' once it elapses)
        trace = [dataclasses.replace(
                     t, slo=(dataclasses.replace(t.slo,
                                                 deadline_s=args.deadline_s)
                             if t.slo is not None
                             else SLOSpec(deadline_s=args.deadline_s)))
                 for t in trace]
    if args.traffic or args.trace_file:
        max_seq = traffic.max_seq_for(trace)
    else:
        max_seq = args.shared_prefix + args.prompt_len + args.max_new_tokens

    with pasta.Session(tools=args.pasta_tools, name="serve") as session:
        params = init_params(jax.random.PRNGKey(args.seed), cfg)
        paged = False if args.no_paged else None   # None = family default
        draft_cfg = None
        if args.draft_arch is not None:
            draft_cfg = configs.get(args.draft_arch)
            if args.reduced:
                draft_cfg = configs.reduced(draft_cfg)
        engine = ServeEngine(cfg, params, max_seq=max_seq,
                             max_slots=args.max_slots, session=session,
                             request_tools=args.request_tools or None,
                             prefix_cache=not args.no_prefix_cache,
                             prefix_block=args.prefix_block,
                             paged=paged, block_size=args.block_size,
                             n_blocks=args.n_blocks,
                             prefill_chunk=args.prefill_chunk,
                             spec_decode=args.spec_decode, draft=args.draft,
                             draft_cfg=draft_cfg,
                             policy=args.policy,
                             interleave=args.interleave,
                             rng_seed=args.seed,
                             faults=args.chaos, fault_seed=args.chaos_seed)
        if args.chaos:
            print(f"[serve] chaos armed: preset={args.chaos} "
                  f"seed={args.chaos_seed} "
                  f"({len(engine.faults.specs)} fault specs)")
        compile_s = 0.0
        if not args.no_warmup:
            # compile the steady-state dispatches BEFORE the trace clock
            # starts, so TTFT/TPOT percentiles measure serving latency,
            # not XLA compile time
            wu = engine.warmup(prompt_lens=[len(t.prompt) for t in trace])
            compile_s = wu["compile_s"]
            print(f"[serve] warmup: {len(wu['warmed'])} shapes compiled "
                  f"in {compile_s:.2f}s (excluded from the trace clock)")
        rids, outputs, dt = drive(engine, trace, args.temperature)
        n_tok = sum(len(t) for t in outputs.values())
        print(f"[serve] {len(rids)} requests, {n_tok} tokens in {dt:.2f}s "
              f"({n_tok / dt:.1f} tok/s), max_slots={args.max_slots}, "
              f"policy={args.policy}, rate={args.rate or 'inf'}")
        if engine.preemptions:
            print(f"[serve] preemptions={engine.preemptions} "
                  f"parked_blocks={engine.parked_blocks} "
                  f"recovered_blocks={engine.recovered_blocks} "
                  f"(zero-recompute resume)")
        if engine.spec_k:
            acc = (engine.accepted_tokens / engine.drafted_tokens
                   if engine.drafted_tokens else 0.0)
            print(f"[serve] speculative k={engine.spec_k} "
                  f"({args.draft}): {engine.accepted_tokens}/"
                  f"{engine.drafted_tokens} drafts accepted "
                  f"({acc:.2f}), {engine.decode_steps} verify ticks")
        health = engine.health()
        if args.chaos or args.deadline_s is not None:
            print(f"[serve] health: faults={health['fault_ticks']} "
                  f"retries={health['request_retries']} "
                  f"failed={health['failed']} "
                  f"timeouts={health['timeouts']} "
                  f"isolated={health['isolated_innocents']} "
                  f"degraded_ticks={health['degraded_ticks']}")
        done_rids = [r for r in rids if r in outputs]
        if done_rids:
            print(f"[serve] sample: {outputs[done_rids[0]][:12]}")
        else:
            print("[serve] sample: <no finished requests>")
        capture_decode(session, engine, params)
        reports = session.reports()

    serving = reports["serving"].data if "serving" in reports else {}
    for name, rep in reports.items():
        print(f"  {name}: {_short(rep.data)}")
    per_request = []
    for req_reports in engine.request_reports:
        for name, rep in req_reports.items():
            per_request.append({"session": rep.session, "tool": name,
                                "data": rep.data})

    if args.json:
        occ = serving.get("occupancy", {})
        pc = serving.get("prefix_cache", {})
        out = {
            "driver": "serve",
            "arch": args.arch,
            "status": "ok",
            "config": {
                "reduced": args.reduced,
                "num_requests": args.num_requests,
                "rate": args.rate,
                "max_slots": args.max_slots,
                "prompt_len": [args.prompt_len_min, args.prompt_len],
                "shared_prefix": args.shared_prefix,
                "max_new_tokens": args.max_new_tokens,
                "temperature": args.temperature,
                "prefix_cache": not args.no_prefix_cache,
                "paged": engine.paged,
                "block_size": engine.block_size,
                "prefill_chunk": engine.prefill_chunk,
                "spec_decode": engine.spec_k,
                "draft": args.draft if engine.spec_k else None,
                "warmup": not args.no_warmup,
                "seed": args.seed,
                "mesh": args.mesh,
                "policy": args.policy,
                "interleave": args.interleave,
                "traffic": args.traffic,
                "trace_file": args.trace_file,
                "trace_seed": trace_meta.get("seed", args.seed),
                "chaos": args.chaos,
                "chaos_seed": args.chaos_seed,
                "deadline_s": args.deadline_s,
                "compile_cache": cache_dir,
            },
            "summary": {
                "wall_s": dt,
                "compile_s": compile_s,
                "generated_tokens": n_tok,
                "tok_per_s": n_tok / dt if dt > 0 else 0.0,
                "ttft_s": serving.get("ttft_s"),
                "tpot_s": serving.get("tpot_s"),
                "queue_s": serving.get("queue_s"),
                "occupancy_mean": occ.get("mean"),
                "occupancy_max": occ.get("max"),
                "decode_steps": serving.get("decode_steps"),
                "prefix_hit_rate": pc.get("hit_rate"),
                "prefix_reused_frac": pc.get("reused_frac"),
                "max_prefill_tokens_per_tick":
                    serving.get("prefill", {}).get("max_tokens_per_tick"),
                "max_prefill_stall_s":
                    serving.get("prefill", {}).get("max_stall_s"),
                "speculative": serving.get("speculative"),
                "bandwidth": serving.get("bandwidth"),
                "pool": engine.pool_stats(),
                "slo": serving.get("slo"),
                "preemption": serving.get("preemption"),
                "tenants": serving.get("tenants"),
                "health": serving.get("health"),
                "engine_health": health,
                "faults": (engine.faults.to_dict()
                           if engine.faults is not None else None),
            },
            "fleet": {name: rep.data for name, rep in reports.items()},
            "requests": per_request,
            "tokens": {int(rid): [int(t) for t in toks]
                       for rid, toks in outputs.items()},
            "request_states": {int(rid): engine.requests[rid].state.value
                               for rid in rids if rid in engine.requests},
        }
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1, default=str)
        print(f"[serve] wrote {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
