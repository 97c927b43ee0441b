"""PASTA's own spans in a profiler trace, and the arithmetic of the
per-layer metrics that read them.

The program opens ``jax.profiler.TraceAnnotation`` spans named ``pasta.*``
on its fine-grained path (``repro.core.instrument`` lists them); some carry
numeric stats (``pasta.kernel.pad``: ``records``, ``padded``).
:func:`read` collects them from an ``.xplane.pb``; :func:`reduce` puts them
beside the harness's ``bench.*`` spans and the device operations of
``bench.trace.read`` and returns:

* ``host_spans``: per span name, the merged intervals clipped to the window
  (ns), the count of spans that overlap the window and the sums of their
  numeric stats;
* ``idle_intervals``: per device, the merged intervals of the window in
  which no operation ran on it;
* ``window_ns``: the window, ``bench.window`` on the trace clock.

``bench/trace.py`` does not call this yet; ``bench/split.py`` adds these
keys to the reduction of one traced run, and the readers of the program's
split (``bench/metrics/*_share.profile.py``,
``round_trips_per_buffer.profile.py``) read them through :func:`share` and
:func:`host_spans`.
"""

from __future__ import annotations

import collections

from bench import trace

PREFIX = "pasta."


def read(path: str) -> list:
    """Every host event named ``pasta.*`` as (name, start, end, stats), in
    ns, with its numeric stats as a dict."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    stats = {k: v for k, v in e.stats
                             if isinstance(v, (int, float))}
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, stats))
    return out


def reduce(raw: dict, events: list, n_devices: int) -> dict:
    """``raw`` from ``bench.trace.read``, ``events`` from :func:`read`."""
    devs = [raw["devices"][d] for d in sorted(raw["devices"])[:n_devices]]
    wins = [(s, e) for n, s, e in raw["spans"] if n == trace.WINDOW_SPAN]
    if wins:
        lo, hi = wins[0]
    else:
        ops = [op for d in devs for op in d["ops"]]
        if not ops:
            raise ValueError("the trace holds no window and no device "
                             "operations")
        lo, hi = min(s for _, s, _ in ops), max(e for _, _, e in ops)
    ivs, count = collections.defaultdict(list), collections.Counter()
    stats: dict = collections.defaultdict(collections.Counter)
    for name, s, e, st in [(n, s, e, {}) for n, s, e in raw["spans"]] \
            + list(events):
        if e > lo and s < hi:
            ivs[name].append((s, e))
            count[name] += 1
            stats[name].update(st)
    spans = {n: {"intervals": trace.union(trace.clip(iv, lo, hi)),
                 "count": count[n], "stats": dict(stats[n])}
             for n, iv in ivs.items()}
    idle = [trace.subtract([[lo, hi]], trace.union(trace.clip(
        [(s, e) for _, s, e in d["ops"]], lo, hi))) for d in devs]
    return {"host_spans": spans, "idle_intervals": idle,
            "window_ns": hi - lo}


def host_spans(rec) -> dict | None:
    """The reduced trace's spans, or ``None`` where it holds no program
    span (a trace of a program without them, or of no trace at all)."""
    t = rec.get("trace") or {}
    hs = t.get("host_spans")
    if not hs or not any(n.startswith(PREFIX) for n in hs):
        return None
    return hs


def union_of(hs: dict, names) -> list:
    """Merged intervals of the spans named in ``names``; a name ending in
    ``*`` stands for every span name that starts with what precedes it."""
    out = []
    for pat in names:
        for n, v in hs.items():
            if n == pat or (pat.endswith("*") and n.startswith(pat[:-1])):
                out += v["intervals"]
    return trace.union(out)


def share(rec, inside, outside=()) -> float | None:
    """Percent of the window inside any span of ``inside`` and outside
    every span of ``outside``."""
    hs = host_spans(rec)
    if hs is None or rec["trace"]["window_ns"] <= 0:
        return None
    iv = trace.subtract(union_of(hs, inside), union_of(hs, outside))
    return 100.0 * trace.total(iv) / rec["trace"]["window_ns"]
