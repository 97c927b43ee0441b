"""Reduces a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

* The window is the host span ``bench.window`` that the driver opens around
  its measured loop (the whole trace where there is none).
* Device busy time is the union of the intervals in which an operation ran
  on a device (its ``XLA Ops`` line), clipped to the window, averaged over
  the devices used; idle is the rest of the window.
* Per-operation device time, summed over all devices by opcode and result
  type, and per-module (jitted program) device time and count.
* Collective time: operations whose name is a collective; the exposed part
  is the time in which a collective runs and no other operation does, on
  that device.
* Idle gaps: the longest stretches with nothing on the device, each labelled
  by the innermost ``bench.*`` host span that covers its middle, which is
  what the host was doing.
"""

from __future__ import annotations

import collections
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|psum|ppermute",
                        re.IGNORECASE)
TOP = 10
LAYOUT = re.compile(r"\{[^{}]*\}")
OPCODE = re.compile(r" ([a-z][\w\-.]*)\(")


def short(name: str) -> str:
    """An XLA op's event name (its whole HLO instruction) cut to the opcode
    and result type, so that like ops of different layers add up:
    ``exponential f32[4,32,1,1024,1024]``."""
    text = LAYOUT.sub("", LAYOUT.sub("", name))
    _, _, rhs = text.partition(" = ")
    m = OPCODE.search(" " + rhs)
    if not m:
        return name[:80]
    result = rhs[:m.start()].strip()
    if result.startswith("("):
        result = "tuple"
    if m.group(1) == "custom-call":
        target = name.split(" = ", 1)[0].lstrip("%").rsplit(".", 1)[0]
        return f"custom-call {target} {result}"
    return f"{m.group(1)} {result}"


def union(intervals) -> list:
    """Sorted, merged ``[start, end)`` intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b) -> list:
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            for e in line.events:
                yield e.name, e.start_ns, e.start_ns + e.duration_ns


def read(path: str) -> dict:
    """The parts of the trace the reduction needs, as plain lists:
    ``devices`` {id: {"ops": [(name, s, e)], "modules": [...]}} and
    ``spans`` [(name, s, e)] of the host's ``bench.*`` annotations, in ns."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(2))] = {
                "ops": list(_events(plane, OPS_LINE)),
                "modules": list(_events(plane, MODULES_LINE))}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return {"devices": devices, "spans": spans}


def reduce(raw: dict, n_devices: int) -> dict:
    """Busy, idle, per-op and collective times of the first ``n_devices``
    devices over the window, in seconds."""
    devs = [raw["devices"][d] for d in sorted(raw["devices"])[:n_devices]]
    if not devs or not any(d["ops"] for d in devs):
        raise ValueError("the trace holds no device operations")
    wins = [(s, e) for n, s, e in raw["spans"] if n == WINDOW_SPAN]
    if wins:
        lo, hi = wins[0]
    else:
        lo = min(s for d in devs for _, s, _ in d["ops"])
        hi = max(e for d in devs for _, _, e in d["ops"])
    window_ns = hi - lo
    busy, coll, exposed = [], 0.0, 0.0
    op_time: dict = collections.Counter()
    module_time: dict = collections.Counter()
    module_count: dict = collections.Counter()
    gaps: list = []
    for d in devs:
        ops = [(n, s, e) for n, s, e in d["ops"] if e > lo and s < hi]
        for n, s, e in ops:
            op_time[short(n)] += (min(e, hi) - max(s, lo)) / 1e9
        for n, s, e in d["modules"]:
            if e > lo and s < hi:
                module_time[n] += (min(e, hi) - max(s, lo)) / 1e9
                module_count[n] += 1
        all_iv = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy.append(total(all_iv))
        c_iv = union(clip([(s, e) for n, s, e in ops if COLLECTIVE.search(n)],
                          lo, hi))
        other = union(clip([(s, e) for n, s, e in ops
                            if not COLLECTIVE.search(n)], lo, hi))
        coll += total(c_iv)
        exposed += total(subtract(c_iv, other))
        gaps += subtract([[lo, hi]], all_iv)
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = sorted(raw["spans"], key=lambda x: x[2] - x[1])   # innermost 1st

    def label(g):
        mid = (g[0] + g[1]) / 2
        for n, s, e in spans:
            if s <= mid < e and n != WINDOW_SPAN:
                return n
        return "outside bench spans"

    n = len(devs)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "op_time": dict(op_time),
        "module_time": dict(module_time),
        "module_count": dict(module_count),
        "collective_s": coll / n / 1e9,
        "collective_exposed_s": exposed / n / 1e9,
        "top_ops": [[k, v] for k, v in op_time.most_common(TOP)],
        "idle_gaps": [[label(g), (g[1] - g[0]) / 1e9] for g in gaps[:TOP]],
    }


def reduce_file(path: str, n_devices: int) -> dict:
    return reduce(read(path), n_devices)
