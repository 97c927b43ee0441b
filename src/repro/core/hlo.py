"""Compiled-HLO walker — PASTA's post-AOT event source and roofline engine.

On GPUs the paper intercepts kernel launches dynamically; on TPU the compiled
XLA artifact is a *static* but exact record of every kernel (top-level HLO
instruction), collective, and loop the device will execute.  This module
parses ``compiled.as_text()`` into a structured module and rolls up:

  * executed-kernel counts          (KERNEL_LAUNCH events, Fig.-7 tool)
  * FLOPs                           (dot/conv + elementwise, ×loop trip counts)
  * HBM traffic                     (fusion-boundary operand+output bytes)
  * collective bytes by opcode      (operand bytes, ×loop trip counts)
  * collective *overlap* accounting (exposed bytes, hidden seconds, wire
    bytes — see below)

Overlap accounting pairs async collectives and credits hidden transfer time:

  * ``*-start`` / ``*-done`` pairs (TPU/GPU async collectives) — the overlap
    window is everything scheduled between the start and its matching done;
    the ``*-done`` carries no payload and is never counted as a kernel.
  * synchronous collectives (XLA:CPU emits these even for split layouts) —
    the *potential* overlap window is everything scheduled between the
    collective and its first real consumer (traced through transparent
    wrappers): exactly the slack an async runtime / latency-hiding scheduler
    exploits, computable from the static schedule.

Window compute time (flops / HBM traffic against the hardware model) hides
up to ``comm_s = bytes / ici_bw`` of the transfer; each collective instance
is stamped with ``exposed_bytes`` (the unhidden remainder), ``hidden_s``,
``overlapped``, and ``wire_bytes`` (an opcode-aware per-device wire model:
ring all-reduce moves ~2× payload, all-gather moves what it *receives*,
etc. — this is what must stay O(1) in pod count for the compressed sync).

XLA's own ``cost_analysis()`` counts ``while`` bodies exactly once (verified
empirically: a 10-iteration scan of a matmul reports the same FLOPs as one
matmul), so scan-over-layers models would be undercounted by ~n_layers.  XLA
annotates ``backend_config={"known_trip_count":{"n":...}}`` on while ops after
optimization; we multiply through the call graph using those counts.
"""

from __future__ import annotations

import dataclasses
import json
import math
import re

from .events import COLLECTIVE_OPCODES

_DTYPE_BITS = {
    "pred": 8, "s2": 2, "u2": 2, "s4": 4, "u4": 4, "s8": 8, "u8": 8,
    "s16": 16, "u16": 16, "s32": 32, "u32": 32, "s64": 64, "u64": 64,
    "f8e4m3fn": 8, "f8e5m2": 8, "f8e4m3b11fnuz": 8, "f8e4m3fnuz": 8,
    "f8e5m2fnuz": 8, "f8e3m4": 8, "f8e4m3": 8, "f8e8m0fnu": 8,
    "bf16": 16, "f16": 16, "f32": 32, "f64": 64, "c64": 64, "c128": 128,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z]\w*)\[([0-9,]*)\]")

# opcodes that move no data / are layout-only at the top level
_FREE_OPCODES = {
    "parameter", "tuple", "get-tuple-element", "bitcast", "constant",
    "after-all", "partition-id", "replica-id", "iota", "opt-barrier",
}

# elementwise/transcendental opcodes counted as 1 flop per output element
_ARITH_OPCODES = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "exponential-minus-one", "log", "log-plus-one",
    "tanh", "logistic", "rsqrt", "sqrt", "cbrt", "sine", "cosine", "tan",
    "power", "atan2", "floor", "ceil", "round-nearest-afz", "sign",
    "remainder", "erf", "select", "clamp", "compare", "and", "or", "xor",
    "not", "shift-left", "shift-right-logical", "shift-right-arithmetic",
}

# structural / data-movement opcodes the rollup handles generically; an
# opcode outside _FREE/_ARITH/these (and not a collective) is still
# processed as a generic kernel but counted under
# ``stats.warnings["unknown-opcode:<op>"]`` so truncated or future-XLA
# dumps degrade visibly instead of silently
_KNOWN_OPCODES = {
    "broadcast", "reshape", "transpose", "slice", "concatenate", "pad",
    "copy", "copy-start", "copy-done", "convert", "reverse", "dot",
    "convolution", "fusion", "reduce", "map", "scatter", "reduce-window",
    "select-and-scatter", "sort", "while", "call", "conditional",
    "custom-call", "rng", "rng-bit-generator", "rng-get-and-update-state",
    "dynamic-slice", "dynamic-update-slice", "gather", "domain",
    "bitcast-convert", "get-dimension-size", "set-dimension-size",
    "cholesky", "triangular-solve", "fft", "clz", "popcnt", "is-finite",
    "real", "imag", "complex", "stochastic-convert", "infeed", "outfeed",
    "send", "recv", "send-done", "recv-done", "async-start",
    "async-update", "async-done", "add-dependency", "slice-start",
    "slice-done",
} | _FREE_OPCODES | _ARITH_OPCODES


def shape_bytes(shape_str: str) -> int:
    """Total bytes of an HLO shape string (handles tuples)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        bits = _DTYPE_BITS.get(dtype)
        if bits is None or bits == 0:
            continue
        numel = 1
        if dims:
            for d in dims.split(","):
                if d:           # tolerate truncated dim lists ("2,3,")
                    numel *= int(d)
        total += numel * bits // 8
    return total


def shape_numel(shape_str: str) -> int:
    numel_total = 0
    for _dtype, dims in _SHAPE_RE.findall(shape_str):
        numel = 1
        if dims:
            for d in dims.split(","):
                if d:
                    numel *= int(d)
        numel_total += numel
    return numel_total


def _first_shape_dims(shape_str: str) -> list:
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return []
    dims = m.group(2)
    return [int(d) for d in dims.split(",") if d] if dims else []


@dataclasses.dataclass
class Instruction:
    name: str
    opcode: str
    shape: str
    operands: list
    attrs: str
    is_root: bool = False

    # ---- lazy attr helpers -------------------------------------------------
    def called_computations(self) -> list:
        out = []
        for key in ("calls", "body", "condition", "to_apply"):
            m = re.search(rf"{key}=%?([\w\.\-]+)", self.attrs)
            if m:
                out.append(m.group(1))
        m = re.search(r"branch_computations=\{([^}]*)\}", self.attrs)
        if m:
            out += [c.strip().lstrip("%") for c in m.group(1).split(",") if c.strip()]
        m = re.search(r"called_computations=\{([^}]*)\}", self.attrs)
        if m:
            out += [c.strip().lstrip("%") for c in m.group(1).split(",") if c.strip()]
        return out

    def trip_count(self) -> int | None:
        m = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', self.attrs)
        return int(m.group(1)) if m else None

    def replica_group_size(self) -> int | None:
        # e.g. replica_groups=[32,16]<=[512] → 16 participants per group
        m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[\d+\]", self.attrs)
        if m:
            return int(m.group(2))
        m = re.search(r"replica_groups=\{\{([^}]*)\}", self.attrs)
        if m:
            return len(m.group(1).split(","))
        groups = self.replica_groups()      # multi-dim iota (T-form) source
        return len(groups[0]) if groups else None

    def replica_groups(self) -> list | None:
        """Explicit device-id groups, decoding both the literal
        ``{{0,4},{1,5}}`` and the iota ``[4,2]<=[8]T(1,0)`` forms."""
        m = re.search(r"replica_groups=\{\{([^=]*?)\}\}", self.attrs)
        if m:
            return [[int(d) for d in grp.split(",") if d.strip()]
                    for grp in m.group(1).split("},{")]
        m = re.search(
            r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?",
            self.attrs)
        if m:
            import numpy as _np
            rows, cols = int(m.group(1)), int(m.group(2))
            dims = [int(d) for d in m.group(3).split(",")]
            ids = _np.arange(int(_np.prod(dims))).reshape(dims)
            if m.group(4):
                ids = ids.transpose([int(p) for p in m.group(4).split(",")])
            return ids.reshape(rows, cols).tolist()
        return None

    def out_bytes(self) -> int:
        return shape_bytes(self.shape)


@dataclasses.dataclass
class Computation:
    name: str
    instructions: dict          # name -> Instruction
    order: list                 # instruction names in program order

    def shape_of(self, operand: str) -> str:
        ins = self.instructions.get(operand.lstrip("%"))
        return ins.shape if ins else ""


@dataclasses.dataclass
class HloModule:
    computations: dict          # name -> Computation
    entry: str
    #: param numbers donated via the module's input_output_alias header
    aliased_params: set = dataclasses.field(default_factory=set)
    #: counted parser warnings (malformed lines skipped, never raised)
    parse_warnings: dict = dataclasses.field(default_factory=dict)

    def entry_computation(self) -> Computation:
        return self.computations[self.entry]


#: ``input_output_alias={ {0}: (0, {}, may-alias), ... }`` header entries:
#: capture (output index tuple, parameter number)
_ALIAS_ENTRY_RE = re.compile(r"\{[\d,\s]*\}:\s*\((\d+)")


def _split_balanced(s: str, opener: str = "(", closer: str = ")") -> tuple:
    """Return (inside, rest) for the first balanced paren group in ``s``."""
    depth = 0
    start = None
    for i, ch in enumerate(s):
        if ch == opener:
            if depth == 0:
                start = i
            depth += 1
        elif ch == closer:
            depth -= 1
            if depth == 0:
                return s[start + 1:i], s[i + 1:]
    return "", s


_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s+\(.*\)\s*->\s*.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*)$")


def parse_hlo(text: str) -> HloModule:
    computations: dict = {}
    entry = None
    cur: Computation | None = None
    aliased: set = set()
    warnings: dict = {}

    def warn(key: str) -> None:
        warnings[key] = warnings.get(key, 0) + 1

    for raw in text.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        if cur is None and line.lstrip().startswith("HloModule"):
            m = re.search(r"input_output_alias=\{(.*?)\}\s*(?:,|$)",
                          line)
            if m is None:
                m = re.search(r"input_output_alias=\{(.*)", line)
            if m:
                aliased.update(int(p) for p in
                               _ALIAS_ENTRY_RE.findall(m.group(1)))
            continue
        hdr = _COMP_HDR.match(line)
        if hdr and " = " not in line.split("{")[0]:
            cur = Computation(hdr.group(2), {}, [])
            computations[cur.name] = cur
            if hdr.group(1):
                entry = cur.name
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        is_root, name, rhs = bool(m.group(1)), m.group(2), m.group(3)
        # rhs = SHAPE opcode(operands), attrs
        rhs = rhs.strip()
        if rhs.startswith("("):
            shape, rest = _split_balanced(rhs)
            shape = "(" + shape + ")"
        else:
            sp = rhs.find(" ")
            if sp < 0:                      # truncated line: no opcode part
                warn("malformed-instruction")
                continue
            shape, rest = rhs[:sp], rhs[sp:]
        rest = rest.strip()
        sp = rest.find("(")
        if sp < 0:
            warn("malformed-instruction")
            continue
        opcode = rest[:sp].strip()
        inside, attrs = _split_balanced(rest[sp - 1:] if rest[sp - 1] == "(" else rest)
        operands = []
        depth = 0
        tok = ""
        for ch in inside:
            if ch in "([{":
                depth += 1
            elif ch in ")]}":
                depth -= 1
            if ch == "," and depth == 0:
                operands.append(tok.strip())
                tok = ""
            else:
                tok += ch
        if tok.strip():
            operands.append(tok.strip())
        # operand tokens are either plain %names or "<shape> %name" (compiled
        # HLO prints inline operand shapes); keep the name part — dropping the
        # shape here is what lets dot/fusion costs resolve their operand
        # shapes (and hence contraction dims) through while-body computations
        op_names = []
        for o in operands:
            mm = re.match(r"^%?([\w\.\-]+)$", o)
            if mm is None:
                mm = re.search(r"%([\w\.\-]+)\s*$", o)
            op_names.append(mm.group(1) if mm else o)
        ins = Instruction(name, opcode, shape, op_names, attrs.strip(", "),
                          is_root=is_root)
        cur.instructions[name] = ins
        cur.order.append(name)
    if entry is None:
        # fall back: computation named main-ish, else last one
        for cname in computations:
            if "main" in cname:
                entry = cname
        if entry is None and computations:
            entry = list(computations)[-1]
    if not computations:
        warn("empty-module")
    return HloModule(computations, entry, aliased_params=aliased,
                     parse_warnings=warnings)


# --------------------------------------------------------------------------
# rollups
# --------------------------------------------------------------------------

def _base_collective(opcode: str) -> str | None:
    op = opcode[:-6] if opcode.endswith("-start") else opcode
    return op if op in COLLECTIVE_OPCODES else None


def _is_collective_done(opcode: str) -> bool:
    return opcode.endswith("-done") and opcode[:-5] in COLLECTIVE_OPCODES


def _default_hw() -> dict:
    """Peaks of the analytic target chip, used for overlap credit when the
    caller supplies none (imported lazily: tools→hlo→tools cycle)."""
    from repro.core.tools.roofline import ANALYTIC_TARGET, peaks
    return peaks(ANALYTIC_TARGET)


def collective_wire_bytes(opcode: str, op_bytes: float, out_bytes: float,
                          group_size: int | None) -> float:
    """Per-device *wire* bytes of one collective — what actually crosses the
    interconnect, unlike the raw operand-bytes proxy.  Ring algorithms:
    all-reduce moves ~2× payload, all-gather / reduce-scatter move the
    shards they receive / retire, all-to-all keeps (N−1)/N of the payload
    on the wire."""
    frac = (group_size - 1) / group_size if group_size else 1.0
    if opcode == "all-gather":
        return max(out_bytes - op_bytes, 0.0)
    if opcode == "reduce-scatter":
        return max(op_bytes - out_bytes, 0.0)
    if opcode == "all-reduce":
        return 2.0 * op_bytes * frac
    if opcode in ("all-to-all", "ragged-all-to-all"):
        return op_bytes * frac
    return float(op_bytes)          # collective-permute / broadcast


@dataclasses.dataclass
class HloStats:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_bytes: dict = dataclasses.field(default_factory=dict)
    collective_wire_bytes: dict = dataclasses.field(default_factory=dict)
    collective_instances: list = dataclasses.field(default_factory=list)
    kernel_counts: dict = dataclasses.field(default_factory=dict)
    kernel_meta: dict = dataclasses.field(default_factory=dict)
    hw: dict = dataclasses.field(default_factory=dict)
    #: counted analysis warnings (parser skips, unknown opcodes,
    #: per-instruction visit errors) — populated, never raised
    warnings: dict = dataclasses.field(default_factory=dict)

    @property
    def total_collective_bytes(self) -> float:
        return float(sum(self.collective_bytes.values()))

    @property
    def total_wire_bytes(self) -> float:
        return float(sum(self.collective_wire_bytes.values()))

    @property
    def exposed_collective_bytes(self) -> float:
        """Wire bytes NOT hidden behind the overlap windows (exposure is
        priced on the wire model so split collective layouts compare
        fairly with the fused ones they replace)."""
        return float(sum(i["exposed_bytes"] * i["mult"]
                         for i in self.collective_instances))

    @property
    def hidden_collective_s(self) -> float:
        """Seconds of collective time credited as overlapped."""
        return float(sum(i["hidden_s"] * i["mult"]
                         for i in self.collective_instances))

    @property
    def collective_comm_s(self) -> float:
        """Total alpha-beta collective seconds (wire + per-message
        latency, on each collective's link)."""
        return float(sum(i["comm_s"] * i["mult"]
                         for i in self.collective_instances))

    @property
    def exposed_collective_s(self) -> float:
        """Collective seconds NOT hidden behind concurrent work."""
        return float(sum(max(i["comm_s"] - i["hidden_s"], 0.0) * i["mult"]
                         for i in self.collective_instances))


def _dot_flops(comp: Computation, ins: Instruction) -> float:
    out_numel = shape_numel(ins.shape)
    lhs_shape = comp.shape_of(ins.operands[0]) if ins.operands else ""
    lhs_dims = _first_shape_dims(lhs_shape)
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.attrs)
    k = 1
    if m and lhs_dims:
        for d in m.group(1).split(","):
            if d:
                di = int(d)
                if di < len(lhs_dims):
                    k *= lhs_dims[di]
    return 2.0 * out_numel * k


def _window_fields(attrs: str) -> dict:
    """``window={size=1x12 pad=0_0x11_11 ...}`` → {field: [per-dim str]}."""
    m = re.search(r"window=\{([^}]*)\}", attrs)
    if not m:
        return {}
    return {k: v.split("x") for k, v in
            (f.split("=", 1) for f in m.group(1).split() if "=" in f)}


def _conv_taps(lhs_len: int, out_len: int, size: int, stride: int,
               pad_lo: int, lhs_dilate: int) -> float:
    """Mean kernel taps per output position that land on a real input
    element (not padding, not a dilation hole) along one spatial dim."""
    last = (lhs_len - 1) * lhs_dilate
    hits = sum(1 for o in range(out_len) for k in range(size)
               if 0 <= o * stride + k - pad_lo <= last
               and (o * stride + k - pad_lo) % lhs_dilate == 0)
    return hits / max(out_len, 1)


def _conv_flops(comp: Computation, ins: Instruction) -> float:
    """2 × output elements × multiply-adds per output.  The TPU compiler
    lowers matmuls to convolutions that carry batch and contraction dims as
    padded, dilated windows (``window={size=1x12 pad=0_0x11_11}``,
    ``lhs_dilate=8x12``), so the multiply-adds are the kernel's input
    features times the taps that reach a real input element."""
    out_dims = _first_shape_dims(ins.shape)
    out_numel = shape_numel(ins.shape)
    lhs_dims = _first_shape_dims(comp.shape_of(ins.operands[0])
                                 if ins.operands else "")
    rhs_shape = comp.shape_of(ins.operands[1]) if len(ins.operands) > 1 else ""
    rhs_dims = _first_shape_dims(rhs_shape)
    m = re.search(r"dim_labels=(\w+)_(\w+)->(\w+)", ins.attrs)
    if not (m and lhs_dims and rhs_dims and out_dims):
        k = shape_numel(rhs_shape) // max(1, rhs_dims[-1] if rhs_dims else 1)
        return 2.0 * out_numel * max(1, k)
    lhs_l, rhs_l, out_l = m.groups()
    win = _window_fields(ins.attrs)

    def field(name, i, default):
        vals = win.get(name)
        return vals[i] if vals and i < len(vals) else default
    macs = float(rhs_dims[rhs_l.index("i")])
    for i, d in enumerate(sorted(c for c in rhs_l if c.isdigit())):
        macs *= _conv_taps(
            lhs_dims[lhs_l.index(d)], out_dims[out_l.index(d)],
            int(field("size", i, 1)), int(field("stride", i, 1)),
            int(field("pad", i, "0_0").split("_")[0]),
            int(field("lhs_dilate", i, 1)))
    return 2.0 * out_numel * macs


def _computation_flops(module: HloModule, comp: Computation, memo: dict) -> float:
    """FLOPs of one execution of ``comp``, recursing into calls (not whiles —
    whiles handled by the walker with their trip counts)."""
    if comp.name in memo:
        return memo[comp.name]
    total = 0.0
    memo[comp.name] = 0.0   # guard cycles
    for iname in comp.order:
        ins = comp.instructions[iname]
        if ins.opcode == "dot":
            total += _dot_flops(comp, ins)
        elif ins.opcode == "convolution":
            total += _conv_flops(comp, ins)
        elif ins.opcode in _ARITH_OPCODES:
            total += shape_numel(ins.shape)
        elif ins.opcode in ("fusion", "call", "map", "reduce", "reduce-window",
                            "scatter", "select-and-scatter", "sort"):
            for c in ins.called_computations():
                sub = module.computations.get(c)
                if sub is not None:
                    total += _computation_flops(module, sub, memo)
        elif ins.opcode == "while":
            # handled by walker; don't count here
            pass
        elif ins.opcode == "conditional":
            branches = [module.computations.get(c)
                        for c in ins.called_computations()]
            branches = [b for b in branches if b is not None]
            if branches:
                total += max(_computation_flops(module, b, memo)
                             for b in branches)
    memo[comp.name] = total
    return total


#: ops that neither move independent data nor block in-place analysis —
#: uses/roots are traced *through* them (XLA:CPU's bf16 legalization wraps
#: everything in convert pairs; on TPU those buffers stay bf16/aliased).
_TRANSPARENT = {"convert", "bitcast", "reshape", "copy"}


def _fusion_io_bytes(module: HloModule, comp: Computation,
                     ins: Instruction) -> tuple:
    """(in_bytes, out_bytes) for a fusion, with slicing-aware accounting:

      * a fused parameter consumed ONLY by dynamic-slice/gather ops (possibly
        through convert/bitcast chains) contributes the sliced bytes, not the
        full operand (scan-stacked weights!);
      * a parameter consumed ONLY as the in-place target (operand 0) of
        dynamic-update-slice contributes nothing (aliased, not read);
      * a dynamic-update-slice root (again through transparent chains)
        writes/reads the update region only.
    """
    subs = [module.computations.get(c) for c in ins.called_computations()]
    sub = next((s for s in subs if s is not None), None)
    if sub is None:
        in_b = sum(shape_bytes(comp.shape_of(o)) for o in ins.operands)
        return in_b, ins.out_bytes()
    param_of: dict = {}
    for iname in sub.order:
        si = sub.instructions[iname]
        if si.opcode == "parameter" and si.operands:
            try:
                param_of[iname] = int(si.operands[0])
            except ValueError:
                pass
    # forward def->use edges
    users: dict = {}
    root_name = None
    for iname in sub.order:
        si = sub.instructions[iname]
        if si.is_root:
            root_name = iname
        for pos, o in enumerate(si.operands):
            users.setdefault(o.lstrip("%"), []).append((si, pos))

    def terminal_uses(name: str, seen=None) -> list:
        seen = seen or set()
        out = []
        for si, pos in users.get(name, ()):
            if si.opcode in _TRANSPARENT:
                if si.name in seen:
                    continue
                seen.add(si.name)
                if si.name == root_name:
                    out.append(("__root__", shape_bytes(si.shape), 0))
                out += terminal_uses(si.name, seen)
            else:
                out.append((si.opcode, shape_bytes(si.shape), pos))
        if name == root_name and not users.get(name):
            out.append(("__root__", 0, 0))
        return out

    in_b = 0
    for pname, idx in param_of.items():
        opnd = ins.operands[idx] if idx < len(ins.operands) else ""
        full = shape_bytes(comp.shape_of(opnd))
        u = terminal_uses(pname)
        if u and all(op in ("dynamic-slice", "gather") for op, _b, _p in u):
            in_b += min(full, sum(b for _op, b, _p in u))
        elif u and all(op == "dynamic-update-slice" and p == 0
                       for op, _b, p in u):
            in_b += 0
        else:
            in_b += full
    # operands without a parsed parameter (defensive): count full
    for idx, opnd in enumerate(ins.operands):
        if idx not in param_of.values():
            in_b += shape_bytes(comp.shape_of(opnd))

    def effective(name: str) -> Instruction | None:
        si = sub.instructions.get(name.lstrip("%"))
        hops = 0
        while si is not None and si.opcode in _TRANSPARENT and si.operands \
                and hops < 16:
            si = sub.instructions.get(si.operands[0].lstrip("%"))
            hops += 1
        return si

    def _out_bytes_of(name: str, declared: int) -> int:
        r = effective(name)
        if r is not None and r.opcode == "dynamic-update-slice" \
                and len(r.operands) > 1:
            upd = sub.shape_of(r.operands[1])
            return 2 * shape_bytes(upd)          # read update + write region
        return declared

    out_b = ins.out_bytes()
    if root_name is not None:
        root = sub.instructions[root_name]
        if root.opcode == "tuple":
            out_b = sum(_out_bytes_of(o, shape_bytes(sub.shape_of(o)))
                        for o in root.operands)
        else:
            out_b = _out_bytes_of(root_name, out_b)
    return in_b, out_b


# ------------------------------------------------------- overlap accounting
def _instr_hbm_bytes(module: HloModule, comp: Computation,
                     ins: Instruction) -> float:
    """HBM traffic of one top-level-style instruction (same rules as the
    kernel rollup), used to price overlap windows."""
    if ins.opcode == "fusion":
        in_b, out_b = _fusion_io_bytes(module, comp, ins)
        return float(in_b + out_b)
    if ins.opcode in ("dynamic-slice", "gather"):
        return 2.0 * ins.out_bytes()
    if ins.opcode == "dynamic-update-slice":
        upd = shape_bytes(comp.shape_of(ins.operands[1])
                          if len(ins.operands) > 1 else "")
        return 2.0 * upd
    return float(sum(shape_bytes(comp.shape_of(o)) for o in ins.operands)
                 + ins.out_bytes())


def _collective_window(comp: Computation, ins: Instruction,
                       pos: dict) -> tuple:
    """``(window_instruction_names, done_name | None)`` for one collective.

    Async ``*-start``: the window spans to the matching ``*-done`` (the
    instruction of the paired opcode consuming the start's value).  Sync
    collective: the window spans to the first real consumer, tracing
    through transparent wrappers (convert/bitcast/reshape/copy and
    get-tuple-element); no consumer in this computation ⇒ empty window
    (conservative — the value escapes and we credit nothing).
    """
    i = pos[ins.name]
    order = comp.order
    if ins.opcode.endswith("-start"):
        done_op = ins.opcode[:-6] + "-done"
        for j in range(i + 1, len(order)):
            cand = comp.instructions[order[j]]
            if cand.opcode == done_op and ins.name in cand.operands:
                return order[i + 1:j], cand.name
        return [], None
    # The value is traced element-precisely through tuples, optimization
    # barriers, and get-tuple-element, so a pipeline pinned with
    # lax.optimization_barrier (the bucketed overlapped sync) resolves to
    # the *true* consumer, not the barrier plumbing.
    alias: dict = {ins.name: None}      # name -> tuple element carrying it
    for j in range(i + 1, len(order)):
        cand = comp.instructions[order[j]]
        hit = next(((o, p) for p, o in enumerate(cand.operands)
                    if o in alias), None)
        if hit is None:
            continue
        src, opos = hit
        elem = alias[src]
        if cand.opcode in _TRANSPARENT and elem is None:
            alias[cand.name] = None
            continue
        if cand.opcode == "tuple" and elem is None:
            alias[cand.name] = opos
            continue
        if cand.opcode == "opt-barrier":
            alias[cand.name] = elem
            continue
        if cand.opcode == "get-tuple-element":
            m = re.search(r"index=(\d+)", cand.attrs)
            k = int(m.group(1)) if m else None
            if elem is None or k is None or k == elem:
                alias[cand.name] = None
            continue                    # wrong element ⇒ not our value
        return order[i + 1:j], None
    return [], None


def _instr_cost(module: HloModule, comp: Computation, ins: Instruction,
                flop_memo: dict) -> tuple:
    """``(flops, hbm_bytes)`` of one instruction's computable work.
    Collectives (and their ``-done`` halves) contend for the interconnect,
    so they contribute nothing; free/transparent ops cost nothing."""
    if ins.opcode in _FREE_OPCODES or ins.opcode in _TRANSPARENT:
        return 0.0, 0.0
    if _base_collective(ins.opcode) is not None \
            or _is_collective_done(ins.opcode):
        return 0.0, 0.0
    wf = 0.0
    if ins.opcode == "while":
        trip = ins.trip_count() or 1
        for c in ins.called_computations():
            sub = module.computations.get(c)
            if sub is not None:
                wf += _computation_flops(module, sub, flop_memo) * trip
        return wf, 0.0
    if ins.opcode == "dot":
        wf = _dot_flops(comp, ins)
    elif ins.opcode == "convolution":
        wf = _conv_flops(comp, ins)
    elif ins.opcode in _ARITH_OPCODES:
        wf = float(shape_numel(ins.shape))
    elif ins.opcode in ("fusion", "call", "map", "reduce", "reduce-window",
                        "scatter", "select-and-scatter", "sort"):
        for c in ins.called_computations():
            sub = module.computations.get(c)
            if sub is not None:
                wf += _computation_flops(module, sub, flop_memo)
    return wf, _instr_hbm_bytes(module, comp, ins)


def _window_cost(module: HloModule, comp: Computation, names,
                 flop_memo: dict) -> tuple:
    """``(flops, hbm_bytes)`` of the computable work inside an overlap
    window."""
    wf = 0.0
    wb = 0.0
    for nm in names:
        f, b = _instr_cost(module, comp, comp.instructions[nm], flop_memo)
        wf += f
        wb += b
    return wf, wb


def _crosses_pods(ins: Instruction, n_devices: int, pods: int) -> bool:
    """Whether any replica group spans two pods (pod = leading mesh axis ⇒
    pod id = device_id // (n_devices // pods))."""
    groups = ins.replica_groups()
    if not groups:
        return False
    per_pod = max(n_devices // pods, 1)
    return any(len({d // per_pod for d in g}) > 1 for g in groups)


def _merged_intervals(*interval_lists) -> list:
    out = sorted(iv for lst in interval_lists for iv in lst)
    merged: list = []
    for b0, b1 in out:
        if merged and b0 <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b1))
        else:
            merged.append((b0, b1))
    return merged


def _simulate_async_runtime(module: HloModule, comp: Computation,
                            hw: dict, flop_memo: dict,
                            pods: int | None = None,
                            n_devices: int | None = None) -> dict:
    """Async-runtime overlap model for a *synchronous* schedule.

    XLA:CPU never emits ``*-start``/``*-done`` pairs — every collective is
    scheduled immediately before its consumer, so the committed schedule
    carries zero overlap slack even for layouts (like the bucketed pod-sync
    pipeline) a latency-hiding scheduler would overlap.  This list-schedules
    the computation onto concurrent serial resources — a compute unit
    (per-instruction ``max(flops/peak, hbm/bw)``), the intra-pod ICI link,
    and (when ``pods`` is given) the inter-pod DCI link (each collective
    alpha-beta priced: ``ici_latency + wire/link_bw``) — respecting data
    dependences, backfilling each resource as soon as dependences allow.  A
    collective is hidden wherever its transfer runs concurrently with
    *other-resource* work (compute or the other link); the remainder is
    exposed.  Message aggregation falls out of the alpha term: many small
    collectives pay many latencies.

    Returns ``{collective_name: (hidden_s, dur_s, link)}`` for the
    computation's sync collectives.
    """
    # the simulation is O(V^2) worst case; a computation with no sync
    # collectives (single-device artifacts, the common capture) has
    # nothing to re-derive — skip it entirely
    if not any(_base_collective(ins.opcode) is not None
               and not ins.opcode.endswith("-start")
               for ins in comp.instructions.values()):
        return {}
    alpha = hw.get("ici_latency", 0.0)
    peak = hw.get("peak_flops", 0.0)
    hbm_bw = hw.get("hbm_bw", 0.0)
    bw = {"ici": hw.get("ici_bw", 0.0),
          "dci": hw.get("dci_bw", hw.get("ici_bw", 0.0))}
    finish: dict = {}
    busy: list = []                     # compute intervals, kept sorted
    links: dict = {"ici": [], "dci": []}
    spans: dict = {}                    # name -> (start, end, link)

    def place(intervals: list, ready: float, dur: float) -> tuple:
        """Backfill onto a serial resource: the earliest gap at or after
        ``ready`` that fits ``dur`` (an async runtime issues out of program
        order as soon as dependences allow)."""
        t = ready
        for b0, b1 in intervals:
            if t + dur <= b0:
                break
            t = max(t, b1)
        intervals.append((t, t + dur))
        intervals.sort()
        return t, t + dur

    for iname in comp.order:                # program order is topological
        ins = comp.instructions[iname]
        ready = max((finish.get(o.lstrip("%"), 0.0) for o in ins.operands),
                    default=0.0)
        if _is_collective_done(ins.opcode):
            finish[iname] = ready
            continue
        base = _base_collective(ins.opcode)
        if base is not None and bw["ici"]:
            op_bytes = sum(shape_bytes(comp.shape_of(o))
                           for o in ins.operands) or ins.out_bytes()
            wire = collective_wire_bytes(base, op_bytes, ins.out_bytes(),
                                         ins.replica_group_size())
            lk = ("dci" if pods and n_devices
                  and _crosses_pods(ins, n_devices, pods) else "ici")
            start, end = place(links[lk], ready, alpha + wire / bw[lk])
            finish[iname] = end
            if not ins.opcode.endswith("-start"):
                spans[iname] = (start, end, lk)
            continue
        f, b = _instr_cost(module, comp, ins, flop_memo)
        dur = max(f / peak if peak else 0.0, b / hbm_bw if hbm_bw else 0.0)
        if dur <= 0.0:
            finish[iname] = ready
            continue
        _start, end = place(busy, ready, dur)
        finish[iname] = end

    out: dict = {}
    other = {"ici": "dci", "dci": "ici"}
    merged = {lk: _merged_intervals(busy, links[other[lk]])
              for lk in ("ici", "dci")}
    for name, (s0, s1, lk) in spans.items():
        hidden = 0.0
        for b0, b1 in merged[lk]:
            if b1 <= s0:
                continue
            if b0 >= s1:
                break
            hidden += min(b1, s1) - max(b0, s0)
        out[name] = (hidden, s1 - s0, lk)
    return out


def analyze(module: HloModule, default_trip: int = 1,
            hw: dict | None = None, pods: int | None = None,
            n_devices: int | None = None) -> HloStats:
    """Roll up executed stats from the entry computation.

    ``default_trip`` is used for while loops without a known_trip_count.
    ``hw`` is the hardware model used for overlap credit (defaults to the
    roofline TPU v5e constants).  ``pods``/``n_devices`` classify
    collectives whose replica groups cross a pod boundary onto the slower
    inter-pod DCI link in the overlap model (pod = leading mesh axis).
    """
    if hw is None:
        hw = _default_hw()
    stats = HloStats(hw=dict(hw))

    def warn(key: str, n: int = 1) -> None:
        stats.warnings[key] = stats.warnings.get(key, 0) + n

    for k, v in getattr(module, "parse_warnings", {}).items():
        warn(k, v)
    if not module.computations or module.entry not in module.computations:
        if "empty-module" not in stats.warnings:
            warn("empty-module")
        return stats
    flop_memo: dict = {}
    pos_memo: dict = {}
    window_memo: dict = {}

    def overlap_of(comp: Computation, ins: Instruction,
                   wire: float) -> dict:
        # exposure is priced against *wire* bytes (what actually crosses the
        # link), so a split reduce-scatter + all-gather layout compares
        # apples-to-apples with the single all-reduce it replaces
        key = (comp.name, ins.name)
        if key not in window_memo:
            if comp.name not in pos_memo:
                pos_memo[comp.name] = {n: i for i, n
                                       in enumerate(comp.order)}
            window, done = _collective_window(comp, ins,
                                              pos_memo[comp.name])
            wf, wb = _window_cost(module, comp, window, flop_memo)
            window_memo[key] = (wf, wb, done)
        wf, wb, done = window_memo[key]
        comm_s = (hw.get("ici_latency", 0.0) + wire / hw["ici_bw"]
                  if hw.get("ici_bw") else 0.0)
        hide_s = max(wf / hw["peak_flops"] if hw.get("peak_flops") else 0.0,
                     wb / hw["hbm_bw"] if hw.get("hbm_bw") else 0.0)
        hidden_s = min(comm_s, hide_s)
        exposed = (wire * (1.0 - hidden_s / comm_s)
                   if comm_s > 0 else float(wire))
        return {"window_flops": wf, "window_hbm_bytes": wb,
                "comm_s": comm_s, "link": "ici",
                "hidden_s": hidden_s, "exposed_bytes": exposed,
                "overlapped": hidden_s > 0.0,
                "async": ins.opcode.endswith("-start"), "done": done}

    def visit(comp: Computation, mult: float, top_level: bool):
        for iname in comp.order:
            ins = comp.instructions[iname]
            try:
                visit_one(comp, ins, mult, top_level)
            except Exception:                               # noqa: BLE001
                # a malformed instruction must not sink the whole rollup —
                # skip it, count it, keep walking
                warn(f"instr-error:{ins.opcode}")

    def visit_one(comp: Computation, ins: Instruction, mult: float,
                  top_level: bool):
        if _is_collective_done(ins.opcode):
            return              # paired with its *-start; no payload, free
        base = _base_collective(ins.opcode)
        if base is None and ins.opcode not in _KNOWN_OPCODES:
            warn(f"unknown-opcode:{ins.opcode}")
        if base is not None:
            op_bytes = sum(shape_bytes(comp.shape_of(o)) for o in ins.operands)
            if op_bytes == 0:                 # e.g. unresolved operand
                op_bytes = ins.out_bytes()
            stats.collective_bytes[base] = (
                stats.collective_bytes.get(base, 0.0) + op_bytes * mult)
            group = ins.replica_group_size()
            wire = collective_wire_bytes(base, op_bytes,
                                         ins.out_bytes(), group)
            stats.collective_wire_bytes[base] = (
                stats.collective_wire_bytes.get(base, 0.0) + wire * mult)
            mo = re.search(r'op_name="([^"]*)"', ins.attrs)
            stats.collective_instances.append({
                "opcode": base, "name": ins.name, "bytes": op_bytes,
                "mult": mult, "group_size": group,
                "computation": comp.name, "wire_bytes": wire,
                "op_name": mo.group(1) if mo else "",
                **overlap_of(comp, ins, wire),
            })
        if ins.opcode == "while":
            trip = ins.trip_count() or default_trip
            for c in ins.called_computations():
                sub = module.computations.get(c)
                if sub is not None:
                    visit(sub, mult * trip, top_level)
            return
        if ins.opcode in ("call", "conditional", "async-start"):
            for c in ins.called_computations():
                sub = module.computations.get(c)
                if sub is not None:
                    visit(sub, mult, top_level)
            # fall through to count this op's traffic too (cheap)
        if top_level:
            if ins.opcode not in _FREE_OPCODES and base is None \
                    and ins.opcode not in ("while",):
                stats.kernel_counts[ins.name] = (
                    stats.kernel_counts.get(ins.name, 0) + mult)
                if ins.opcode == "fusion":
                    in_bytes, ob = _fusion_io_bytes(module, comp, ins)
                    stats.hbm_bytes += (in_bytes + ob) * mult
                elif ins.opcode in ("dynamic-slice", "gather"):
                    in_bytes = ins.out_bytes()
                    stats.hbm_bytes += 2 * in_bytes * mult
                elif ins.opcode == "dynamic-update-slice":
                    upd = shape_bytes(comp.shape_of(ins.operands[1])
                                      if len(ins.operands) > 1 else "")
                    in_bytes = upd
                    stats.hbm_bytes += 2 * upd * mult
                else:
                    in_bytes = sum(shape_bytes(comp.shape_of(o))
                                   for o in ins.operands)
                    stats.hbm_bytes += (in_bytes + ins.out_bytes()) * mult
                if ins.name not in stats.kernel_meta:
                    mo = re.search(r'op_name="([^"]*)"', ins.attrs)
                    stats.kernel_meta[ins.name] = {
                        "opcode": ins.opcode,
                        "op_name": mo.group(1) if mo else "",
                        "bytes": in_bytes + ins.out_bytes(),
                    }
            if ins.opcode == "dot":
                stats.flops += _dot_flops(comp, ins) * mult
            elif ins.opcode == "convolution":
                stats.flops += _conv_flops(comp, ins) * mult
            elif ins.opcode in _ARITH_OPCODES:
                stats.flops += shape_numel(ins.shape) * mult
            elif ins.opcode in ("fusion", "reduce", "map", "scatter",
                                "reduce-window", "sort"):
                for c in ins.called_computations():
                    sub = module.computations.get(c)
                    if sub is not None:
                        stats.flops += _computation_flops(
                            module, sub, flop_memo) * mult

    visit(module.entry_computation(), 1.0, True)

    # Synchronous schedules (XLA:CPU) expose no committed overlap windows —
    # re-derive sync collectives' exposure at the entry level from the
    # async-runtime model, keeping explicit *-start/*-done spans where the
    # artifact already committed to an async schedule.
    entry = module.entry_computation()
    try:
        sim = _simulate_async_runtime(module, entry, hw, flop_memo,
                                      pods=pods, n_devices=n_devices)
    except Exception:                                       # noqa: BLE001
        warn("sim-error")
        sim = {}
    for inst in stats.collective_instances:
        if inst["computation"] != entry.name or inst["async"]:
            continue
        hidden, dur, lk = sim.get(inst["name"], (None, None, None))
        if dur is None:
            continue
        inst["hidden_s"] = hidden
        inst["comm_s"] = dur
        inst["link"] = lk
        inst["overlapped"] = hidden > 0.0
        inst["exposed_bytes"] = (inst["wire_bytes"]
                                 * max(0.0, 1.0 - hidden / dur)
                                 if dur > 0 else 0.0)
    return stats


def analyze_text(text: str, default_trip: int = 1, hw: dict | None = None,
                 pods: int | None = None,
                 n_devices: int | None = None) -> HloStats:
    """``parse_hlo`` + ``analyze`` with a no-raise guarantee: a dump the
    parser cannot make sense of yields empty stats with
    ``warnings={"parse-error": 1}`` instead of an exception."""
    try:
        module = parse_hlo(text)
    except Exception:                                       # noqa: BLE001
        stats = HloStats(hw=dict(hw) if hw is not None else _default_hw())
        stats.warnings["parse-error"] = 1
        return stats
    return analyze(module, default_trip=default_trip, hw=hw,
                   pods=pods, n_devices=n_devices)
