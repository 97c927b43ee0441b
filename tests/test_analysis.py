"""Static-analysis subsystem: registry + spec grammar, Finding/Baseline
plumbing, the five builtin passes against golden HLO, the collective
wire-bytes golden table (with pod/DCI classification), property tests
(spec round-trip, mutation robustness), and subprocess end-to-end
seeded-defect checks over real compiled train steps.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest
from hypothesis import given, settings, strategies as st

from repro import analysis
from repro.analysis import (Baseline, Finding, Findings, PASS_REGISTRY,
                            estimate_peak_bytes, format_pass_spec,
                            parse_pass_spec, resolve_passes, run_passes,
                            spec_of)
from repro.core.hlo import (analyze_text, collective_wire_bytes, parse_hlo)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HW = {"peak_flops": 100e12, "hbm_bw": 800e9, "ici_bw": 50e9,
      "dci_bw": 12.5e9, "ici_latency": 0.0, "hbm_bytes": 16 * 2 ** 30}

#: 2x2x2 pod x data x model mesh, row-major device ids
MESH = {"pod": 2, "data": 2, "model": 2}
MODEL_GROUPS = "{{0,1},{2,3},{4,5},{6,7}}"      # fastest axis -> model
POD_GROUPS = "{{0,4},{1,5},{2,6},{3,7}}"        # slowest axis -> pod

#: DEFAULT_RULES as a plain dict, without importing jax in this process
RULES = {
    "p_vocab": "model", "p_embed": "data", "p_heads": "model",
    "p_ff": "model", "p_experts": "data", "p_experts_ep": "model",
    "batch": ("pod", "data"), "seq_sp": "model", "heads": "model",
    "ff": "model", "vocab": "model", "experts_ep": "model",
}

RUN_KW = dict(mesh_axes=MESH, rules=RULES, kind="train", hw=HW,
              pods=2, n_devices=8, emit_events=False)


# ---------------------------------------------------------------------------
# registry + spec grammar
# ---------------------------------------------------------------------------

def test_registry_has_the_five_builtin_passes():
    for name in ("exposed-collectives", "implicit-reshard",
                 "dtype-promotion", "peak-memory", "host-sync"):
        assert name in PASS_REGISTRY


def test_spec_parse_and_knob_override():
    suite = resolve_passes(
        "exposed-collectives:threshold_frac=0.5,min_bytes=1024,peak-memory")
    assert len(suite) == 2
    assert suite[0].knobs["threshold_frac"] == 0.5
    assert suite[0].knobs["min_bytes"] == 1024
    assert suite[1].REGISTRY_NAME == "peak-memory"


def test_unknown_pass_and_unknown_knob_raise():
    with pytest.raises(KeyError):
        resolve_passes("no-such-pass")
    with pytest.raises(TypeError):
        resolve_passes("peak-memory:bogus_knob=1")


def test_spec_of_records_only_non_default_knobs():
    suite = resolve_passes("exposed-collectives:threshold_frac=0.5,host-sync")
    assert spec_of(suite) == "exposed-collectives:threshold_frac=0.5,host-sync"


# ---------------------------------------------------------------------------
# Finding / Baseline plumbing
# ---------------------------------------------------------------------------

def _mk(pass_name="p", sev="warn", opcode="all-gather", comp="main",
        ins="ag.1"):
    return Finding(pass_name=pass_name, severity=sev, message="m",
                   opcode=opcode, computation=comp, instruction=ins)


def test_finding_key_shape():
    assert _mk().key == "p:all-gather:main/ag.1"
    assert _mk(ins="").key == "p:all-gather:main"
    assert Finding(pass_name="p", severity="warn", message="m").key == "p:-:-"


def test_baseline_exact_then_glob(tmp_path):
    f = Findings()
    f.extend([_mk(ins="ag.1"), _mk(ins="ag.2"), _mk(pass_name="q")])
    base = {"version": 1, "suppress": [
        {"key": "p:all-gather:main/ag.1", "reason": "known"},
        {"key": "q:*"},
    ]}
    assert f.apply_baseline(base) == 2
    live = f.unsuppressed("warn")
    assert [x.instruction for x in live] == ["ag.2"]
    assert f.findings[0].suppressed_reason == "known"
    # write-baseline round trip accepts what still fires
    p = tmp_path / "b.json"
    f.write_baseline(str(p), reason="adopt")
    doc = json.loads(p.read_text())
    assert doc["suppress"] == [{"key": "p:all-gather:main/ag.2",
                                "reason": "adopt"}]
    f2 = Findings()
    f2.extend([_mk(ins="ag.2")])
    assert f2.apply_baseline(str(p)) == 1
    assert not f2.unsuppressed()


def test_findings_severity_filter_and_counts():
    f = Findings(label="cell")
    f.extend([_mk(sev="info"), _mk(sev="warn"), _mk(sev="error")])
    assert len(f.unsuppressed("warn")) == 2
    assert f.max_severity() == "error"
    assert f.counts() == {"p": {"info": 1, "warn": 1, "error": 1}}
    d = json.loads(f.to_json())
    assert d["label"] == "cell" and d["n_findings"] == 3
    assert len(d["findings"]) == 3 and d["findings"][0]["key"]


# ---------------------------------------------------------------------------
# exposed-collectives
# ---------------------------------------------------------------------------

BLOCKING_HLO = """
HloModule blocking_sync

ENTRY %main (p1: f32[1048576]) -> f32[1048576] {
  %p1 = f32[1048576]{0} parameter(0)
  %ar = f32[1048576]{0} all-reduce(f32[1048576]{0} %p1), replica_groups=""" \
    + POD_GROUPS + """, to_apply=%add
  ROOT %use = f32[1048576]{0} add(f32[1048576]{0} %ar, f32[1048576]{0} %ar)
}
"""

OVERLAPPED_HLO = """
HloModule overlapped_sync

ENTRY %main (p0: f32[1024,1024], p1: f32[4096]) -> (f32[1024,1024], f32[4096]) {
  %p0 = f32[1024,1024]{1,0} parameter(0)
  %p1 = f32[4096]{0} parameter(1)
  %ar-start = f32[4096]{0} all-reduce-start(f32[4096]{0} %p1), replica_groups=""" \
    + POD_GROUPS + """, to_apply=%add
  %dot = f32[1024,1024]{1,0} dot(f32[1024,1024]{1,0} %p0, f32[1024,1024]{1,0} %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar-done = f32[4096]{0} all-reduce-done(f32[4096]{0} %ar-start)
  ROOT %t = (f32[1024,1024]{1,0}, f32[4096]{0}) tuple(f32[1024,1024]{1,0} %dot, f32[4096]{0} %ar-done)
}
"""


def test_exposed_fires_on_blocking_sync():
    f = run_passes(BLOCKING_HLO, "exposed-collectives", **RUN_KW)
    hits = f.by_pass("exposed-collectives")
    assert len(hits) == 1
    (h,) = hits
    assert h.opcode == "all-reduce" and h.severity == "warn"
    assert h.data["link"] == "dci" and h.data["exposed_frac"] > 0.9
    assert h.seconds_impact > 0 and h.bytes_impact > 0
    assert "overlap" in h.fix_hint


def test_exposed_quiet_when_async_pair_hides_the_transfer():
    f = run_passes(OVERLAPPED_HLO, "exposed-collectives", **RUN_KW)
    assert not f.by_pass("exposed-collectives")


def test_exposed_link_filter_and_aggregate_budget():
    # per-instance gating off (threshold > 1), tiny DCI budget -> exactly
    # one summary finding anchored at total[dci]
    spec = ("exposed-collectives:link=dci,threshold_frac=1.1,"
            "total_budget_s=1e-07")
    f = run_passes(BLOCKING_HLO, spec, **RUN_KW)
    (h,) = f.by_pass("exposed-collectives")
    assert h.instruction == "total[dci]"
    assert h.data["total_exposed_s"] > 1e-07
    assert f.meta["exposed_s:dci"] == pytest.approx(h.data["total_exposed_s"])
    # the same budget scoped to ICI sees no traffic at all
    spec = ("exposed-collectives:link=ici,threshold_frac=1.1,"
            "total_budget_s=1e-07")
    f = run_passes(BLOCKING_HLO, spec, **RUN_KW)
    assert not f.by_pass("exposed-collectives")
    assert f.meta["exposed_s:ici"] == 0.0


# ---------------------------------------------------------------------------
# implicit-reshard
# ---------------------------------------------------------------------------

RESHARD_ACT_HLO = """
HloModule reshard_activation

ENTRY %main (p0: f32[512,512]) -> f32[1024,512] {
  %p0 = f32[512,512]{1,0} parameter(0)
  %dot = f32[512,512]{1,0} dot(f32[512,512]{1,0} %p0, f32[512,512]{1,0} %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jit(main)/dot_general"}
  ROOT %ag = f32[1024,512]{1,0} all-gather(f32[512,512]{1,0} %dot), replica_groups=""" \
    + MODEL_GROUPS + """, dimensions={0}, use_global_device_ids=true, metadata={op_name="jit(step)/jit(main)/dot_general"}
}
"""

RESHARD_WEIGHT_HLO = """
HloModule weight_gather

ENTRY %main (p0: bf16[512,512]) -> f32[1024,512] {
  %p0 = bf16[512,512]{1,0} parameter(0), metadata={op_name="params['embed']"}
  %cv = f32[512,512]{1,0} convert(bf16[512,512]{1,0} %p0)
  ROOT %ag = f32[1024,512]{1,0} all-gather(f32[512,512]{1,0} %cv), replica_groups=""" \
    + MODEL_GROUPS + """, dimensions={0}, use_global_device_ids=true, metadata={op_name="jit(step)/jit(main)/gather"}
}
"""

RESHARD_RSAG_HLO = """
HloModule rs_ag_decomposition

%cond (cp: (f32[512,512], s32[])) -> pred[] {
  %cp = (f32[512,512]{1,0}, s32[]) parameter(0)
  %iter = s32[] get-tuple-element((f32[512,512]{1,0}, s32[]) %cp), index=1
  %lim = s32[] constant(4)
  ROOT %lt = pred[] compare(s32[] %iter, s32[] %lim), direction=LT
}

%body (bp: (f32[512,512], s32[])) -> (f32[512,512], s32[]) {
  %bp = (f32[512,512]{1,0}, s32[]) parameter(0)
  %acc = f32[512,512]{1,0} get-tuple-element((f32[512,512]{1,0}, s32[]) %bp), index=0
  %iter2 = s32[] get-tuple-element((f32[512,512]{1,0}, s32[]) %bp), index=1
  %grad = f32[1024,512]{1,0} iota(), iota_dimension=0
  %rs = f32[512,512]{1,0} reduce-scatter(f32[1024,512]{1,0} %grad), replica_groups=""" \
    + MODEL_GROUPS + """, dimensions={0}, to_apply=%add, metadata={op_name="jit(step)/jit(main)/psum"}
  %sum = f32[512,512]{1,0} add(f32[512,512]{1,0} %acc, f32[512,512]{1,0} %rs)
  %one = s32[] constant(1)
  %next = s32[] add(s32[] %iter2, s32[] %one)
  ROOT %rt = (f32[512,512]{1,0}, s32[]) tuple(f32[512,512]{1,0} %sum, s32[] %next)
}

ENTRY %main (p0: f32[512,512]) -> f32[1024,512] {
  %p0 = f32[512,512]{1,0} parameter(0)
  %c0 = f32[512,512]{1,0} constant(0)
  %z = s32[] constant(0)
  %init = (f32[512,512]{1,0}, s32[]) tuple(f32[512,512]{1,0} %c0, s32[] %z)
  %w = (f32[512,512]{1,0}, s32[]) while((f32[512,512]{1,0}, s32[]) %init), condition=%cond, body=%body
  %g = f32[512,512]{1,0} get-tuple-element((f32[512,512]{1,0}, s32[]) %w), index=0
  ROOT %ag = f32[1024,512]{1,0} all-gather(f32[512,512]{1,0} %g), replica_groups=""" \
    + MODEL_GROUPS + """, dimensions={0}, use_global_device_ids=true, metadata={op_name="jit(step)/jit(main)/while"}
}
"""


def test_reshard_fires_on_activation_gather_over_tensor_axis():
    f = run_passes(RESHARD_ACT_HLO, "implicit-reshard", **RUN_KW)
    (h,) = f.by_pass("implicit-reshard")
    assert h.opcode == "all-gather"
    assert h.data["axes"] == ["model"]
    assert "mis-sharded" in h.fix_hint


def test_reshard_quiet_on_intended_batch_axis_gather():
    # the rs+ag gradient-sync layout gathers over the batch axes: intended
    text = RESHARD_ACT_HLO.replace(MODEL_GROUPS, POD_GROUPS)
    f = run_passes(text, "implicit-reshard", **RUN_KW)
    assert not f.by_pass("implicit-reshard")


def test_reshard_quiet_on_param_rooted_weight_gather():
    f = run_passes(RESHARD_WEIGHT_HLO, "implicit-reshard", **RUN_KW)
    assert not f.by_pass("implicit-reshard")


def test_reshard_quiet_on_rs_ag_decomposition_through_while():
    """The all-gather tail of an all-reduce XLA split around a microbatch
    loop (reduce-scatter inside the body, gather on the loop-carried
    accumulator) is intended reduction traffic."""
    f = run_passes(RESHARD_RSAG_HLO, "implicit-reshard", **RUN_KW)
    assert not f.by_pass("implicit-reshard")
    # break the evidence: a reduce-scatter over DIFFERENT axes is not the
    # partner of this gather -> the finding comes back
    text = RESHARD_RSAG_HLO.replace(
        "reduce-scatter(f32[1024,512]{1,0} %grad), replica_groups="
        + MODEL_GROUPS,
        "reduce-scatter(f32[1024,512]{1,0} %grad), replica_groups="
        + POD_GROUPS)
    f = run_passes(text, "implicit-reshard", **RUN_KW)
    assert len(f.by_pass("implicit-reshard")) == 1


def test_reshard_skips_explicitly_requested_collectives():
    text = RESHARD_ACT_HLO.replace(
        'op_name="jit(step)/jit(main)/dot_general"',
        'op_name="jit(step)/jit(main)/jit(shmap_body)/all_gather"')
    f = run_passes(text, "implicit-reshard", **RUN_KW)
    assert not f.by_pass("implicit-reshard")


def test_reshard_allow_axes_knob():
    f = run_passes(RESHARD_ACT_HLO, "implicit-reshard:allow_axes=model",
                   **RUN_KW)
    assert not f.by_pass("implicit-reshard")


# ---------------------------------------------------------------------------
# dtype-promotion
# ---------------------------------------------------------------------------

DTYPE_HLO = """
HloModule f32_leak

ENTRY %main (p0: bf16[1024,1024]) -> f32[1024,1024] {
  %p0 = bf16[1024,1024]{1,0} parameter(0)
  %cv = f32[1024,1024]{1,0} convert(bf16[1024,1024]{1,0} %p0)
  ROOT %mul = f32[1024,1024]{1,0} multiply(f32[1024,1024]{1,0} %cv, f32[1024,1024]{1,0} %cv)
}
"""


def test_dtype_fires_on_large_upcast():
    f = run_passes(DTYPE_HLO, "dtype-promotion", **RUN_KW)
    (h,) = f.by_pass("dtype-promotion")
    assert h.opcode == "convert" and h.data["src"] == "bf16"
    assert h.data["numel"] == 1024 * 1024


def test_dtype_exempts_reduction_accumulator():
    text = DTYPE_HLO.replace(
        "ROOT %mul = f32[1024,1024]{1,0} multiply(f32[1024,1024]{1,0} %cv, "
        "f32[1024,1024]{1,0} %cv)",
        "ROOT %r = f32[1024]{0} reduce(f32[1024,1024]{1,0} %cv, f32[] %zero)"
        ", dimensions={1}, to_apply=%add")
    f = run_passes(text, "dtype-promotion", **RUN_KW)
    assert not f.by_pass("dtype-promotion")
    # the exemption is a knob
    f = run_passes(text, "dtype-promotion:allow_reduce=false", **RUN_KW)
    assert len(f.by_pass("dtype-promotion")) == 1


def test_dtype_min_numel_floor():
    f = run_passes(DTYPE_HLO, "dtype-promotion:min_numel=2097152", **RUN_KW)
    assert not f.by_pass("dtype-promotion")


# ---------------------------------------------------------------------------
# peak-memory
# ---------------------------------------------------------------------------

PEAK_HLO = """
HloModule peak

ENTRY %main (p0: f32[512,512]) -> f32[512,512] {
  %p0 = f32[512,512]{1,0} parameter(0)
  %e = f32[512,512]{1,0} exponential(f32[512,512]{1,0} %p0)
  ROOT %d = f32[512,512]{1,0} dot(f32[512,512]{1,0} %e, f32[512,512]{1,0} %e), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""

MIB = 2 ** 20


def test_estimate_peak_bytes_liveness():
    est = estimate_peak_bytes(parse_hlo(PEAK_HLO))
    assert est["persistent_bytes"] == 1 * MIB          # the parameter
    assert est["transient_peak_bytes"] == 2 * MIB      # %e and %d both live
    assert est["peak_bytes"] == 3 * MIB
    assert est["at_instruction"] == "d"


def test_peak_memory_budget_gate():
    f = run_passes(PEAK_HLO, "peak-memory", device_budget=2 * MIB,
                   **RUN_KW)
    (h,) = f.by_pass("peak-memory")
    assert h.severity == "error" and h.opcode == "liveness"
    assert f.meta["peak_bytes_est"] == 3 * MIB
    # 16 GiB default budget: quiet, but the estimate is still published
    f = run_passes(PEAK_HLO, "peak-memory", **RUN_KW)
    assert not f.by_pass("peak-memory")
    assert f.meta["peak_bytes_est"] == 3 * MIB


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------

HOST_HLO = """
HloModule host_sync, input_output_alias={ {0}: (0, {}, may-alias) }

ENTRY %main (p0: f32[512,512], p1: f32[512,512]) -> (f32[512,512], f32[512,512]) {
  %p0 = f32[512,512]{1,0} parameter(0)
  %p1 = f32[512,512]{1,0} parameter(1)
  %cc = f32[512,512]{1,0} custom-call(f32[512,512]{1,0} %p0), custom_call_target="xla_ffi_python_cpu_callback"
  ROOT %t = (f32[512,512]{1,0}, f32[512,512]{1,0}) tuple(f32[512,512]{1,0} %cc, f32[512,512]{1,0} %p1)
}
"""


def test_host_sync_flags_callback_and_missed_donation():
    f = run_passes(HOST_HLO, "host-sync", **RUN_KW)
    hits = f.by_pass("host-sync")
    by_op = {h.opcode: h for h in hits}
    assert "custom-call" in by_op            # host callback round trip
    # p0 is aliased (donated); p1 matches an output shape but is not
    assert by_op["parameter"].instruction == "p1"
    assert by_op["parameter"].data["param_index"] == 1
    assert "donate" in by_op["parameter"].fix_hint


def test_host_sync_min_donate_bytes_floor():
    f = run_passes(HOST_HLO, "host-sync:min_donate_bytes=2097152", **RUN_KW)
    assert [h.opcode for h in f.by_pass("host-sync")] == ["custom-call"]


# ---------------------------------------------------------------------------
# collective wire-bytes golden table  (B = 96000 payload bytes)
# ---------------------------------------------------------------------------

#: (opcode, op_bytes, out_bytes, N) -> exact wire bytes of the ring model
WIRE_TABLE = [
    ("all-reduce",         96000, 96000,  2,  96000.0),
    ("all-reduce",         96000, 96000,  4, 144000.0),
    ("all-reduce",         96000, 96000,  8, 168000.0),
    ("all-gather",         48000, 96000,  2,  48000.0),
    ("all-gather",         24000, 96000,  4,  72000.0),
    ("all-gather",         12000, 96000,  8,  84000.0),
    ("reduce-scatter",     96000, 48000,  2,  48000.0),
    ("reduce-scatter",     96000, 24000,  4,  72000.0),
    ("reduce-scatter",     96000, 12000,  8,  84000.0),
    ("all-to-all",         96000, 96000,  2,  48000.0),
    ("all-to-all",         96000, 96000,  4,  72000.0),
    ("all-to-all",         96000, 96000,  8,  84000.0),
    ("collective-permute", 96000, 96000,  2,  96000.0),
    ("collective-permute", 96000, 96000,  4,  96000.0),
    ("collective-permute", 96000, 96000,  8,  96000.0),
]


@pytest.mark.parametrize("opcode,op_b,out_b,n,expected", WIRE_TABLE)
def test_collective_wire_bytes_golden(opcode, op_b, out_b, n, expected):
    assert collective_wire_bytes(opcode, op_b, out_b, n) == expected


#: groups on an 8-device 2-pod topology: (groups, group size, crosses DCI)
LINK_TABLE = [
    ("{{0,4},{1,5},{2,6},{3,7}}", 2, "dci"),
    ("{{0,1},{2,3},{4,5},{6,7}}", 2, "ici"),
    ("{{0,2,4,6},{1,3,5,7}}",     4, "dci"),
    ("{{0,1,2,3},{4,5,6,7}}",     4, "ici"),
    ("{{0,1,2,3,4,5,6,7}}",       8, "dci"),
]


@pytest.mark.parametrize("groups,n,link", LINK_TABLE)
@pytest.mark.parametrize("opcode", ["all-reduce", "all-gather",
                                    "reduce-scatter", "all-to-all"])
def test_collective_link_classification(opcode, groups, n, link):
    numel = 65536
    if opcode == "all-gather":
        in_shape, out_shape = f"f32[{numel // n}]", f"f32[{numel}]"
    elif opcode == "reduce-scatter":
        in_shape, out_shape = f"f32[{numel}]", f"f32[{numel // n}]"
    else:
        in_shape = out_shape = f"f32[{numel}]"
    dims = "" if opcode in ("all-reduce", "all-to-all") else \
        " dimensions={0},"
    text = f"""
HloModule link_class

ENTRY %main (p0: {in_shape}) -> {out_shape} {{
  %p0 = {in_shape}{{0}} parameter(0)
  ROOT %c = {out_shape}{{0}} {opcode}({in_shape}{{0}} %p0), replica_groups={groups},{dims} to_apply=%add
}}
"""
    stats = analyze_text(text, hw=HW, pods=2, n_devices=8)
    (inst,) = stats.collective_instances
    assert inst["link"] == link, (opcode, groups)


# ---------------------------------------------------------------------------
# event emission + robustness plumbing
# ---------------------------------------------------------------------------

def test_findings_emitted_as_session_events():
    emitted = []

    class _Handler:
        def emit(self, ev):
            emitted.append(ev)

    class _Session:
        handler = _Handler()

    f = run_passes(BLOCKING_HLO, "exposed-collectives", session=_Session(),
                   mesh_axes=MESH, rules=RULES, kind="train", hw=HW,
                   pods=2, n_devices=8)
    assert len(emitted) == len(f.findings) == 1
    ev = emitted[0]
    assert ev.kind.name == "FINDING"
    assert ev.attrs["severity"] == "warn" and ev.attrs["key"] == \
        f.findings[0].key


def test_unparseable_artifact_warns_never_raises():
    f = run_passes("this is not HLO at all {{{", None, **RUN_KW)
    assert isinstance(f, Findings)
    assert f.warnings, "garbage input must surface a counted warning"
    assert not any(k.startswith("pass-error") for k in f.warnings)


def test_pass_error_backstop():
    class Exploding(analysis.AnalysisPass):
        REGISTRY_NAME = "exploding"

        def run(self, ctx):
            raise RuntimeError("boom")

    f = run_passes(BLOCKING_HLO, [Exploding()], **RUN_KW)
    assert f.warnings.get("pass-error:exploding") == 1
    (h,) = f.findings
    assert h.severity == "error" and "boom" in h.message


# ---------------------------------------------------------------------------
# property tests
# ---------------------------------------------------------------------------

_NAMES = sorted(PASS_REGISTRY)
_STR_CHOICES = ["warn", "error", "info", "dci", "ici", "model+data"]


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.integers(0, len(_NAMES) - 1),
                          st.integers(0, 3),
                          st.integers(0, 10 ** 6),
                          st.floats(0.0, 100.0),
                          st.booleans(),
                          st.integers(0, len(_STR_CHOICES) - 1)),
                min_size=1, max_size=6))
def test_pass_spec_round_trips_through_registry_parser(draws):
    """format_pass_spec(parse_pass_spec(s)) is the identity on canonical
    specs built from real registry passes with type-correct knob values."""
    entries = []
    for name_i, n_knobs, iv, fv, bv, si in draws:
        name = _NAMES[name_i]
        cls = PASS_REGISTRY[name]
        knobs = {}
        for k, default in sorted(cls.KNOBS.items())[:n_knobs]:
            if isinstance(default, bool):
                knobs[k] = bv
            elif isinstance(default, int):
                knobs[k] = iv
            elif isinstance(default, float):
                knobs[k] = fv
            else:
                knobs[k] = _STR_CHOICES[si]
        entries.append((name, knobs))
    spec = format_pass_spec(entries)
    assert parse_pass_spec(spec) == entries
    assert format_pass_spec(parse_pass_spec(spec)) == spec
    # every canonical spec also instantiates
    suite = resolve_passes(spec)
    assert [p.REGISTRY_NAME for p in suite] == [n for n, _ in entries]


_MUTATION_BASE = (RESHARD_RSAG_HLO + DTYPE_HLO + HOST_HLO
                  + BLOCKING_HLO + OVERLAPPED_HLO)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 5), st.integers(0, 10 ** 9),
                          st.integers(0, 10 ** 9)),
                min_size=1, max_size=8))
def test_random_hlo_mutations_never_make_passes_raise(mutations):
    """Dropped/truncated/duplicated/corrupted lines must degrade to counted
    warnings — run_passes never raises AND no pass crashes internally."""
    lines = _MUTATION_BASE.splitlines()
    for kind, a, b in mutations:
        if not lines:
            break
        i = a % len(lines)
        j = b % len(lines)
        if kind == 0:
            del lines[i]
        elif kind == 1:
            lines[i] = lines[i][:b % (len(lines[i]) + 1)]
        elif kind == 2:
            lines.insert(j, lines[i])
        elif kind == 3:
            lines[i], lines[j] = lines[j], lines[i]
        elif kind == 4:
            toks = lines[i].split(" ")
            if toks:
                toks[a % len(toks)] = "@@corrupt@@"
            lines[i] = " ".join(toks)
        else:
            lines.insert(i, "%%% not hlo %%%")
    f = run_passes("\n".join(lines), None, **RUN_KW)
    assert isinstance(f, Findings)
    assert not any(k.startswith("pass-error") for k in f.warnings), \
        f.warnings


# ---------------------------------------------------------------------------
# end-to-end: real compiled train cells (subprocess, 8 virtual devices)
# ---------------------------------------------------------------------------

def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_e2e_seeded_reshard_defect_fires_and_green_is_quiet():
    out = run_sub("""
        import sys
        sys.argv = ["lint", "--devices", "8"]
        from repro.launch import lint

        green = lint.smoke_cell("qwen3-32b", spec="implicit-reshard")
        base = {"version": 1,
                "suppress": [{"key": f.key} for f in green.findings]}
        green.apply_baseline(base)
        assert not green.unsuppressed("warn"), green.to_json()

        defect = lint.smoke_cell("qwen3-32b",
                                 rules_patch=dict(lint.DEFECT_RULES),
                                 spec="implicit-reshard", baseline=base)
        hits = [f for f in defect.unsuppressed("warn")
                if f.pass_name == "implicit-reshard"]
        assert hits, defect.to_json()
        assert all(f.data["axes"] == ["model"] for f in hits)
        print("OK green=", len(green.findings), " defect_new=", len(hits))
    """)
    assert "OK" in out


def test_e2e_blocking_sync_trips_dci_budget_overlap_does_not():
    out = run_sub("""
        import sys
        sys.argv = ["lint", "--devices", "8"]
        from repro.launch import lint

        spec = ("exposed-collectives:link=dci,threshold_frac=1.1,"
                "min_bytes=0,total_budget_s=3e-05")
        ok = lint.smoke_cell("qwen3-32b", overlap_sync=True, spec=spec)
        assert not ok.by_pass("exposed-collectives"), ok.to_json()

        bad = lint.smoke_cell("qwen3-32b", overlap_sync=False, spec=spec)
        (h,) = bad.by_pass("exposed-collectives")
        assert h.instruction == "total[dci]"
        assert h.data["total_exposed_s"] > 3e-05
        print("OK exposed_us=", h.data["total_exposed_s"] * 1e6)
    """)
    assert "OK" in out


def test_e2e_peak_memory_estimate_tracks_measured_peak():
    """The static liveness estimate must land within 20% of the
    dryrun-measured (XLA memory_analysis) peak."""
    out = run_sub("""
        import sys
        sys.argv = ["lint", "--devices", "8"]
        from repro.launch import lint

        f = lint.smoke_cell("qwen3-32b", spec="peak-memory")
        est = f.meta["peak_bytes_est"]
        meas = f.meta["measured_peak_bytes"]
        assert meas > 0
        ratio = est / meas
        assert 0.8 <= ratio <= 1.2, (est, meas, ratio)
        print("OK ratio=", ratio)
    """)
    assert "OK" in out
