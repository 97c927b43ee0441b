"""The ``train_mesh`` kind on a tiny conv/attention model with experts, run
through the harness on the CPU: a cell added by files alone, its sound run
correct, the faults that a training cell can have and the float8 control
caught, and the new cells' readers reading a recorded tiny run."""

import io
import json
import os
import subprocess
import sys
import time

import pytest

import tiny
from bench import flops, flops_moe, harness, peaks
from bench.faults import planted
from bench.registry import Registry

SEED = 2**31 + 977
CELL = "tiny-lfm2.train_mesh"
PEAK = peaks.peaks("TPU v5 lite")

CONFIG = {
    "name": "tiny-lfm2",
    "source": "a CPU test configuration of the LFM2-MoE reference's layout",
    "reference": "lfm2_moe",
    "reduced": [],
    "program": {
        "name": "tiny-lfm2", "family": "conv_moe", "n_layers": 6,
        "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
        "d_ff": 96, "vocab_size": 256, "mlp": "swiglu", "qk_norm": True,
        "rope_theta": 1e6, "tie_embeddings": True, "rmsnorm_eps": 1e-5,
        "n_experts": 8, "n_experts_active": 2, "d_ff_expert": 32,
        "router": "sigmoid_bias", "n_experts_held": 4,
        "layer_pattern": ["conv", "conv", "attn", "conv", "conv", "conv"],
        "n_dense_layers": 2,
        "dtype": "bfloat16", "param_dtype": "float32",
        "opt_moment_dtype": "float32", "remat": "full",
        # 64 positions take the blocked attention, as 8,192 do on the chip
        "attn_blocked_threshold": 32,
    },
}
MIX = {"kind": "train_mesh", "mesh": [1, 1], "batch": 4, "seq": 64,
       "tokens": "zipf", "tools": "kernel_freq,timeline",
       "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                     "weight_decay": 0.1, "clip_norm": 1.0,
                     "warmup_steps": 2, "total_steps": 10000,
                     "min_lr_frac": 0.1},
       "check_steps": 3, "reference_rows": 1}
#: between the tiny model's sound readings on the CPU and its control's,
#: the largest and the smallest over four seeds (loss 1.75e-4 / 6.6e-4,
#: gradient norm 3.74e-2 / 9.06e-2, change 1.25e-2 / 1.96e-2); at float32
#: compute the program reads 2.6e-7, 8.3e-7 and 2.2e-6
LIMITS = {"loss_gap": 4e-4, "grad_norm_gap": 6e-2, "update_norm_gap": 1.6e-2}
READERS = ("mfu.train_8k", "device_idle_share.train_8k",
           "expert_load_max_over_mean.train_8k",
           "expert_matmul_roofline.train_8k")


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    root = tiny.tiny_root(str(tmp_path_factory.mktemp("bench")))
    b = os.path.join(root, "bench")
    for path, obj in (("configs/tiny-lfm2.json", CONFIG),
                      ("traffic/tiny_train_mesh.json", MIX),
                      (f"limits/{CELL}.json", LIMITS)):
        with open(os.path.join(b, path), "w") as f:
            json.dump(obj, f)
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-lfm2", "source": "test",
                            "file": "bench/configs/tiny-lfm2.json",
                            "reduced": [], "why": "CPU tests"})
    spec["workloads"].append({"name": CELL, "config": "tiny-lfm2",
                              "traffic": "tiny_train_mesh", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("train_tokens_per_s",) + READERS:
            m["workloads"].append(CELL)
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    return Registry(root)


def _ctx(reg, seed=SEED):
    import jax
    w = reg.cell(CELL)
    cfg = reg.config(w["config"])
    return harness.Context(cell=w, config=cfg, mix=reg.traffic(w["traffic"]),
                           limits=reg.limits(CELL), seed=seed,
                           reference=reg.reference(cfg["reference"]),
                           devices=jax.devices()[:1], peak=None,
                           log=io.StringIO())


def _run(reg):
    import jax
    return harness.run(CELL, SEED, 0.3, False, jax.devices()[:1],
                       time.perf_counter(), registry=reg, log=io.StringIO())


def test_sound_run_is_correct(reg):
    out = _run(reg)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    assert set(out["checks"]) == set(LIMITS)


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged"])
def test_planted_fault_is_not_correct(reg, fault):
    with planted(fault):
        out = _run(reg)
    assert not out["correct"], (fault, out["checks"])


def test_control_is_not_correct(reg):
    """The reference at float8 matmul operands in the program's place."""
    import jax.numpy as jnp
    ctx = _ctx(reg)
    vals = reg.driver("train_mesh").control(ctx, jnp.dtype("float8_e4m3fn"))
    assert any(v > LIMITS[k] for k, v in vals.items()), vals


def test_readers_read_a_recorded_run(reg):
    """The window of a tiny run records the expert counters; each new
    reader returns its number from that record and a reduced trace."""
    driver = reg.driver("train_mesh")
    ctx = _ctx(reg)
    state = driver.setup(ctx)
    win = driver.window(ctx, state, 0.2)
    driver.check(ctx, state, win)
    w = win["record"]
    c = CONFIG["program"]
    held, k = c["n_experts_held"], c["n_experts_active"]
    layers = c["n_layers"] - c["n_dense_layers"]
    assert len(w["expert_counts"]) == held
    # every token's k assignments, over the expert layers, land somewhere
    assert 0 < sum(w["expert_counts"]) <= layers * k * MIX["batch"] \
        * MIX["seq"]
    assert 0 < w["held_share"] <= 1
    trace = {"busy_s": 0.9 * w["window_s"], "window_s": w["window_s"],
             "op_time": {"custom-call ragged-dot-none bf16[512,32]": 1e-3,
                         "custom-call ragged-dot-metadata tuple": 1e-5,
                         "fusion bf16[4,64,64]": 5.0},
             "collective_exposed_s": 0.0}
    rec = {"window": win, "trace": trace, "config": CONFIG, "mix": MIX,
           "peak": PEAK, "chips": 1}
    got = {m: reg.reader(m).read(rec) for m in READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    fpt = flops_moe.conv_moe_train_flops_per_token(c, MIX["seq"])
    assert got["mfu.train_8k"] == pytest.approx(
        100 * fpt * w["tokens"] / w["window_s"] / PEAK["flops_bf16"])
    assert got["device_idle_share.train_8k"] == pytest.approx(10.0)
    counts = w["expert_counts"]
    assert got["expert_load_max_over_mean.train_8k"] == pytest.approx(
        max(counts) * len(counts) / sum(counts))
    per_call = flops_moe.expert_matmul_cost(sum(counts) / layers, c)
    least = w["steps"] * layers * 12 * flops.least_time(*per_call, PEAK)
    assert got["expert_matmul_roofline.train_8k"] == pytest.approx(
        100 * least / (1e-3 + 1e-5))
    # a dense step returns no counter: the expert readers read nothing
    dense = dict(rec, window={"record": {k: v for k, v in w.items()
                                         if k not in ("expert_counts",
                                                      "held_share")}})
    assert reg.reader("expert_load_max_over_mean.train_8k").read(dense) \
        is None
    assert reg.reader("expert_matmul_roofline.train_8k").read(dense) is None


def test_four_chip_readers():
    reg = Registry()
    trace = {"busy_s": 8.0, "window_s": 10.0, "collective_exposed_s": 0.5}
    rec = {"window": {"record": {"tokens": 8 * 4096 * 10, "window_s": 10.0}},
           "trace": trace, "peak": PEAK, "chips": 4, "mix": {"seq": 4096},
           "config": reg.config("stablelm-1.6b")}
    assert reg.reader("collective_exposed_share.train_4chip").read(rec) \
        == pytest.approx(5.0)
    assert reg.reader("device_idle_share.train_4chip").read(rec) == \
        pytest.approx(20.0)
    fpt = flops.dense_train_flops_per_token(rec["config"]["program"], 4096)
    assert reg.reader("mfu.train_4chip").read(rec) == pytest.approx(
        100 * fpt * 8 * 4096 / (4 * PEAK["flops_bf16"]))
    assert reg.reader("collective_exposed_share.train_4chip").read(
        dict(rec, trace=None)) is None


#: the four-chip cell's committed limits, and a tiny dense cell over a 2 x 2
#: data x model mesh that is held to them
MESH_CELL = "tiny-lm.train_4chip"
MESH_FAULTS = ("half_batch", "state_unchanged", "exchange_left_out")


@pytest.fixture(scope="module")
def mesh_readings(tmp_path_factory):
    """The sound run and each fault of the tiny four-device cell, from one
    process with four virtual CPU devices (``mesh_faults.py``)."""
    root = tiny.tiny_root(str(tmp_path_factory.mktemp("bench4")))
    b = os.path.join(root, "bench")
    with open(os.path.join(tiny.ROOT, "bench", "limits",
                           "stablelm-1.6b.train_4chip.json")) as f:
        limits = json.load(f)
    with open(os.path.join(b, "traffic", "train_4chip_s4096.json")) as f:
        mix = dict(json.load(f), batch=4, seq=32)
    with open(os.path.join(b, "configs", "tiny-lm.json")) as f:
        config = json.load(f)
    # float32 compute: at width 64 the tiny model's bf16 rounding alone
    # reads a gradient gap of 2.5e-3 on the CPU, above the limit that the
    # chip's readings at the cell's own widths set; the faults read 0.066
    # and more either way
    config["program"]["dtype"] = "float32"
    for path, obj in (("configs/tiny-lm32.json", config),
                      ("traffic/tiny_mesh4.json", mix),
                      (f"limits/{MESH_CELL}.json", limits)):
        with open(os.path.join(b, path), "w") as f:
            json.dump(obj, f)
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-lm32", "source": "test",
                            "file": "bench/configs/tiny-lm32.json",
                            "reduced": [], "why": "CPU tests"})
    spec["workloads"].append({"name": MESH_CELL, "config": "tiny-lm32",
                              "traffic": "tiny_mesh4", "chips": 4,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append(MESH_CELL)
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run(
        [sys.executable, os.path.join(tiny.ROOT, "bench", "tests",
                                      "mesh_faults.py"),
         root, MESH_CELL, str(SEED), "sound", *MESH_FAULTS],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    out = {}
    for line in r.stdout.splitlines():
        if line.startswith("{"):
            rec = json.loads(line)
            out[rec["fault"]] = rec
    return limits, out


def test_four_device_sound_run_is_correct(mesh_readings):
    _limits, out = mesh_readings
    assert out["sound"]["devices"] == 4
    assert out["sound"]["correct"], out["sound"]


@pytest.mark.parametrize("fault", MESH_FAULTS)
def test_four_device_fault_is_not_correct(mesh_readings, fault):
    """Under the four-chip cell's committed limits, on a 2 x 2 mesh."""
    limits, out = mesh_readings
    rec = out[fault]
    assert not rec["correct"], rec
    assert any(v > limits[k] for k, v in rec["checks"].items()), rec
