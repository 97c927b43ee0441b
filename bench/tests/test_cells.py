"""Registry discovery by name; cells added by files alone and run through
the harness on the CPU at a tiny size; and every fault the cells can have,
planted underneath the harness, turning ``correct`` false."""

import io
import json
import os
import subprocess
import sys
import time

import pytest

import tiny
from bench import harness
from bench.faults import planted
from bench.registry import Registry

SEED = 2**31 + 977          # more than 32 signed bits hold


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    return Registry(tiny.tiny_root(str(tmp_path_factory.mktemp("bench"))))


def _run(reg, cell, seconds=0.3):
    import jax
    log = io.StringIO()
    out = harness.run(cell, SEED, seconds, False, jax.devices()[:1],
                      time.perf_counter(), registry=reg, log=log)
    return out, log.getvalue()


def test_registry_finds_every_piece_by_name():
    reg = Registry()
    for w in reg.spec["workloads"]:
        cfg = reg.config(w["config"])
        mix = reg.traffic(w["traffic"])
        assert reg.limits(w["name"])
        assert hasattr(reg.driver(mix["kind"]), "window")
        assert hasattr(reg.reference(cfg["reference"]), "init_weights")
        names = [m["name"] for m in reg.metrics("end_to_end", w["name"])]
        assert "setup_s" in names and len(names) >= 2
        layer = reg.metrics("per_layer", w["name"])
        assert layer
        for m in layer:
            assert hasattr(reg.reader(m["name"]), "read")
            assert m["moves"] in names
    with pytest.raises(KeyError):
        reg.cell("no-such-cell")


def test_a_cell_added_by_files_alone_is_found(reg):
    cell = reg.cell("tiny-lm.train")
    assert reg.config(cell["config"])["program"]["d_model"] == 64
    assert reg.traffic(cell["traffic"])["kind"] == "train"
    assert [m["name"] for m in reg.metrics("end_to_end", "tiny-lm.train")] \
        == ["train_tokens_per_s", "setup_s"]


@pytest.mark.parametrize("cell,rate", [("tiny-lm.profile",
                                        "profiled_tokens_per_s"),
                                       ("tiny-lm.train",
                                        "train_tokens_per_s"),
                                       ("tiny-lm.serve", "itl_p95_s")])
def test_sound_run_is_correct(reg, cell, rate):
    out, log = _run(reg, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["metrics"][rate]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert log.strip().splitlines()[-1].startswith("[check]")


def test_same_seed_same_inputs():
    from bench import traffic
    a = traffic.token_batch(SEED, 3, 2, 8, 100)
    assert (a == traffic.token_batch(SEED, 3, 2, 8, 100)).all()
    assert not (a == traffic.token_batch(SEED, 4, 2, 8, 100)).all()
    assert not (a == traffic.token_batch(SEED + 1, 3, 2, 8, 100)).all()


FAULTS = [("tiny-lm.train", "state_unchanged"),
          ("tiny-lm.train", "half_batch"),
          ("tiny-lm.train", "loss_altered"),
          ("tiny-lm.profile", "logit_altered"),
          ("tiny-lm.profile", "count_altered"),
          ("tiny-lm.serve", "token_altered")]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_planted_fault_is_not_correct(reg, cell, fault):
    with planted(fault):
        out, _ = _run(reg, cell)
    assert not out["correct"], (fault, out["checks"])


@pytest.mark.parametrize("cell", ["tiny-lm.train", "tiny-lm.profile",
                                  "tiny-lm.serve"])
def test_control_is_not_correct(reg, cell):
    """The reference at float8 matmul operands in the program's place."""
    import jax
    import jax.numpy as jnp
    w = reg.cell(cell)
    cfg = reg.config(w["config"])
    mix = reg.traffic(w["traffic"])
    ctx = harness.Context(cell=w, config=cfg, mix=mix,
                          limits=reg.limits(cell), seed=SEED,
                          reference=reg.reference(cfg["reference"]),
                          devices=jax.devices()[:1], peak=None)
    vals = reg.driver(mix["kind"]).control(ctx, jnp.dtype("float8_e4m3fn"))
    assert any(v > ctx.limits[k] for k, v in vals.items()), vals


def test_run_without_a_chip_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(tiny.BENCH, "run.py"),
                        "--workload", "paper-gpt2.train", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert "correct" not in p.stdout


def test_run_with_only_the_benchmark_files_prints_no_result(tmp_path):
    root = tiny.tiny_root(str(tmp_path))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, os.path.join(root, "bench", "run.py"),
                        "--workload", "paper-gpt2.train", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120,
                       cwd=root)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert json.load(open(os.path.join(root, "BENCHMARK.json")))
