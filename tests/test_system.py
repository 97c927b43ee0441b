"""End-to-end behaviour tests: training loop, elasticity, serving, PASTA
instrumentation over a real (reduced) workload."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.configs as C
import repro.core as pasta
from repro.models import init_params
from repro.train import (OptConfig, make_train_step, DataConfig,
                         SyntheticTokens, LoopConfig, TrainLoop,
                         checkpoint as ckpt)
from repro.train.optimizer import init_opt_state

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _setup(arch="paper-gpt2", steps=12, seq=64, batch=4, **loop_kw):
    cfg = C.reduced(C.get(arch))
    opt_cfg = OptConfig(lr=3e-3, total_steps=steps, warmup_steps=2)
    step = jax.jit(make_train_step(cfg, opt_cfg, microbatches=2),
                   donate_argnums=(0, 1))
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = init_opt_state(params, opt_cfg)
    src = SyntheticTokens(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                     global_batch=batch))
    place = lambda b: {k: jnp.asarray(v) for k, v in b.items()}  # noqa: E731
    # no handler passed: the loop resolves the innermost active session
    loop = TrainLoop(LoopConfig(total_steps=steps, **loop_kw), step, src,
                     place)
    return cfg, params, opt, loop


def test_train_loop_loss_decreases():
    losses = []
    cfg, params, opt, loop = _setup(steps=15)
    params, opt, step = loop.run(params, opt,
                                 metrics_cb=lambda s, m: losses.append(
                                     m["loss"]))
    assert step == 15 and len(losses) == 15
    assert np.mean(losses[-3:]) < np.mean(losses[:3]), losses


def test_elastic_restart_after_injected_failure(tmp_path):
    """A mid-run failure restores the last checkpoint and completes; the
    step-indexed pipeline replays deterministically."""
    cfg, params, opt, loop = _setup(steps=12, ckpt_dir=str(tmp_path),
                                    ckpt_every=4, inject_failure_at=6)
    seen = []
    params, opt, step = loop.run(params, opt,
                                 metrics_cb=lambda s, m: seen.append(
                                     (s, m["loss"])))
    assert step == 12
    assert loop.restarts == 1
    # steps 4/5 executed twice (replay from the step-4 checkpoint) with
    # identical losses -> bit-exact restart
    by_step = {}
    replayed = 0
    for s, l in seen:
        if s in by_step:
            replayed += 1
            assert by_step[s] == pytest.approx(l, rel=0, abs=0), s
        by_step[s] = l
    assert replayed >= 1
    assert ckpt.latest_step(str(tmp_path)) == 12


def test_failure_exhausts_restarts(tmp_path):
    cfg, params, opt, loop = _setup(steps=8, ckpt_dir=str(tmp_path),
                                    ckpt_every=4)
    loop.cfg.max_restarts = 0
    loop.cfg.inject_failure_at = 5
    with pytest.raises(RuntimeError):
        loop.run(params, opt)


def test_straggler_watchdog_counts():
    cfg, params, opt, loop = _setup(steps=10)
    orig = loop.train_step
    calls = {"n": 0}

    def slow_step(*a):
        calls["n"] += 1
        if calls["n"] == 8:
            import time
            time.sleep(1.5)
        return orig(*a)

    loop.train_step = slow_step
    loop.run(params, opt)
    assert loop.stragglers >= 1


def test_serve_engine_batched_generation():
    cfg = C.reduced(C.get("glm4-9b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    from repro.serve import ServeEngine
    eng = ServeEngine(cfg, params, max_seq=48)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (3, 16), dtype=np.int32)
    out = eng.generate(prompts, max_new_tokens=8)
    assert out.shape == (3, 8)
    # greedy decode is deterministic
    out2 = ServeEngine(cfg, params, max_seq=48).generate(
        prompts, max_new_tokens=8)
    np.testing.assert_array_equal(out, out2)


def test_pasta_instruments_training_end_to_end(handler):
    """The paper's core scenario: attach tools, run a workload, get reports
    with kernel frequencies from the compiled artifact."""
    tools = [pasta.KernelFrequencyTool(), pasta.LocatorTool()]
    proc = pasta.EventProcessor(handler, tools=tools)
    cfg, params, opt, loop = _setup(steps=3)
    src = loop.source
    batch = {k: jnp.asarray(v) for k, v in src.batch_at(0).items()}
    compiled = jax.jit(make_train_step(
        cfg, OptConfig(), microbatches=1)).lower(params, opt,
                                                 batch).compile()
    with pasta.region("capture"):
        stats = handler.capture_compiled(compiled, label="train",
                                         default_trip=cfg.n_layers, steps=3)
    rep = proc.finalize()
    kf = rep["KernelFrequencyTool"]
    assert kf["total_invocations"] > 0
    assert kf["by_label"]["train"]
    assert rep["LocatorTool"]["kernel"]
    assert stats.flops > 0 and stats.hbm_bytes > 0


def test_checkpoint_restore_validates_manifest(tmp_path):
    """A checkpoint saved from a different model must be refused with an
    error naming the mismatch, not silently unflattened into garbage."""
    state = {"params": {"w": np.ones((4, 2), np.float32),
                        "b": np.zeros((2,), np.float32)},
             "step_count": np.int32(7)}
    ckpt.save(str(tmp_path), 3, state)
    step, back = ckpt.restore(str(tmp_path), state)
    assert step == 3
    np.testing.assert_array_equal(back["params"]["w"], state["params"]["w"])

    wrong_shape = {"params": {"w": np.ones((4, 3), np.float32),
                              "b": np.zeros((2,), np.float32)},
                   "step_count": np.int32(7)}
    with pytest.raises(ValueError, match=r"params/w"):
        ckpt.restore(str(tmp_path), wrong_shape)

    wrong_dtype = {"params": {"w": np.ones((4, 2), np.float16),
                              "b": np.zeros((2,), np.float32)},
                   "step_count": np.int32(7)}
    with pytest.raises(ValueError, match=r"float16"):
        ckpt.restore(str(tmp_path), wrong_dtype)

    wrong_tree = {"params": {"w": np.ones((4, 2), np.float32),
                             "extra": np.zeros((1,), np.float32)},
                  "step_count": np.int32(7)}
    with pytest.raises(ValueError, match=r"tree mismatch"):
        ckpt.restore(str(tmp_path), wrong_tree)


def test_checkpoint_crash_mid_save_is_ignored(tmp_path):
    """Simulated crash debris — an in-flight ``.tmp`` dir, a dir missing
    COMMIT, junk names — must never shadow the last good checkpoint."""
    state = {"w": np.arange(6, dtype=np.float32)}
    ckpt.save(str(tmp_path), 2, state)

    # crash mid-write: .tmp never renamed (manifest present, no COMMIT)
    tmp_dir = tmp_path / "step_00000004.tmp"
    tmp_dir.mkdir()
    (tmp_dir / "manifest.json").write_text("{}")
    # torn dir without COMMIT (e.g. partially copied from another host)
    (tmp_path / "step_00000006").mkdir()
    # junk that merely looks checkpoint-shaped
    (tmp_path / "step_garbage").mkdir()
    (tmp_path / "step_notes.txt").write_text("x")

    assert ckpt.latest_step(str(tmp_path)) == 2
    step, back = ckpt.restore(str(tmp_path), state)
    assert step == 2
    np.testing.assert_array_equal(back["w"], state["w"])


def test_elastic_resize_restore_across_device_counts(tmp_path):
    """Save sharded at 2 forced host devices, resume at 1 (and at 2): the
    checkpoint holds global arrays, so the same trajectory replays
    regardless of the device count it restores onto."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    base = [sys.executable, "-m", "repro.launch.train", "--arch",
            "paper-gpt2", "--reduced", "--seq-len", "32",
            "--global-batch", "4", "--ckpt-dir", str(tmp_path),
            "--ckpt-every", "3", "--pasta-tools", "kernel_freq"]
    two_dev = ["--devices", "2", "--mesh", "2x1"]

    r = subprocess.run(base + two_dev + ["--steps", "3"],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr
    assert ckpt.latest_step(str(tmp_path)) == 3

    def resume(extra):
        # --ckpt-every 100: resumes must not publish new checkpoints, or
        # the second resume would restore the first one's step-6 save
        r = subprocess.run(base + extra + ["--resume", "--steps", "6",
                                           "--ckpt-every", "100"],
                           capture_output=True, text=True, env=env,
                           timeout=600)
        assert r.returncode == 0, r.stderr
        assert "resumed from step 3" in r.stdout
        assert "done at step 6" in r.stdout
        return [ln.split("loss")[1].split()[0]
                for ln in r.stdout.splitlines()
                if ln.startswith("[train] step")]

    one_losses = resume([])                  # N=2 save -> M=1 restore
    two_losses = resume(two_dev)             # and back onto N=2
    assert len(one_losses) == 3
    # the replayed steps 4-6 match to printed precision across meshes
    assert one_losses == two_losses, (one_losses, two_losses)


def test_train_driver_cli_resume(tmp_path):
    """CLI driver: train 6 steps with checkpointing, then resume."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    args = [sys.executable, "-m", "repro.launch.train", "--arch",
            "paper-gpt2", "--reduced", "--steps", "6", "--seq-len", "32",
            "--global-batch", "2", "--ckpt-dir", str(tmp_path),
            "--ckpt-every", "3", "--pasta-tools", "kernel_freq"]
    r = subprocess.run(args, capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, r.stderr
    assert "done at step 6" in r.stdout
    r2 = subprocess.run(args + ["--resume", "--steps", "8"],
                        capture_output=True, text=True, env=env, timeout=600)
    assert r2.returncode == 0, r2.stderr
    assert "resumed from step 6" in r2.stdout
    assert "done at step 8" in r2.stdout


@pytest.mark.parametrize("arch", ["dbrx-132b", "lfm2-8b-a1b"])
def test_train_driver_cli_runs_expert_models(arch):
    """CLI driver on a model with experts: the step's metrics carry the
    per-expert counters beside the scalars, and the loop's callback still
    prints every step."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    args = [sys.executable, "-m", "repro.launch.train", "--arch", arch,
            "--reduced", "--steps", "2", "--seq-len", "32",
            "--global-batch", "2", "--pasta-tools", "kernel_freq"]
    r = subprocess.run(args, capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, r.stderr
    steps = [ln for ln in r.stdout.splitlines()
             if ln.startswith("[train] step")]
    assert len(steps) == 2, r.stdout
    assert "done at step 2" in r.stdout
