"""``chip_smoke.py`` phases at tiny sizes on the CPU: the same calls the chip
run makes, so a broken path fails here before it costs chip time."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
import repro.configs as C  # noqa: E402


def test_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_kernels_phase_interpret(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    out = chip_smoke.phase_kernels(n=4096, k=40, hot_blocks=40, fused_k=20,
                                   fused_blocks=64, n_tbins=4, mm=256,
                                   expect="interpret")
    assert out["object_histogram"]["hits"] > 0
    assert out["matmul_traced"]["trace_rows"] == 4


@pytest.fixture(scope="module")
def stablelm():
    cfg = C.reduced(C.get("stablelm-1.6b"))
    return cfg, chip_smoke.init_params(cfg)


def test_pasta_phase_device_counts_match_host(stablelm):
    cfg, params = stablelm
    out = chip_smoke.phase_pasta(cfg, params, seq=16, n_blocks=1100,
                                 n_tbins=8)
    assert out["trace_buffers"] > cfg.n_layers
    assert out["hotness_accesses"] > 0


def test_serve_phase_matches_reference(stablelm):
    cfg, params = stablelm
    out = chip_smoke.phase_serve(cfg, params, n_requests=4,
                                 prompt_len=(24, 40), shared_prefix=8,
                                 new_tokens=4, max_slots=2)
    assert out["requests"] == 4 and out["tokens"] == 16


def test_train_phase():
    out = chip_smoke.phase_train(C.reduced(C.get("paper-gpt2")), seq=32,
                                 batch=4, steps=3)
    assert len(out["losses"]) == 3
    assert out["kernel_freq_invocations"] > 0


def test_sharded_phase_on_virtual_devices():
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    code = ("import chip_smoke, repro.configs as C\n"
            "out = chip_smoke.phase_sharded(C.reduced(C.get('paper-gpt2')),"
            " seq=32, batch=4, steps=3)\n"
            "print('OK', out['max_abs_diff'])\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "OK" in r.stdout
