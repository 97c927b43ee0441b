"""The entry points' persistent compilation cache lands in
``JAX_COMPILATION_CACHE_DIR`` when it is set, else in ``<repo>/.jax_cache``
— checked in fresh processes, since JAX fixes the cache at first compile."""

import os
import pathlib
import subprocess
import sys

from repro.launch.compile_cache import REPO_CACHE_DIR

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMPILE = """
import pathlib, sys
import repro.launch.compile_cache as cc
cc.REPO_CACHE_DIR = pathlib.Path(sys.argv[1])
print(cc.setup_compile_cache())
import jax, jax.numpy as jnp
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


def _run(env_dir, fallback):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", _COMPILE, str(fallback)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout.strip().splitlines()[-1]


def _entries(d):
    return sorted(os.listdir(d)) if os.path.isdir(d) else []


def test_env_var_dir_wins(tmp_path):
    env_dir, fallback = tmp_path / "env", tmp_path / "fallback"
    assert _run(env_dir, fallback) == str(env_dir)
    assert _entries(env_dir)
    assert not _entries(fallback)


def test_fixed_repo_dir_when_unset(tmp_path):
    fallback = tmp_path / "fallback"
    assert _run(None, fallback) == str(fallback)
    assert _entries(fallback)


def test_repo_dir_is_fixed_under_the_checkout():
    assert REPO_CACHE_DIR == pathlib.Path(REPO) / ".jax_cache"
