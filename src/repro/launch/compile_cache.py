"""Where the entry points keep JAX's persistent compilation cache.

Called by ``chip_smoke.py`` and the ``repro.launch.serve`` /
``repro.launch.train`` drivers before their first compile — never on
import, so a library user's own cache settings stand.
"""

from __future__ import annotations

import os
from pathlib import Path

#: fixed in-repo fallback: the cache is keyed by what is compiled, and a
#: directory that moved between runs would never hit
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and no
    other directory is set here; otherwise the cache goes to
    ``<repo>/.jax_cache``.  The size and compile-time floors are zeroed so
    every program caches, the small ones included."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
