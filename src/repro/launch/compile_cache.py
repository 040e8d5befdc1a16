"""Persistent XLA compile cache for the serving entry points
(``python -m repro.launch.serve``, ``chip_smoke.py``)."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, inside the checkout (git-ignored): the directory is part of what
# a later run must find again, so it never comes from a temporary name, a
# process id or the time
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set here; otherwise the cache goes to
    ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
