"""JAX's persistent compilation cache for the command-line entry points.

Only entry points call :func:`setup_compile_cache` (``chip_smoke.py``,
``repro.launch.train``, ``benchmarks.run``); importing the library or
running the tests never turns the cache on.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing else is configured.  Otherwise the cache lives at one
fixed path inside the checkout (``<repo>/.jax_cache``, git-ignored): the
directory is part of each entry's key, so a path that changed from run to
run (a temp name, a pid, a time) would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the repository root.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Call once, before the first compilation.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
