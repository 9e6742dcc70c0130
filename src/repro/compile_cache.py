"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so it lives at one fixed path: the
directory ``JAX_COMPILATION_CACHE_DIR`` names when the environment sets it
(JAX reads that variable itself, and nothing else is set), otherwise
``.jax_cache/`` at the repository root, which git ignores.  Entry points
call ``use_compile_cache()`` first thing; importing this module changes
nothing, so tests and library users keep JAX's own settings.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
