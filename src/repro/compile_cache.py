"""Where JAX keeps its persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set, the
cache lives there and this module sets nothing. Otherwise the cache
goes to one fixed directory inside the checkout, ``<repo>/.jax_cache``
(gitignored). The directory never depends on a temp name, a PID or the
time: a cache whose path moves between runs is never hit again.

Entry points that compile the sweep engine (``chip_smoke.py``, the
benchmark ``main``s) call :func:`use_compile_cache` before their first
compile. Library code and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
