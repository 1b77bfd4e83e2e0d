"""The persistent compile cache lives where JAX_COMPILATION_CACHE_DIR
says, else in the fixed in-checkout ``.jax_cache``, and nowhere else."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro import compile_cache
from tests._subproc import SRC

REPO = Path(__file__).resolve().parents[1]


def test_env_dir_gets_the_cache_and_nothing_else_is_set(tmp_path):
    env_dir = tmp_path / "env-cache"
    code = f"""
import os
from pathlib import Path
import jax, jax.numpy as jnp
from repro.compile_cache import REPO_CACHE_DIR, use_compile_cache
had_repo_cache = REPO_CACHE_DIR.exists()
before = sorted(REPO_CACHE_DIR.iterdir()) if had_repo_cache else None
assert use_compile_cache() == {str(env_dir)!r}
assert jax.config.jax_compilation_cache_dir == {str(env_dir)!r}
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
assert os.listdir({str(env_dir)!r}), "nothing cached in the env dir"
after = sorted(REPO_CACHE_DIR.iterdir()) if REPO_CACHE_DIR.exists() else None
assert after == before, "the in-checkout cache was written"
print("CACHE OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(env_dir),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH",
                                                            ""))
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "CACHE OK" in res.stdout


def test_without_env_the_cache_is_the_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.use_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.use_compile_cache() == path   # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
