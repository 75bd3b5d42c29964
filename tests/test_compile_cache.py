"""The entry points' persistent compile cache (``repro.util``): JAX's own
``JAX_COMPILATION_CACHE_DIR`` wins; otherwise one fixed, git-ignored path
in the checkout."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import util

ROOT = Path(__file__).resolve().parent.parent

CHILD = """
import jax, jax.numpy as jnp
from repro.util import enable_compile_cache
assert enable_compile_cache() is None
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.arange(8.0)).block_until_ready()
"""


def _listing(path: Path):
    return sorted(p.name for p in path.iterdir()) if path.exists() else []


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


def test_env_var_is_left_to_jax(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert util.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_ignored_path_in_checkout(monkeypatch,
                                                   restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = util.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert util.enable_compile_cache() == path          # same every call
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_entries_land_only_in_the_env_dir(tmp_path):
    repo_cache = ROOT / ".jax_cache"
    before = _listing(repo_cache)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", CHILD], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert _listing(tmp_path / "cache"), "no cache entry was written"
    assert _listing(repo_cache) == before
