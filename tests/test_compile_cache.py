"""The entry points' persistent compilation cache location."""
import os
import subprocess
import sys

import jax
import pytest

from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_default_is_fixed_repo_path(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert REPO_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert enable_compile_cache() == REPO_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == REPO_CACHE_DIR


def test_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                         cache_dir_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_env_var_cache_is_written_there(tmp_path):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    code = ("from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "import jax\n"
            "jax.block_until_ready(jax.jit(lambda x: x * 2 + 1)(1.0))\n")
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == str(tmp_path)
    assert any(name.startswith("jit_") for name in os.listdir(tmp_path))
