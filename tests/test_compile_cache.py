"""The entry points' persistent compilation cache: JAX's own environment
variable wins; otherwise a fixed, git-ignored directory in the checkout."""
import os
import subprocess
import sys

import jax

from repro.launch.compile_cache import ENV_VAR, REPO_CACHE_DIR, use_compile_cache

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_and_ignored(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == REPO_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == REPO_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert REPO_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cache_lands_in_env_dir(tmp_path):
    code = ("from repro.launch.compile_cache import use_compile_cache\n"
            "use_compile_cache()\n"
            "import jax, jax.numpy as jnp\n"
            "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((8, 8))).block_until_ready()\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               PYTHONPATH=os.path.join(ROOT, "src"))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    assert os.listdir(tmp_path)
