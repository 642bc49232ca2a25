"""Persistent compilation cache for the entry points.

A cold TPU compile of a ResNet-74 train step takes tens of seconds; JAX's
persistent cache keeps the compiled programs on disk so the next process
loads them instead.  The cache key includes the directory, so the
directory must not move between runs: it is either the one named by
``JAX_COMPILATION_CACHE_DIR`` (which JAX reads by itself) or a fixed
``.jax_cache`` directory at the root of the checkout (git-ignored).

Only entry points call :func:`use_compile_cache` (``chip_smoke.py``,
``examples/train_e2e.py``, ``launch/train.py``, ``benchmarks/run.py``);
importing the library never touches the cache configuration.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  Sets nothing when ``JAX_COMPILATION_CACHE_DIR``
    is set."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
