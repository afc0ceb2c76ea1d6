"""JAX's persistent compilation cache, placed from outside.

One helper decides the directory for every caller (tests, ``bench.py``,
``chip_smoke.py``, ``tools/``, the serving runtime): the
``JAX_COMPILATION_CACHE_DIR`` environment variable when it is set, else
the fixed ``<repo>/.jax_cache``.  A fixed path matters: the directory is
where the next process looks, so a path that moves never hits.  No call
site sets another path.
"""

from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def compile_cache_dir() -> str:
    return os.environ.get(CACHE_ENV) or REPO_CACHE


def enable_compile_cache(min_compile_secs: float = 1.0) -> str:
    """Point JAX's persistent cache at :func:`compile_cache_dir` and keep
    programs that took at least ``min_compile_secs`` to compile."""
    path = compile_cache_dir()
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_compile_secs)
    return path
