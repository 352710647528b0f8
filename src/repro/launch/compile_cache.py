"""Where the entry points keep JAX's persistent compilation cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the
cache stays there.  Otherwise it goes to ``.jax_cache/`` at the root of
the checkout (gitignored).  The path is fixed: it is part of the cache's
key, so a directory that moved between runs would never hit.

The serving entry points call :func:`enable_compile_cache` first thing
(``chip_smoke.py``, ``launch/serve.py``, ``examples/serve_mla.py``,
``benchmarks/bench_serving.py``); tests do not.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
