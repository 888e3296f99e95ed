"""JAX's persistent compilation cache, kept at one fixed place per checkout.

Every entry point that compiles (``repro.launch.serve``, ``benchmarks.run``,
``chip_smoke.py``) calls :func:`enable_compile_cache` before its first
compile, so that a second run of the same programs loads them instead of
compiling again. The cache key includes the directory, so it must not
move between runs: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it
itself, and nothing here overrides it), otherwise ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["compile_cache_dir", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The cache directory this process uses: the environment's, or the
    fixed ``.jax_cache`` directory at the root of the checkout."""
    return os.environ.get(ENV_VAR) or str(_CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
