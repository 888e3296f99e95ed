"""The persistent compilation cache helper (repro.runtime.compile_cache):
one fixed directory per checkout, unless ``JAX_COMPILATION_CACHE_DIR``
names another."""

import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax

from repro.runtime.compile_cache import (ENV_VAR, compile_cache_dir,
                                         enable_compile_cache)

ROOT = Path(__file__).resolve().parents[1]


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    return calls


def test_env_var_is_honoured(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    calls = _record_updates(monkeypatch)
    assert compile_cache_dir() == str(tmp_path)
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: no other directory is configured
    assert calls == []


def test_unset_resolves_to_one_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    calls = _record_updates(monkeypatch)
    first, second = enable_compile_cache(), enable_compile_cache()
    assert first == second == str(ROOT / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", first)] * 2


def test_entries_land_in_the_env_var_directory(tmp_path):
    """End to end in a fresh interpreter: with the variable set, a compile
    writes its cache entry there and nothing into the checkout's
    default directory.

    The interpreter runs a copy of the sources in a checkout of its own,
    so tests that compile into this checkout's default directory at the
    same time (other test workers) cannot touch the directory checked."""
    checkout = tmp_path / "checkout"
    shutil.copytree(ROOT / "src", checkout / "src",
                    ignore=shutil.ignore_patterns("__pycache__"))
    default = checkout / ".jax_cache"
    before = sorted(os.listdir(default)) if default.exists() else []
    src = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.runtime import compile_cache
        from repro.runtime.compile_cache import enable_compile_cache
        print(compile_cache.__file__)
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) * 3)(jnp.arange(8.0)).block_until_ready()
    """)
    env = dict(os.environ, PYTHONPATH=str(checkout / "src"),
               JAX_PLATFORMS="cpu")
    env[ENV_VAR] = str(tmp_path / "cache")
    out = subprocess.run([sys.executable, "-c", src], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.startswith(str(checkout / "src"))
    assert os.listdir(tmp_path / "cache")
    after = sorted(os.listdir(default)) if default.exists() else []
    assert after == before
