"""What the program derives from the machine: interpret mode, the compile
cache directory and the autotune plan file (``repro.runtime``)."""
from __future__ import annotations

import os

import jax
import pytest

from repro.kernels import dispatch
from repro.runtime import REPO_ROOT, enable_compile_cache, interpret_kernels


@pytest.fixture
def cache_dir_restored():
    """Later tests in this process must not compile into the checkout's
    cache: restore the directory and drop any cache JAX opened on it."""
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)
    compilation_cache.reset_cache()


def test_interpret_only_on_cpu():
    assert interpret_kernels() == (jax.default_backend() == "cpu")


def test_compile_cache_defaults_into_checkout(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == os.path.join(REPO_ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_env_left_to_jax(monkeypatch, tmp_path,
                                       cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_autotune_plans_default_into_checkout(monkeypatch):
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    monkeypatch.setattr(dispatch, "_CACHE_OVERRIDE", None)
    assert dispatch.cache_path() == os.path.join(
        REPO_ROOT, ".autotune", "ss_autotune.json"
    )
