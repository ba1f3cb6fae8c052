"""What the program derives from the machine it runs on.

* Pallas kernels run in interpret mode only on the CPU backend, where the
  test suite runs them; on a TPU every kernel compiles through Mosaic.
  Kernel entry points keep their own ``interpret=`` argument for tests.
* JAX's persistent compilation cache: every entry point calls
  ``enable_compile_cache()`` before its first compile. When
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing is set
  here; otherwise the cache lives at ``<repo>/.jax_cache``, a fixed path,
  so successive runs from one checkout hit it.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def interpret_kernels() -> bool:
    """True only when the default backend is the CPU."""
    return jax.default_backend() == "cpu"


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
