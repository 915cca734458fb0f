"""JAX's persistent compilation cache, kept at a fixed place.

Entry points (``chip_smoke.py``, the examples, the benchmarks) call
``enable_compile_cache()`` once before they compile; the library never does
it at import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
it and that directory is used as it is. Otherwise the cache goes to
``<checkout>/.jax_cache`` (git-ignored): a fixed path, because the path is
part of the cache key and a directory that moves never hits.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    path = os.environ.get(ENV_VAR) or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
