"""The persistent compile cache helper: where compiled programs land."""
import contextlib
import os

import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.compile_cache import DEFAULT_DIR, ENV_VAR, enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_OPTIONS = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")


@contextlib.contextmanager
def _restored_cache_config():
    saved = {k: getattr(jax.config, k) for k in _OPTIONS}
    cc.reset_cache()
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def _listing(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else []


def test_env_dir_is_used_and_nothing_else(tmp_path, monkeypatch):
    target = str(tmp_path / "cache")
    monkeypatch.setenv(ENV_VAR, target)
    before = _listing(DEFAULT_DIR)
    with _restored_cache_config():
        assert enable_compile_cache() == target
        assert jax.config.jax_compilation_cache_dir == target
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.block_until_ready(
            jax.jit(lambda x: jnp.tanh(x) * 3.25 + 0.5)(jnp.ones(29)))
    assert _listing(target), "no compiled entry in the configured directory"
    assert _listing(DEFAULT_DIR) == before


def test_unset_env_uses_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    with _restored_cache_config():
        path = enable_compile_cache()
        assert path == DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        # the same path every call: no pid, timestamp or temporary name
        assert enable_compile_cache() == path
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
