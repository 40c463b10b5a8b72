"""The entry points' compile-cache placement (launch/compile_cache.py)."""

import os

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_environment_places_the_cache(monkeypatch, restore_cache_dir):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.configure_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir is None


def test_default_is_fixed_under_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.configure_compile_cache()
    assert got == os.path.join(compile_cache.CHECKOUT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    # The checkout is the directory holding src/ and tests/.
    assert os.path.isfile(os.path.join(compile_cache.CHECKOUT, "tests", "test_compile_cache.py"))
    # Same path on every call: the path is part of each cache entry's key.
    assert compile_cache.configure_compile_cache() == got
