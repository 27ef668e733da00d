"""setup_compile_cache: the persistent cache is placed from outside — by
JAX_COMPILATION_CACHE_DIR when it is set, else at <checkout>/.jax_cache."""
import os

import jax
import pytest

from repro.launch import cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_unset_variable_puts_cache_in_checkout(monkeypatch,
                                               restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = cache.setup_compile_cache()
    assert got == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got


def test_set_variable_is_left_to_jax(monkeypatch, restore_cache_dir,
                                     tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None
