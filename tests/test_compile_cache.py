"""Where the entry points put JAX's persistent compilation cache."""
import os

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.compile_cache import CACHE_ENV, DEFAULT_CACHE_DIR, use_compile_cache

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield prev
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


def test_env_var_is_honoured_and_nothing_else_is_set(
        monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == restore_cache_dir


def test_default_is_a_fixed_gitignored_dir_in_the_checkout(
        monkeypatch, restore_cache_dir):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    assert use_compile_cache() == str(DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
    assert str(DEFAULT_CACHE_DIR) == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
