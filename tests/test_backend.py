"""Compile-cache placement (``utils/backend.py::place_compile_cache``): the
cache directory can be placed from outside, and is otherwise a fixed path of
the checkout — the directory is part of the cache key, so it must not move."""

import os

import jax
import pytest

from sgcn_tpu.utils import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore the process-wide JAX cache setting a test changed — later
    tests must not start writing a persistent cache."""
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_env_var_set_means_the_code_sets_nothing(monkeypatch, cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert backend.place_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir is None


def test_unset_places_the_cache_in_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert backend.place_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()
