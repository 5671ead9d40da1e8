"""Compile-cache placement (``utils/backend.py::place_compile_cache``): the
cache directory can be placed from outside, and is otherwise a fixed path of
the checkout — the directory is part of the cache key, so it must not move.
Op metadata is part of the key too: a cached executable must not lend its
scope names to a program that has others."""

import os
import subprocess
import sys

import jax
import pytest

from sgcn_tpu.utils import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore the process-wide JAX cache setting a test changed — later
    tests must not start writing a persistent cache."""
    old = jax.config.jax_compilation_cache_dir
    meta = jax.config.jax_compilation_cache_include_metadata_in_key
    yield
    jax.config.update("jax_compilation_cache_dir", old)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", meta)


def test_env_var_set_means_the_code_sets_nothing(monkeypatch, cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert backend.place_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir is None
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def test_unset_places_the_cache_in_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert backend.place_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


_TWO_PROGRAMS = """
import sys
import jax, jax.numpy as jnp
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
sys.path.insert(0, sys.argv[1])
from sgcn_tpu.utils import backend
if sys.argv[3] == "placed":
    backend.place_compile_cache()
else:
    jax.config.update("jax_compilation_cache_dir", sys.argv[2])
def f(x):
    with jax.named_scope("sgcn." + sys.argv[4]):
        return jnp.sin(x) @ x
print(sys.argv[4] in jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text())
"""


@pytest.mark.parametrize("how, named", [("placed", True), ("default", False)])
def test_a_cached_executable_does_not_lend_its_scope_names(tmp_path, how,
                                                           named):
    """Two programs that differ in a scope's name only, one cache directory:
    JAX's default key strips metadata, so the second is handed the first's
    executable and names (``default``: the fault, kept visible);
    ``place_compile_cache`` keys on metadata and each keeps its own."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    said = [subprocess.run(
        [sys.executable, "-c", _TWO_PROGRAMS, REPO, str(tmp_path), how, name],
        capture_output=True, text=True, timeout=120, env=env)
        for name in ("agg_slots", "agg_tail")]
    assert [p.returncode for p in said] == [0, 0], said[-1].stderr
    assert said[0].stdout.split()[-1] == "True"
    assert (said[1].stdout.split()[-1] == "True") == named
