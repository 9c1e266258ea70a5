"""Where the persistent compilation cache goes (``repro.compile_cache``)."""

from pathlib import Path

import jax
import pytest

from repro.compile_cache import DEFAULT_DIR, use_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_environment_places_the_cache(monkeypatch, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # set nothing


def test_default_is_a_fixed_ignored_path_in_the_checkout(monkeypatch,
                                                         restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert use_compile_cache() == str(DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    assert DEFAULT_DIR == REPO / ".jax_cache"
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
