"""The persistent compilation cache helper: ``JAX_COMPILATION_CACHE_DIR``
wins where it is set; otherwise the cache sits at one fixed path inside
the checkout."""
from pathlib import Path

import jax
import pytest

from repro import compile_cache

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_cache_dir():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_env_directory_stays_in_force(tmp_path, monkeypatch,
                                      restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_is_one_fixed_path_in_checkout(monkeypatch,
                                               restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    assert first == second == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
