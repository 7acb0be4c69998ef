"""Where the program keeps its compile cache and tuned plans."""
import pathlib

import jax
import pytest

from repro import compile_cache
from repro.kernels import autotune

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_honours_env_dir(monkeypatch, tmp_path,
                                       restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # left to JAX: nothing is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(CHECKOUT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # a fixed path: calling again gives the same directory
    assert compile_cache.enable_compile_cache() == want


def test_autotune_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    assert autotune.cache_path() == CHECKOUT / ".cache" / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "/elsewhere/at.json")
    assert str(autotune.cache_path()) == "/elsewhere/at.json"
