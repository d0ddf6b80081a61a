"""Where the persistent compilation cache goes (launch/compile_cache.py)."""

from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import CACHE_ENV, configure_compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_used_and_nothing_is_set_in_code(monkeypatch, cache_dir_config):
    monkeypatch.setenv(CACHE_ENV, "/placed/from/outside")
    jax.config.update("jax_compilation_cache_dir", None)
    assert configure_compile_cache() == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir is None


def test_fixed_in_checkout_dir_without_env(monkeypatch, cache_dir_config):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    path = configure_compile_cache()
    assert path == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert configure_compile_cache() == path  # the same path on every call
