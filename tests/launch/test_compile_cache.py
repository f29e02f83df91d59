"""The persistent compilation cache is placed from outside: the
``JAX_COMPILATION_CACHE_DIR`` environment variable wins, else a fixed
directory inside the checkout."""

import pathlib

import jax
import pytest

from repro.launch.compile_cache import use_compile_cache

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture(autouse=True)
def _restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_wins_and_is_left_alone(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", "sentinel")
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == "sentinel"


def test_default_is_a_fixed_directory_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first, second = use_compile_cache(), use_compile_cache()
    assert first == second == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
