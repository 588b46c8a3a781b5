"""The entry points' persistent compilation cache helper."""
import pathlib

import jax
import pytest

from repro import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after the test changed it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins_and_nothing_is_set_in_code(monkeypatch, cache_config,
                                                 tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_a_fixed_dir_under_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable()
    assert first == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert compile_cache.enable() == first


def test_cache_dir_is_ignored_by_git():
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
