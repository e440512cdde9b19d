"""The persistent compilation cache is placed from outside the program."""
import jax
import pytest

from repro.common import compile_cache


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_left_to_jax(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compile_cache()
    assert path == str(compile_cache.REPO_ROOT / ".jax_cache")
    assert (compile_cache.REPO_ROOT / "src" / "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.use_compile_cache() == path
