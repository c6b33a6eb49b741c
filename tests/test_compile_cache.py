"""Where the entry points put JAX's persistent compilation cache."""
import jax
import pytest

from repro.launch import compile_cache as cc


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_directory_is_left_to_jax(monkeypatch, cache_dir_config):
    monkeypatch.setenv(cc.ENV, "/elsewhere/jax-cache")
    jax.config.update("jax_compilation_cache_dir", None)
    assert cc.enable_compile_cache() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir is None


def test_default_is_one_fixed_directory_in_the_checkout(monkeypatch,
                                                        cache_dir_config):
    monkeypatch.delenv(cc.ENV, raising=False)
    first = cc.enable_compile_cache()
    assert first == cc.enable_compile_cache() == str(cc.CHECKOUT_CACHE)
    assert jax.config.jax_compilation_cache_dir == first
    assert (cc.CHECKOUT_CACHE.parent / "src" / "repro").is_dir()
