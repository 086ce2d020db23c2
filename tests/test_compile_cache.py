"""The persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when it is
set (and then the helper sets nothing), else ``<checkout>/.jax_cache``."""

import jax
import pytest

from repro import compile_cache

_KEYS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def cache_config():
    """Restore JAX's cache settings; nothing compiles while they differ."""
    was = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def test_cache_dir_from_the_environment_is_left_to_jax(monkeypatch, tmp_path,
                                                       cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = {k: getattr(jax.config, k) for k in _KEYS}
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert {k: getattr(jax.config, k) for k in _KEYS} == before


def test_cache_dir_defaults_to_the_ignored_checkout_dir(monkeypatch,
                                                       cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    ignored = (compile_cache.CHECKOUT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
