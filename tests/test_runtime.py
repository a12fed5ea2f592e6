"""Where the entry points keep JAX's persistent compile cache."""
import pathlib

import jax
import pytest

from repro import runtime

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_used_and_nothing_is_set(cache_config, monkeypatch,
                                            tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert runtime.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None


def test_unset_env_uses_the_fixed_checkout_dir(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = runtime.use_compile_cache()
    assert first == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert runtime.use_compile_cache() == first


def test_checkout_cache_dir_is_gitignored():
    ignored = (CHECKOUT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
