"""The persistent compile cache goes where one helper says, before the
first compile: JAX_COMPILATION_CACHE_DIR when set (and nothing else is
set), else a fixed directory inside the checkout."""
import os

import pytest

from parsec_tpu.utils import compile_cache


@pytest.fixture
def restore_cache_dir():
    import jax
    was = jax.config.jax_compilation_cache_dir
    yield jax
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_kept(monkeypatch, tmp_path, restore_cache_dir):
    jax = restore_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.place_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir is None


def test_unset_env_gives_checkout_dir(monkeypatch, restore_cache_dir):
    jax = restore_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.place_compile_cache()
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    assert path == os.path.join(checkout, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
