"""Where the entry points keep JAX's persistent compile cache."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_env_dir_is_used_and_nothing_else_set(monkeypatch, tmp_path,
                                              restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_uses_fixed_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(compile_cache.CHECKOUT_CACHE_DIR)
    assert compile_cache.CHECKOUT_CACHE_DIR.parent.joinpath(
        "src", "repro", "launch", "compile_cache.py").is_file()
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.enable_compile_cache() == got   # stable across calls
