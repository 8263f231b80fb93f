"""The entry points' persistent compilation cache location."""
import jax
import pytest

from repro.utils import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_defaults_to_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    root = compile_cache.CHECKOUT_CACHE_DIR.parent
    assert got == str(root / ".jax_cache")
    assert (root / "chip_smoke.py").exists()  # the checkout, not site-packages
    assert jax.config.jax_compilation_cache_dir == got


def test_cache_env_var_wins_and_code_sets_nothing(
    monkeypatch, tmp_path, restore_cache_dir
):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None
