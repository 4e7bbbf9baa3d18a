"""The entry points keep JAX's compile cache where JAX_COMPILATION_CACHE_DIR
says, and otherwise in one fixed directory inside the checkout."""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.compile_cache import CHECKOUT, use_compile_cache


@pytest.fixture
def restore_cache_config():
    keys = (
        "jax_compilation_cache_dir",
        "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    prev = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_cache_lands_in_env_dir(monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    assert any(tmp_path.iterdir())


def test_cache_defaults_to_checkout_dir(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = use_compile_cache()
    assert path == str(CHECKOUT / ".jax_cache") == jax.config.jax_compilation_cache_dir
    assert (CHECKOUT / "src" / "repro").is_dir()
