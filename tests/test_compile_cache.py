"""Where ``repro.utils.compile_cache`` puts JAX's persistent compilation
cache: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX's own reading stands),
``<repo>/.jax_cache`` otherwise."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.utils import compile_cache

_OPTIONS = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def restore_cache_config():
    saved = {k: getattr(jax.config, k) for k in _OPTIONS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_env_dir_stands_and_receives_entries(monkeypatch, tmp_path,
                                             restore_cache_config):
    # JAX reads the variable when it is imported; mirror that reading here
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    cc.reset_cache()
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)

    jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.25).lower(
        jnp.zeros((7, 3))).compile()
    assert os.listdir(tmp_path), "no cache entry was written"


def test_default_dir_is_fixed_under_the_repo(monkeypatch,
                                             restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
