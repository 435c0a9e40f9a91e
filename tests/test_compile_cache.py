"""Where the entry points keep JAX's persistent compilation cache."""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_env_dir_is_the_only_cache(monkeypatch, tmp_path, restore_cache_config):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, compiles land there and the
    repository's own cache directory is not used."""
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    before = set(compile_cache.REPO_CACHE.glob("*")) if compile_cache.REPO_CACHE.exists() else set()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    jax.jit(lambda v: jnp.sin(v) * 3 + 1.25)(jnp.arange(7.0)).block_until_ready()
    assert any(tmp_path.iterdir()), "no cache entry written to the env directory"
    after = set(compile_cache.REPO_CACHE.glob("*")) if compile_cache.REPO_CACHE.exists() else set()
    assert after == before


def test_default_dir_is_fixed_and_ignored(monkeypatch, restore_cache_config):
    """Without the variable the cache is ``<repo>/.jax_cache``: the same
    path in every process, and one git ignores."""
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable_compile_cache()
    root = compile_cache.REPO_CACHE.parent
    assert path == str(root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path
    patterns = (root / ".gitignore").read_text().split()
    assert ".jax_cache/" in patterns or ".jax_cache" in patterns
