"""JAX's persistent compilation cache is placed from outside the program:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``."""
import os
import textwrap

import jax

from conftest import REPO, run_subprocess
from repro.launch import compile_cache


def test_default_is_the_fixed_repo_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.enable() == got        # stable across calls
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    old = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == old   # nothing set


def test_env_dir_receives_the_cache_files(tmp_path):
    """A compile after enable() writes its cache entry under the
    environment's directory and nowhere in the repo."""
    repo_cache = os.path.join(REPO, ".jax_cache")
    before = (set(os.listdir(repo_cache)) if os.path.isdir(repo_cache)
              else set())
    code = textwrap.dedent(f"""
        import os
        os.environ["JAX_COMPILATION_CACHE_DIR"] = {str(tmp_path)!r}
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
        import jax, jax.numpy as jnp
        from repro.launch import compile_cache
        compile_cache.enable()
        jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(8)).block_until_ready()
    """)
    run_subprocess(code, devices=1)
    assert os.listdir(tmp_path), "no cache entry written"
    after = (set(os.listdir(repo_cache)) if os.path.isdir(repo_cache)
             else set())
    assert after == before
