"""Where the entry points put JAX's persistent compilation cache."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import cache

REPO = Path(__file__).resolve().parents[1]


def test_checkout_cache_is_at_the_checkout_root():
    assert cache.CHECKOUT_CACHE == REPO / ".jax_cache"


def test_without_env_the_cache_goes_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert cache.enable_compile_cache() == str(cache.CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(
            cache.CHECKOUT_CACHE)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_with_env_the_cache_is_written_there_only(tmp_path):
    """A compile after ``enable_compile_cache`` lands in the directory
    the environment names, and nothing is set to the checkout's."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               PYTHONPATH=str(REPO / "src"))
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: jnp.sin(x) * 2.0)(jnp.ones(8)).block_until_ready()\n")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, check=True,
                         timeout=120).stdout.split()
    assert out == [str(tmp_path), str(tmp_path)]
    assert any(tmp_path.iterdir())
