"""Where the entry points keep JAX's persistent compilation cache
(repro.launch.compile_cache)."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache

SRC = str(Path(compile_cache.__file__).resolve().parents[2])


def test_checkout_is_repo_root():
    assert (compile_cache.CHECKOUT / "src" / "repro").is_dir()


def test_env_dir_is_left_to_jax(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there."""
    cache = tmp_path / "cache"
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import use_compile_cache\n"
        "use_compile_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == str(cache)
    assert any(cache.iterdir())


def test_default_dir_is_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = jax.config.jax_compilation_cache_dir
    try:
        compile_cache.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(
            compile_cache.CHECKOUT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
