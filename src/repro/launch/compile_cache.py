"""JAX's persistent compilation cache for this checkout's entry points."""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> None:
    """Keep compiled programs across runs. Where JAX_COMPILATION_CACHE_DIR is
    set, JAX reads it itself and nothing else is set; otherwise the cache is
    `<checkout>/.jax_cache`, a fixed path, so every later run of this
    checkout finds the entries. Entry points call this before their first
    compile; importing this module changes nothing."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT / ".jax_cache"))
