"""Where JAX keeps its persistent compilation cache.

Entry points (`launch/serve.py`, `launch/train.py`, `benchmarks/run.py`,
`chip_smoke.py`) call `setup_compile_cache()` once at the top of their
``main``; nothing calls it at import time.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed
path, so that every process of this checkout finds what the last one
compiled.
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
