"""JAX's persistent compilation cache, placed from outside the program.

``setup_compile_cache()`` is called at the start of each entry point's
``main()`` (``chip_smoke.py``, ``launch/train.py``, ``benchmarks/run.py``),
never at import:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself and
  nothing here overrides it;
* otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed path
  (it is part of the cache key, so a moving directory never hits).
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def setup_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
