"""XLA's persistent compilation cache: one rule for every entry point.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it. Otherwise the cache lives in ``.jax_cache/`` at the
checkout root: a fixed path, because the path is part of what a later run
must find again, and inside the checkout, because the program writes
nothing around it.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def enable_compile_cache(min_compile_secs: float | None = None) -> str:
    """Turn the persistent cache on under the rule above; returns its
    directory. `min_compile_secs` (None keeps JAX's default) skips writing
    compiles cheaper than that."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    if min_compile_secs is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_compile_secs)
    return path
