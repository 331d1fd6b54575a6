"""Where JAX's persistent compile cache lives.

Call `enable()` before the first compile. Where
JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and no directory
is set in code; otherwise the cache is `<checkout>/.jax_cache`, a fixed
path resolved from this package's location (the path is part of the
cache key, so a directory that moves never hits). Ranks started by
`horovod_tpu.runner` inherit JAX_* variables, so they share it.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Switch the persistent cache on and return its directory."""
    import jax

    placed = os.environ.get(ENV_VAR)
    if not placed:
        placed = default_dir()
        jax.config.update("jax_compilation_cache_dir", placed)
    # Cache every program: a cold chip call is dominated by compiles
    # the default thresholds (>= 1 s, >= 2 executions) would skip.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed
