"""Where JAX's persistent compile cache lives.

Call `enable()` before the first compile. Where
JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and no directory
is set in code; otherwise the cache is `<checkout>/.jax_cache`, a fixed
path resolved from this package's location (the path is part of the
cache key, so a directory that moves never hits). Ranks started by
`horovod_tpu.runner` inherit JAX_* variables, so they share it.

JAX's key for an entry is computed with names stripped (a
`jax.named_scope` is debug information), so a hit returns the
executable under the names it was first compiled with. The `hvd.*`
device scopes (tracing.DEVICE_SCOPES) are such names, and a profiler
trace reads them from the executable: `enable()` therefore adds the
catalogue's version to the key, through the hook JAX's key has for a
deployment's own component.
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
    _key_names_version()
    return placed


def _key_names_version() -> None:
    """Make tracing.DEVICE_SCOPES_VERSION part of every cache key."""
    from jax._src import cache_key

    from ..tracing import DEVICE_SCOPES_VERSION
    if not callable(getattr(cache_key, "custom_hook", None)):
        from . import logging as hlog
        hlog.warning(
            "compile cache: this JAX has no cache_key.custom_hook; a "
            "cache filled by an older horovod_tpu returns executables "
            "without the current hvd.* scope names")
        return
    cache_key.custom_hook = \
        lambda: f"hvd.device_scopes.v{DEVICE_SCOPES_VERSION}"
