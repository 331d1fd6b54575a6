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

`enable()` is the first call of every entry point, so it is also where
the process starts to count its programs (`listen()`): JAX reports
every trace, lowering, backend compile and cache request through
`jax.monitoring`, whichever path made the program (`jax.jit`'s first
call, `aot_compile`, an eager op).
"""

from __future__ import annotations

import os
import threading

from ..metrics import REGISTRY as _METRICS

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_m_jit_seconds = _METRICS.counter(
    "hvd_jit_seconds_total",
    "Host seconds JAX spent making programs, by phase (trace: Python "
    "to jaxpr; lower: jaxpr to StableHLO; backend: the compiler or "
    "the load from the persistent cache) and program. A phase that "
    "runs inside another on the same thread adds nothing: the outer "
    "one's seconds hold it.", ("phase", "program"))
_m_jit_programs = _METRICS.counter(
    "hvd_jit_programs_total",
    "Programs handed to the backend compiler (cache loads included), "
    "by program. Rising after warm-up: something recompiles.",
    ("program",))
_m_cache_requests = _METRICS.counter(
    "hvd_compile_cache_requests_total",
    "Programs looked up in the persistent compile cache, by result "
    "(hit: loaded; miss: compiled and written). Misses on a start "
    "meant to be warm: entries were evicted, or the programs changed.",
    ("result",))

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_REQUESTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_open_phases = threading.local()   # .n: phases open on this thread
_listening = False


def _program(fun_name) -> str:
    """One label for a program's three phases: the trace event names
    the function (`step`), the other two its module (`jit(step)`)."""
    name = str(fun_name or "unnamed")
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name


def _on_phase_start(event, _start, **_kw) -> None:
    if event in _PHASES:
        _open_phases.n = getattr(_open_phases, "n", 0) + 1


def _on_duration(event, secs, fun_name=None, **_kw) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        return
    # JAX announces a phase's start too (a scalar event), so a phase
    # inside another, as a jitted helper traced while its caller is, is
    # seen to be inside and its seconds are not counted twice.
    outer = max(getattr(_open_phases, "n", 1) - 1, 0)
    _open_phases.n = outer
    if outer and phase != "backend":
        return
    program = _program(fun_name)
    if phase == "backend":
        _m_jit_programs.labels(program=program).inc()
    if not outer:
        _m_jit_seconds.labels(phase=phase, program=program).inc(secs)


def _on_event(event, **_kw) -> None:
    result = _CACHE_REQUESTS.get(event)
    if result is not None:
        _m_cache_requests.labels(result=result).inc()


def listen() -> None:
    """Count this process's programs into the registry from here on:
    one `jax.monitoring` listener set, however often it is called."""
    global _listening
    if _listening:
        return
    _listening = True
    from jax import monitoring
    monitoring.register_scalar_listener(_on_phase_start)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


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
    listen()
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
