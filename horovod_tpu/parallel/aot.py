"""Ahead-of-time compilation of jitted step/forward functions.

Promoted out of bench.py (round 15) so the benchmark harness and the
serving subsystem (serving.py) share one warmup/AOT path: lower the
jitted function against exemplar arguments once, compile, and reuse
the executable — both for the hot loop (no trace/compile on the
first timed call) and for XLA's cost analysis (compiling a second
time just to read flops would double a multi-ten-second ResNet
compile).

A failed compile raises: a caller that asked for an executable for
this backend must not be handed a jit path that may be compiling
for another. Only cost analysis is optional (flops=0.0 where the
backend does not report it).
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

from ..common import logging as hlog
from ..metrics import REGISTRY as _METRICS
from ..tracing import host_span

_m_lower = _METRICS.counter(
    "hvd_aot_lower_seconds_total",
    "Host seconds aot_compile spent tracing and lowering step "
    "functions to StableHLO.")
_m_compile = _METRICS.counter(
    "hvd_aot_compile_seconds_total",
    "Host seconds aot_compile spent in the backend compiler or "
    "loading its result from the persistent compile cache.")
_m_programs = _METRICS.counter(
    "hvd_aot_programs_total", "Programs aot_compile produced.")


def aot_compile(step_fn: Callable[..., Any], *args
                ) -> Tuple[Callable[..., Any], float]:
    """AOT-compile ``step_fn`` (a jitted callable) for ``args``.

    Returns ``(compiled, flops_per_execution)``. The executable is
    exact-shape, exact-placement: callers must feed arguments matching
    ``args``. flops is 0.0 whenever cost analysis is unavailable.
    """
    with host_span("aot.lower") as lower:
        lowered = step_fn.lower(*args)
    with host_span("aot.compile") as compile_:
        compiled = lowered.compile()
    _m_lower.inc(lower.seconds)
    _m_compile.inc(compile_.seconds)
    _m_programs.inc()
    flops = 0.0
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        flops = float(ca.get("flops", 0.0))
    except Exception as e:  # pragma: no cover - backend-dependent
        hlog.info("aot: cost analysis unavailable (%s)", e)
    return compiled, flops
