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


def aot_compile(step_fn: Callable[..., Any], *args
                ) -> Tuple[Callable[..., Any], float]:
    """AOT-compile ``step_fn`` (a jitted callable) for ``args``.

    Returns ``(compiled, flops_per_execution)``. The executable is
    exact-shape, exact-placement: callers must feed arguments matching
    ``args``. flops is 0.0 whenever cost analysis is unavailable.
    """
    compiled = step_fn.lower(*args).compile()
    flops = 0.0
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        flops = float(ca.get("flops", 0.0))
    except Exception as e:  # pragma: no cover - backend-dependent
        hlog.info("aot: cost analysis unavailable (%s)", e)
    return compiled, flops
