"""Median over the window's steps of the time from calling the
executable to its return, before the loss is read; the benchmark's
clock."""

NAME = "host_dispatch_ms"
UNIT = "ms"
LAYER = "jit step, host side (parallel/aot.py executable call)"
MOVES = "step_ms_p95"


def compute(ctx):
    import statistics
    if not ctx["dispatch_s"]:
        return None
    return 1e3 * statistics.median(ctx["dispatch_s"])
