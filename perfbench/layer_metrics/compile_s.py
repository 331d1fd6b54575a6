"""Seconds the host spent in `aot_compile` (trace, lower, compile or
cache hit) for the cell's one step program; the benchmark's clock."""

NAME = "compile_s"
UNIT = "s"
LAYER = "entry points (hvd.init, common/compile_cache.py, parallel/aot.py)"
MOVES = "setup_s"


def compute(ctx):
    return ctx["compile_s"]
