"""Seconds `aot_compile` spent tracing and lowering the cell's step
program on the host: the program's `hvd_aot_lower_seconds_total`."""

from perfbench.scope_readers import counter

NAME = "trace_lower_s"
UNIT = "s"
LAYER = "entry points (hvd.init, common/compile_cache.py, parallel/aot.py)"
MOVES = "setup_s"


def compute(_ctx):
    return counter("hvd_aot_lower_seconds_total")
