"""Seconds `aot_compile` spent in the backend compiler, or loading
its result from the compile cache: the program's
`hvd_aot_compile_seconds_total`."""

from perfbench.scope_readers import counter

NAME = "backend_compile_s"
UNIT = "s"
LAYER = "entry points (hvd.init, common/compile_cache.py, parallel/aot.py)"
MOVES = "setup_s"


def compute(_ctx):
    return counter("hvd_aot_compile_seconds_total")
