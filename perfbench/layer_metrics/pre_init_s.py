"""Seconds from the process's creation to the first line of
`hvd.init()`: the interpreter, the imports and whatever touched JAX
before it; the program's gauge `hvd_init_started_after_seconds`."""

from perfbench.setup_readers import series_sum

NAME = "pre_init_s"
UNIT = "s"
LAYER = "entry points (hvd.init, common/compile_cache.py, parallel/aot.py)"
MOVES = "setup_s"


def compute(_ctx):
    return series_sum("hvd_init_started_after_seconds")
