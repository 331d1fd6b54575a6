"""Host seconds in the backend compiler, or loading its results from
the compile cache, over every program the process made:
`hvd_jit_seconds_total{phase="backend"}`. `backend_compile_s` is the
step program's part of it."""

from perfbench.setup_readers import jit_seconds

NAME = "programs_backend_compile_s"
UNIT = "s"
LAYER = "entry points (hvd.init, common/compile_cache.py, parallel/aot.py)"
MOVES = "setup_s"


def compute(_ctx):
    return jit_seconds("backend")
