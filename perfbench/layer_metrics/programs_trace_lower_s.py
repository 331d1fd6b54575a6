"""Host seconds of Python tracing and lowering over every program the
process made, the step's and set-up's own (weights, sample, reference,
ring): `hvd_jit_seconds_total`, phases `trace` and `lower`.
`trace_lower_s` is the step program's part of it."""

from perfbench.setup_readers import jit_seconds

NAME = "programs_trace_lower_s"
UNIT = "s"
LAYER = "entry points (hvd.init, common/compile_cache.py, parallel/aot.py)"
MOVES = "setup_s"


def compute(_ctx):
    return jit_seconds("trace", "lower")
