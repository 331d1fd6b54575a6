"""Time of one step during which a collective was on the first chip's
timeline (running, or between an asynchronous start and its done), over
the traced steps."""

from perfbench.layer_readers import per_traced_step_ms

NAME = "collective_ms"
UNIT = "ms"
LAYER = "bucketed overlap (train.py plan_overlap)"
MOVES = "tokens_per_s_chip"


def compute(ctx):
    return per_traced_step_ms(ctx, "collective_s")
