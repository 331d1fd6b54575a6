"""The part of `collective_ms` during which no other instruction ran on
the first chip: communication the backward pass did not hide."""

from perfbench.layer_readers import per_traced_step_ms

NAME = "collective_exposed_ms"
UNIT = "ms"
LAYER = "bucketed overlap (train.py plan_overlap)"
MOVES = "tokens_per_s_chip"


def compute(ctx):
    return per_traced_step_ms(ctx, "collective_exposed_s")
