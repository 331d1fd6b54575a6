"""See `perfbench/scope_readers.py` `grad_reduce_ms`."""

from perfbench.scope_readers import grad_reduce_ms as compute  # noqa: F401

NAME = "grad_reduce_ms"
UNIT = "ms"
LAYER = "bucketed overlap (train.py plan_overlap)"
MOVES = "tokens_per_s_chip"
