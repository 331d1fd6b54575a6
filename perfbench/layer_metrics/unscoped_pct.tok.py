"""See `perfbench/scope_readers.py` `unscoped_pct`."""

from perfbench.scope_readers import unscoped_pct as compute  # noqa: F401

NAME = "unscoped_pct.tok"
UNIT = "%"
LAYER = "jit step, device side (parallel/train.py build_train_step)"
MOVES = "tokens_per_s_chip"
