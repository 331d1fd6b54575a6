"""See `perfbench/scope_readers.py` `recompute_ms`."""

from perfbench.scope_readers import recompute_ms as compute  # noqa: F401

NAME = "recompute_ms"
UNIT = "ms"
LAYER = "model layers (models/transformer.py hvd.* scopes)"
MOVES = "tokens_per_s_chip"
