"""Device ms a traced step under `hvd.embed` and `hvd.head_loss`: the
embedding, the final norm, the LM head and the cross-entropy."""

from perfbench.scope_readers import scope_ms

NAME = "head_loss_ms"
UNIT = "ms"
LAYER = "model layers (models/transformer.py hvd.* scopes)"
MOVES = "tokens_per_s_chip"


def compute(ctx):
    return scope_ms(ctx, "hvd.embed", "hvd.head_loss")
