"""Device ms a traced step under `hvd.attn.core`: attention scores,
mask, softmax and PV, all passes."""

from perfbench.scope_readers import scope_ms

NAME = "attn_core_ms"
UNIT = "ms"
LAYER = "model layers (models/transformer.py hvd.* scopes)"
MOVES = "tokens_per_s_chip"


def compute(ctx):
    return scope_ms(ctx, "hvd.attn.core")
