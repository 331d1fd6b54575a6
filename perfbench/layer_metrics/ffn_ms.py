"""Device ms a traced step under `hvd.ffn`: norm and SwiGLU, all
passes."""

from perfbench.scope_readers import scope_ms

NAME = "ffn_ms"
UNIT = "ms"
LAYER = "model layers (models/transformer.py hvd.* scopes)"
MOVES = "tokens_per_s_chip"


def compute(ctx):
    return scope_ms(ctx, "hvd.ffn")
