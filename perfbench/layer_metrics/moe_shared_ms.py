"""Device ms a traced step under `hvd.moe.shared`: the shared expert's
SwiGLU, all passes. Nothing where the program has no such scope."""

from perfbench.scope_readers import scope_ms

NAME = "moe_shared_ms"
UNIT = "ms"
LAYER = "model layers (hvd.* scopes)"
MOVES = "tokens_per_s_chip"


def compute(ctx):
    return scope_ms(ctx, "hvd.moe.shared")
