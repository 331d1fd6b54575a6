"""Device ms a traced step under `hvd.moe.experts`: the grouped matmuls
over the experts held and their SwiGLU, all passes. Nothing where the
program has no such scope."""

from perfbench.scope_readers import scope_ms

NAME = "moe_experts_ms"
UNIT = "ms"
LAYER = "model layers (hvd.* scopes)"
MOVES = "tokens_per_s_chip"


def compute(ctx):
    return scope_ms(ctx, "hvd.moe.experts")
