"""Device ms a traced step under `hvd.hc`: the residual streams' mixers
(coefficients, Sinkhorn, the mix into each sub-layer's input, the
write-back, the exit sum), all passes. Nothing where the program has
no such scope."""

from perfbench.scope_readers import scope_ms

NAME = "hc_ms"
UNIT = "ms"
LAYER = "model layers (hvd.* scopes)"
MOVES = "tokens_per_s_chip"


def compute(ctx):
    return scope_ms(ctx, "hvd.hc")
