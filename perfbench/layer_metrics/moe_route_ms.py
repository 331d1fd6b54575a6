"""Device ms a traced step under `hvd.moe.route`: the expert layers'
norm, router scores, top-k, the sort and the gathers of the pairs held
here out and back, all passes. Nothing where the program has no such
scope."""

from perfbench.scope_readers import scope_ms

NAME = "moe_route_ms"
UNIT = "ms"
LAYER = "model layers (hvd.* scopes)"
MOVES = "tokens_per_s_chip"


def compute(ctx):
    return scope_ms(ctx, "hvd.moe.route")
