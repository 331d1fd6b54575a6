"""Device ms a traced step under `hvd.mtp`: the multi-token module's two
norms, its embedding lookup and the 2D x D projection (its block keeps
its own scopes), all passes. Nothing where the program has no such
scope."""

from perfbench.scope_readers import scope_ms

NAME = "mtp_ms"
UNIT = "ms"
LAYER = "model layers (hvd.* scopes)"
MOVES = "tokens_per_s_chip"


def compute(ctx):
    return scope_ms(ctx, "hvd.mtp")
