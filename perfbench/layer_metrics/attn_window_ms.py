"""Device ms a traced step under `hvd.attn.window`: the attention
core (scores, mask, softmax, PV) of the sliding-window layers, all
passes; `attn_core_ms` reads the full layers' core beside it. Nothing
where the program has no such scope."""

from perfbench.scope_readers import scope_ms

NAME = "attn_window_ms"
UNIT = "ms"
LAYER = "model layers (hvd.* scopes)"
MOVES = "tokens_per_s_chip"


def compute(ctx):
    return scope_ms(ctx, "hvd.attn.window")
