"""Device ms a traced step under `hvd.attn.proj`: the attention block's
norm, projections, rope and GQA repeat, all passes."""

from perfbench.scope_readers import scope_ms

NAME = "attn_proj_ms"
UNIT = "ms"
LAYER = "model layers (models/transformer.py hvd.* scopes)"
MOVES = "tokens_per_s_chip"


def compute(ctx):
    return scope_ms(ctx, "hvd.attn.proj")
