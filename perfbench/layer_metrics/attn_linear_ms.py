"""Device ms a traced step under `hvd.attn.linear`: the core of the
linear-attention layers (`parallel/linear_attention.py`: the masked
products inside a chunk, the state carried from chunk to chunk and its
cotangent carried back), all passes. Nothing where the program has no
such scope."""

from perfbench.scope_readers import scope_ms

NAME = "attn_linear_ms"
UNIT = "ms"
LAYER = "model layers (hvd.* scopes)"
MOVES = "tokens_per_s_chip"


def compute(ctx):
    return scope_ms(ctx, "hvd.attn.linear")
