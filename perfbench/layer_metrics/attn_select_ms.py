"""Device ms a traced step under `hvd.attn.select`: the block-sparse
layer's selection (`parallel/sparse_attention.py` `select_blocks`:
pooled keys, their softmax, the sum over a group's heads, the max over
a block, top-k, the words and walk tables the kernels read), forward
and recompute; it has no backward pass. Nothing where the program has
no such scope."""

from perfbench.scope_readers import scope_ms

NAME = "attn_select_ms"
UNIT = "ms"
LAYER = "model layers (hvd.* scopes)"
MOVES = "tokens_per_s_chip"


def compute(ctx):
    return scope_ms(ctx, "hvd.attn.select")
