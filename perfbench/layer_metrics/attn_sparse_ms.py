"""Device ms a traced step under `hvd.attn.sparse`: attention over the
key blocks each query selected (the `hvd_sparse_attention_*` kernels
and the `di` row sums), all passes; a sparse layer at or under its
dense length runs `hvd.attn.core` instead. Nothing where the program
has no such scope."""

from perfbench.scope_readers import scope_ms

NAME = "attn_sparse_ms"
UNIT = "ms"
LAYER = "model layers (hvd.* scopes)"
MOVES = "tokens_per_s_chip"


def compute(ctx):
    return scope_ms(ctx, "hvd.attn.sparse")
