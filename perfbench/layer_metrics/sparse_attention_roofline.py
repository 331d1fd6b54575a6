"""Roofline share of the block-sparse attention kernels (the Pallas
kernels `hvd_sparse_attention_fwd` / `_dq` / `_dkv` of
`horovod_tpu/parallel/sparse_attention.py`): the least time the chip
could take for the (query, key) pairs the selection keeps in a traced
step, the larger of FLOPs / peak FLOP/s and bytes / peak bytes/s
(`perfbench/models/minicpm_sala.py` `sparse_attention_flops` /
`sparse_attention_bytes`: the forward kernel twice under remat, dQ,
dK/dV, over 64 blocks of 64 keys a query), over the device time of
those custom calls. The kernels compute every kernel block that some
query of a query block selected, whole and under a token-level mask,
so the share is what the selection's clustering leaves of the MXU's:
with seeded weights the 512 queries of a block select nearly every
block between them and the share stays near the selected pairs' part
of the causal ones. Nothing where the program has no such kernel. The
metric is the one cell's, whose files say the shapes."""

from perfbench.roofline_readers import roofline_pct

NAME = "sparse_attention_roofline"
UNIT = "%"
LAYER = "XLA fusions (kernels)"
MOVES = "tokens_per_s_chip"
CELL = "minicpm-sala-tp2vp8.jit-dp1"


def compute(ctx):
    return roofline_pct(ctx, "hvd_sparse_attention_", CELL,
                        "sparse_attention_flops", "sparse_attention_bytes")
