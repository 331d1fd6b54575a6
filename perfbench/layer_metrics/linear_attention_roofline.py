"""Roofline share of the linear-attention kernels (the Pallas kernels
`hvd_linear_attention_fwd` / `_dq` / `_dkv` of
`horovod_tpu/parallel/linear_attention.py`): the least time the chip
could take for a traced step's lightning cores, the larger of FLOPs /
peak FLOP/s and bytes / peak bytes/s (`perfbench/models/minicpm_sala.py`
`linear_attention_flops` / `linear_attention_bytes`: the forward kernel
twice under remat, dQ, dK/dV; the recurrence's own operations, every
operand read and every result written once), over the device time of
those custom calls. The bytes bound it: 7.2 GB against 0.3 TFLOP.
Nothing where the program has no such kernel (the `jax.numpy` path
off the TPU, or a program older than the kernels). The metric is the
one cell's, whose files say the shapes."""

from perfbench.roofline_readers import roofline_pct

NAME = "linear_attention_roofline"
UNIT = "%"
LAYER = "XLA fusions (kernels)"
MOVES = "tokens_per_s_chip"
CELL = "minicpm-sala-tp2vp8.jit-dp1"


def compute(ctx):
    return roofline_pct(ctx, "hvd_linear_attention_", CELL,
                        "linear_attention_flops", "linear_attention_bytes")
