"""Roofline share of the selective-scan kernels (the Pallas kernels
`hvd_selective_scan_fwd` / `_bwd` of
`horovod_tpu/parallel/selective_scan.py`): the least time the chip
could take for a traced step's scans, the larger of FLOPs / peak FLOP/s
and bytes / peak bytes/s (`perfbench/models/jamba.py`
`selective_scan_flops` / `selective_scan_bytes`: the forward kernel
twice under remat and the backward once; the recurrence's own
operations, every operand read and every result written once), over the
device time of those custom calls. The bytes bound it: 19.7 GB against
0.27 TFLOP. The kernels' true bound is the vector unit's exp and
multiply-adds over 16 states a channel, for which `peaks.json` has no
peak, so the share reads low by construction. Nothing where the program
has no such kernel (the `jax.numpy` path off the TPU, or a program older
than the kernels). The metric is the one cell's, whose files say the
shapes."""

from perfbench.roofline_readers import roofline_pct

NAME = "selective_scan_roofline"
UNIT = "%"
LAYER = "XLA fusions (kernels)"
MOVES = "tokens_per_s_chip"
CELL = "jamba2-3b-tp2vp4.jit-dp1"


def compute(ctx):
    return roofline_pct(ctx, "hvd_selective_scan_", CELL,
                        "selective_scan_flops", "selective_scan_bytes")
