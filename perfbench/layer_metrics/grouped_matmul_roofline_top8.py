"""Roofline share of the held experts' grouped matmuls in the top-8
cell (the Pallas kernels `hvd_grouped_matmul_*` of
`horovod_tpu/parallel/grouped_matmul.py`, at hidden 2304 and expert
width 896, a quarter of the dispatch buffer live): the least time the
chip could take for the published work of a traced step, the larger of
FLOPs / peak FLOP/s and bytes / peak bytes/s
(`perfbench/models/mellum.py` `grouped_matmul_flops` / `_bytes`, the
routed rows at their expectation; the FLOPs bound it, 33.0 ms against
16.2 ms), over the device time of those custom calls.
`grouped_matmul_roofline` reads the same kernels in the `xing4` cell.
Nothing where the program has no such kernel (the `lax.ragged_dot`
path, which a program whose row movers refuse hidden 2304 takes). The
metric is the one cell's, whose files say the shapes."""

from perfbench.roofline_readers import roofline_pct

NAME = "grouped_matmul_roofline_top8"
UNIT = "%"
LAYER = "XLA fusions (kernels)"
MOVES = "tokens_per_s_chip"
CELL = "mellum2-12b-ep4.jit-dp1"


def compute(ctx):
    return roofline_pct(ctx, "hvd_grouped_matmul_", CELL,
                        "grouped_matmul_flops", "grouped_matmul_bytes")
