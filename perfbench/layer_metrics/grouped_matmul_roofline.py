"""Roofline share of the routed experts' grouped matmuls (the Pallas
kernels `hvd_grouped_matmul_fwd` / `_dx` / `_dw` of
`horovod_tpu/parallel/grouped_matmul.py`): the least time the chip
could take for the published work of a traced step, the larger of
FLOPs / peak FLOP/s and bytes / peak bytes/s
(`perfbench/models/xing4.py` `grouped_matmul_flops` / `_bytes`, the
routed rows at their expectation), over the device time of those
custom calls. Nothing where the program has no such kernel. The
metric is the one cell's, whose files say the shapes."""

import os

from perfbench import peaks, run
from perfbench.kernel_readers import custom_call_ms

NAME = "grouped_matmul_roofline"
UNIT = "%"
LAYER = "XLA fusions (kernels)"
MOVES = "tokens_per_s_chip"
CELL = "xing4-29b-ep8.jit-dp1"


def compute(ctx):
    measured_ms = custom_call_ms(ctx, "hvd_grouped_matmul_")
    if not measured_ms:
        return None
    spec = run.read_json(os.path.join(run.HERE, "workloads", CELL + ".json"))
    config = run.read_json(os.path.join(run.HERE, "configs",
                                        spec["config"] + ".json"))
    model = run.load_module(run.HERE, "models", spec["model"])
    peak = peaks.lookup(ctx["device_kind"])
    least_s = max(
        model.grouped_matmul_flops(config, spec) / peak["bf16_flops_per_s"],
        model.grouped_matmul_bytes(config, spec) / peak["hbm_bytes_per_s"])
    return 100.0 * 1e3 * least_s / measured_ms
