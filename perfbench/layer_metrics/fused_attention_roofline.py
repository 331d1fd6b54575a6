"""Roofline share of the fused attention kernels (the Pallas kernels
`hvd_fused_attention_fwd` / `_dq` / `_dkv` of
`horovod_tpu/parallel/fused_attention.py`) in a cell whose layers mix
a sliding window with full causal attention: the least time the chip
could take for the (query, key) pairs the masks leave in a traced
step, the larger of FLOPs / peak FLOP/s and bytes / peak bytes/s
(`perfbench/models/afmoe.py` `attention_flops` / `attention_bytes`:
the forward kernel twice under remat, dQ, dK/dV, both layer kinds),
over the device time of those custom calls. Blocks a mask cuts are
computed whole, so the share stays under what the MXU reaches on
whole blocks. Nothing where the program has no such kernel or the
cell's model file does not count its pairs. The metric is the one
cell's, whose files say the shapes."""

import os

from perfbench import peaks, run
from perfbench.kernel_readers import custom_call_ms

NAME = "fused_attention_roofline"
UNIT = "%"
LAYER = "XLA fusions (kernels)"
MOVES = "tokens_per_s_chip"
CELL = "trinity-large-ep32tp4.jit-dp1"


def compute(ctx):
    measured_ms = custom_call_ms(ctx, "hvd_fused_attention_")
    if not measured_ms:
        return None
    spec = run.read_json(os.path.join(run.HERE, "workloads", CELL + ".json"))
    config = run.read_json(os.path.join(run.HERE, "configs",
                                        spec["config"] + ".json"))
    model = run.load_module(run.HERE, "models", spec["model"])
    peak = peaks.lookup(ctx["device_kind"])
    least_s = max(
        model.attention_flops(config, spec) / peak["bf16_flops_per_s"],
        model.attention_bytes(config, spec) / peak["hbm_bytes_per_s"])
    return 100.0 * 1e3 * least_s / measured_ms
