"""What the `<kernel>_roofline` metrics of one cell's own kernels
share: the least time the chip could take for a traced step's calls,
the larger of FLOPs / peak FLOP/s and bytes / peak bytes/s by the
cell's model file, over the device time of the custom calls whose name
starts with a prefix (`kernel_readers.custom_call_ms`). The cell is
named because `ctx` carries neither spec nor configuration. `ctx` is
the driver's context (README.md)."""

from __future__ import annotations

import os
from typing import Optional

from perfbench import peaks, run
from perfbench.kernel_readers import custom_call_ms


def roofline_pct(ctx, prefix: str, cell: str, flops: str,
                 bytes_moved: str) -> Optional[float]:
    """100 x least time / measured time of the custom calls named
    `prefix`*, with `flops` and `bytes_moved` the names of the model
    file's counting functions (config, spec) -> number; nothing where
    the program has no such kernel or the run was not traced."""
    measured_ms = custom_call_ms(ctx, prefix)
    if not measured_ms:
        return None
    spec = run.read_json(os.path.join(run.HERE, "workloads", cell + ".json"))
    config = run.read_json(os.path.join(run.HERE, "configs",
                                        spec["config"] + ".json"))
    model = run.load_module(run.HERE, "models", spec["model"])
    peak = peaks.lookup(ctx["device_kind"])
    least_s = max(
        getattr(model, flops)(config, spec) / peak["bf16_flops_per_s"],
        getattr(model, bytes_moved)(config, spec) / peak["hbm_bytes_per_s"])
    return 100.0 * 1e3 * least_s / measured_ms
