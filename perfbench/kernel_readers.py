"""Device time of a program's own kernels, by the name the program
gave their custom calls (`name=` of a `pallas_call`), for the
`<kernel>_roofline` metrics. Reads the trace this run wrote, as
`scope_readers.py` does; nothing where the run was not traced or the
program has no such kernel, as a program older than the kernel has
not. `ctx` is the driver's context (README.md)."""

from __future__ import annotations

import functools
import os
from typing import Dict, Optional

from perfbench import scope_reduce
from perfbench.trace_reduce import PS, clip, instruction, self_times


@functools.lru_cache(maxsize=1)
def _self_ms_by_instruction(path: str, _mtime: float):
    """(traced steps, instruction name -> device ms a step of self
    time on the first chip)."""
    steps, ops, _ = scope_reduce.read_trace(path)
    if not steps or not ops:
        return 0, {}
    ops = clip(ops, (steps[0][0], steps[-1][1]))
    out: Dict[str, float] = {}
    for text, ps in self_times(ops).items():
        name = instruction(text)[0]
        out[name] = out.get(name, 0.0) + 1e3 * ps / PS / len(steps)
    return len(steps), out


def custom_call_ms(ctx, prefix: str) -> Optional[float]:
    """Device ms a traced step of the instructions whose name starts
    with `prefix` (XLA names a Pallas call after its `name=`, with a
    `.N` suffix)."""
    traced = ctx["traced"]
    path = scope_reduce.newest_trace()
    if traced is None or path is None:
        return None
    steps, by_name = _self_ms_by_instruction(path, os.path.getmtime(path))
    if steps != traced["steps"]:
        return None
    found = [ms for name, ms in by_name.items() if name.startswith(prefix)]
    return sum(found) if found else None
