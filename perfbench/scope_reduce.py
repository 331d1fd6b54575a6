"""From a profiler trace to device time by `hvd.*` scope and pass.

    python3 -m perfbench.scope_reduce [file.xplane.pb]

The program names its work (`horovod_tpu/tracing.py` `DEVICE_SCOPES`):
a `jax.named_scope` is part of every instruction's `op_name`, which
the trace carries as the statistic `OP_NAME_STAT` of each event of
the `XLA Ops` line. From it an instruction has a scope, the last
`hvd.*` token of the name (`unscoped` without one), and a pass:
`recompute` where the name holds `rematted_computation` (the forward
run again under `jax.checkpoint`), else `backward` where it holds
`transpose(`, else `forward`. A fusion has the name XLA gave the fused
instruction, so the border between neighbouring scopes is soft by what
XLA fused across it; the sum is exact.

Window, chip and self time are `trace_reduce`'s: from the first
`perfbench.step` span to the last, the first chip's `XLA Ops` line,
nested events taken out of the event that holds them. The parts are
whole picoseconds and add up to that chip's busy time.

The per-layer readers (`scope_readers.py`) get the driver's context,
which holds neither the events' statistics nor the trace's path. So
`newest()` finds the trace the run just wrote: the driver removes the
cell's `out/trace/<cell>` before it traces, which makes the newest
`.xplane.pb` under `out/trace/` this run's. It is read once a process.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import re
import statistics
import sys
import time
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

from perfbench.tests.cut_trace import fields, first
from perfbench.trace_reduce import (COLLECTIVE, DEVICE_PLANE, OPS_LINE, PS,
                                    STEP_SPAN, clip, instruction,
                                    self_times)

HERE = os.path.dirname(os.path.abspath(__file__))
OP_NAME_STAT = "tf_op"
SCOPE = re.compile(r"hvd\.[a-z0-9_.]+")
BUCKET = re.compile(r"^hvd\.grad_reduce\.b(\d+)$")
RECOMPUTE, BACKWARD = "rematted_computation", "transpose("
UNSCOPED = "unscoped"
PASSES = ("forward", "backward", "recompute")
Event = Tuple[str, int, int]            # instruction text, start, end in ps


def scope_and_pass(op_name: str) -> Tuple[str, str]:
    scopes = SCOPE.findall(op_name)
    return (scopes[-1] if scopes else UNSCOPED,
            "recompute" if RECOMPUTE in op_name else
            "backward" if BACKWARD in op_name else "forward")


def read_trace(path: str) -> Tuple[List[Tuple[int, int]], List[Event],
                                   Dict[str, str]]:
    """(the `perfbench.step` spans, the first chip's `XLA Ops` events,
    instruction text -> op_name) of a trace file."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        space = f.read()
    steps: List[Tuple[int, int]] = []
    chips: Dict[int, Any] = {}
    for plane in ProfileData.from_serialized_xspace(space).planes:
        found = DEVICE_PLANE.match(plane.name)
        if found:
            chips[int(found.group(1))] = plane
            continue
        for line in plane.lines:
            steps.extend(_interval(e) for e in line.events
                         if e.name == STEP_SPAN)
    if not chips:
        return sorted(steps), [], {}
    chip = chips[min(chips)]
    ops = [(e.name, *_interval(e)) for line in chip.lines
           if line.name == OPS_LINE for e in line.events]
    return sorted(steps), ops, op_names_of(space, chip.name)


def op_names_of(space: bytes, plane_name: str) -> Dict[str, str]:
    """Event name -> `OP_NAME_STAT` of one plane. The statistic hangs
    on the events' metadata (XPlane.event_metadata[].stats), which
    `ProfileData` does not show, so this walks the wire format: XSpace
    > planes (1) > XPlane: name 2, event_metadata 4, stat_metadata 5 >
    XEventMetadata: name 2, stats 5 > XStat: metadata_id 1, str_value
    5, ref_value 7 (the id of a stat_metadata entry whose name is the
    value)."""
    for number, _, value, _ in fields(space):
        plane = fields(value) if number == 1 else []
        if first(plane, 2, b"").decode() != plane_name:
            continue
        stat_names = {}
        for n, _, v, _ in plane:
            if n == 5:
                meta = fields(first(fields(v), 2, b""))
                stat_names[first(meta, 1)] = first(meta, 2, b"").decode()
        wanted = {i for i, name in stat_names.items()
                  if name == OP_NAME_STAT}
        out: Dict[str, str] = {}
        for n, _, v, _ in plane:
            if n != 4:
                continue
            meta = fields(first(fields(v), 2, b""))
            for stat in (fields(sv) for sn, _, sv, _ in meta if sn == 5):
                if first(stat, 1) in wanted:
                    ref = first(stat, 7, None)
                    out[first(meta, 2, b"").decode()] = (
                        stat_names.get(ref, "") if ref is not None
                        else first(stat, 5, b"").decode())
        return out
    return {}


def _interval(event) -> Tuple[int, int]:
    return (round(event.start_ns * 1000),
            round((event.start_ns + event.duration_ns) * 1000))


def reduce(steps, ops, op_names) -> Optional[Dict[str, Any]]:
    """Self time by (scope, pass) over the traced steps; nothing where
    the trace has no step span or no device event, or the program
    named nothing (no `op_name` holds an `hvd.*` scope)."""
    if not steps or not ops or not any(
            SCOPE.search(name) for name in op_names.values()):
        return None
    window = (steps[0][0], steps[-1][1])
    ops = clip(ops, window)
    by: Dict[Tuple[str, str], int] = defaultdict(int)
    for text, ps in self_times(ops).items():
        by[scope_and_pass(op_names.get(text, ""))] += ps
    return {"steps": len(steps), "busy_ps": sum(by.values()),
            "by": dict(by),
            "bucket_start_ms": _bucket_starts(steps, ops, op_names)}


def _bucket_starts(steps, ops, op_names) -> Dict[str, float]:
    """For each gradient bucket, when its collective starts relative to
    the end of the backward layer scan, in ms, the median over the
    traced steps. Negative is overlap: the collective started while
    the scan still ran. The trace gives a `while` itself no `op_name`,
    so the backward scan is the last `while` that holds an instruction
    of the backward pass."""
    starts: Dict[str, List[float]] = defaultdict(list)
    for lo, hi in steps:
        mine = [(text, s, e) for text, s, e in ops if lo <= s < hi]
        backward = [(s, e) for text, s, e in mine
                    if BACKWARD in op_names.get(text, "")]
        scans = [we for text, ws, we in mine
                 if instruction(text)[1] == "while" and
                 any(ws <= s and e <= we for s, e in backward)]
        if not scans:
            continue
        seen = set()
        for text, s, _ in sorted(mine, key=lambda o: o[1]):
            scope = scope_and_pass(op_names.get(text, ""))[0]
            if BUCKET.match(scope) and scope not in seen and \
                    COLLECTIVE.match(instruction(text)[1]):
                seen.add(scope)
                starts[scope].append((s - max(scans)) / 1e9)
    return {scope: statistics.median(values)
            for scope, values in sorted(
                starts.items(),
                key=lambda kv: int(BUCKET.match(kv[0]).group(1)))}


def ms_a_step(reduced, scopes=None, passes=PASSES) -> Optional[float]:
    """Device ms a traced step of the given scopes (a predicate on the
    scope's name; all when None) and passes; nothing where no such
    instruction ran."""
    ps = [v for (scope, p), v in reduced["by"].items()
          if p in passes and (scopes is None or scopes(scope))]
    if not ps:
        return None
    return 1e3 * sum(ps) / PS / reduced["steps"]


def table(reduced) -> Dict[str, Any]:
    """The `scopes` line: every scope with its forward / backward /
    recompute ms a step and its share of busy time."""
    rows: Dict[str, Dict[str, float]] = {}
    for (scope, p), ps in sorted(reduced["by"].items()):
        rows.setdefault(scope, dict.fromkeys(PASSES, 0.0))[p] = \
            1e3 * ps / PS / reduced["steps"]
    for scope, row in rows.items():
        row["pct_of_busy"] = 100.0 * sum(
            ps for (s, _), ps in reduced["by"].items()
            if s == scope) / reduced["busy_ps"]
    out = {"steps": reduced["steps"],
           "busy_ms": 1e3 * reduced["busy_ps"] / PS / reduced["steps"],
           "scopes": rows}
    if reduced["bucket_start_ms"]:
        out["bucket_start_ms_after_backward_scan"] = \
            reduced["bucket_start_ms"]
    return out


def newest_trace(root: Optional[str] = None) -> Optional[str]:
    found = glob.glob(os.path.join(
        root or HERE, "out", "trace", "*", "plugins", "profile", "*",
        "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, _mtime: float):
    t = time.perf_counter()
    reduced = reduce(*read_trace(path))
    if reduced is not None:
        print(json.dumps({"phase": "scopes", "trace": path,
                          "read_s": time.perf_counter() - t,
                          **table(reduced)}), flush=True)
    return reduced


def newest(root: Optional[str] = None) -> Optional[Dict[str, Any]]:
    """The reduction of the trace this run wrote, read once."""
    path = newest_trace(root)
    if path is None:
        return None
    return _reduce_file(path, os.path.getmtime(path))


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else newest_trace()
    print(json.dumps(table(reduce(*read_trace(which))), indent=1))
