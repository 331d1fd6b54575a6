"""From a profiler trace (`.xplane.pb`) to numbers.

    python3 -m perfbench.trace_reduce <file.xplane.pb> [chips]

prints what the file holds (planes, lines, the commonest event names)
and the reduction, which is how to look at a trace by hand.

What is read, on the installed JAX with a TPU v5e (looked at by hand
in PR 26): each chip is a plane `/device:TPU:<n>`. Its line `XLA Ops`
holds one event for every executed HLO instruction, named by the
instruction's whole text; an instruction inside a `while` is nested in
the loop's own event, so time by operation is self time. The host's
threads are lines of the plane `/host:CPU`, where the driver's
`jax.profiler.TraceAnnotation` spans (`perfbench.*`) land on the same
clock. The window is from the start of the first `perfbench.step` span
to the end of the last; device time is taken on the first chip. Times
are whole picoseconds and every number is an exact sum of them, so the
same file always reduces to the same numbers.
"""

from __future__ import annotations

import glob
import os
import re
import sys
from collections import defaultdict
from typing import Any, Dict, List, Sequence, Tuple

Interval = Tuple[int, int]          # start, end in ps
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"     # start -> done of asynchronous instructions
HOST_SPAN = "perfbench."
STEP_SPAN = "perfbench.step"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)")
LAYOUT = re.compile(r"\{[^}]*\}")
OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
KIND = re.compile(r"kind=(\w+)")
TOP_OPS, TOP_KINDS, TOP_GAPS = 5, 5, 3
PS = 1e12


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_events(path: str) -> Dict[str, Dict[str, List[Tuple[str, int, int]]]]:
    """plane -> line -> [(name, start_ps, end_ps)] of a trace file."""
    from jax.profiler import ProfileData
    planes: Dict[str, Dict[str, list]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, round(e.start_ns * 1000),
                 round((e.start_ns + e.duration_ns) * 1000))
                for e in line.events)
    return planes


def union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def length(intervals: Sequence[Interval]) -> int:
    return sum(end - start for start, end in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """The parts of the merged intervals `a` that no interval of the
    merged `b` covers."""
    out: List[Interval] = []
    j = 0
    for start, end in a:
        at = start
        while j < len(b) and b[j][1] <= at:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if at < end:
            out.append((at, end))
    return out


def clip(events, window: Interval):
    lo, hi = window
    return [(name, max(s, lo), min(e, hi)) for name, s, e in events
            if e > lo and s < hi]


def instruction(text: str) -> Tuple[str, str, str]:
    """(name, kind, label) of an event of the `XLA Ops` line, whose
    name is `%name = result opcode(operands), attributes`. The kind is
    the opcode, for a fusion with its `kind=`; the label says enough to
    find the instruction in the program's HLO."""
    head, _, rest = text.partition(" = ")
    name = head.lstrip("%")
    found = OPCODE.search(LAYOUT.sub("", rest))
    if not found:
        return name, name.split(".")[0], name
    opcode = found.group(1)
    result = LAYOUT.sub("", rest)[:found.start()]
    fusion = KIND.search(rest) if opcode == "fusion" else None
    kind = f"fusion {fusion.group(1)}" if fusion else opcode
    if len(result) > 72:
        result = result[:69] + "..."
    return name, kind, f"{name} = {result} {kind}"


def self_times(ops) -> Dict[str, int]:
    """Time by event name with the time of nested events taken out of
    the event that holds them."""
    table: Dict[str, int] = defaultdict(int)
    open_events: List[Tuple[int, str]] = []      # end, name
    for name, start, end in sorted(ops, key=lambda o: (o[1], -o[2], o[0])):
        while open_events and open_events[-1][0] <= start:
            open_events.pop()
        if open_events:
            table[open_events[-1][1]] -= end - start
        table[name] += end - start
        open_events.append((end, name))
    return table


def reduce(planes, n_chips: int) -> Dict[str, Any]:
    """The trace's numbers; see the module's docstring."""
    host = [ev for lines in planes.values() for events in lines.values()
            for ev in events if ev[0].startswith(HOST_SPAN)]
    steps = sorted((s, e) for name, s, e in host if name == STEP_SPAN)
    if not steps:
        raise ValueError("the trace holds no perfbench.step span")
    window = (steps[0][0], steps[-1][1])
    chips = sorted((int(DEVICE_PLANE.match(name).group(1)), name)
                   for name in planes if DEVICE_PLANE.match(name))
    if len(chips) != n_chips:
        raise ValueError(f"the trace holds {len(chips)} device planes "
                         f"for {n_chips} chip(s): {sorted(planes)}")
    busy_ps = [length(union([(s, e) for _, s, e in clip(
        planes[name].get(OPS_LINE, []), window)])) for _, name in chips]
    if not min(busy_ps) > 0:
        raise ValueError("a chip ran no operation inside the window")

    ops = clip(planes[chips[0][1]][OPS_LINE], window)
    # A collective is on the chip's timeline while its instruction
    # runs, and between an asynchronous one's start and its done. A
    # loop's own event spans its body, so only instructions that hold
    # no other count as computing beside it.
    in_flight = clip(planes[chips[0][1]].get(ASYNC_LINE, []), window)
    parsed = {text: instruction(text)
              for text in {text for text, _, _ in ops + in_flight}}
    is_collective = {text: bool(COLLECTIVE.match(kind))
                     for text, (_, kind, _) in parsed.items()}
    busy = union([(s, e) for _, s, e in ops])
    collective = union([(s, e) for text, s, e in ops + in_flight
                        if is_collective[text]])
    compute = union([(s, e) for text, s, e in _leaves(ops)
                     if not is_collective[text]])
    by_op: Dict[str, int] = defaultdict(int)
    by_kind: Dict[str, int] = defaultdict(int)
    for text, ps in self_times(ops).items():
        _, kind, label = parsed[text]
        by_op[label] += ps
        by_kind[f"all of kind {kind}"] += ps
    collective_ops = {parsed[text][0] for text, _, _ in ops
                      if is_collective[text]}

    gaps = subtract([window], busy)
    spans = sorted((s, e, name) for name, s, e in host
                   if name != STEP_SPAN)
    longest = sorted(((e - s, _cause((s, e), spans)) for s, e in gaps),
                     key=lambda g: (-g[0], g[1]))[:TOP_GAPS]
    by_cause = {"all gaps outside any perfbench span": length(gaps)}
    for s, e, name in spans:        # the spans follow one another
        ps = length(subtract(gaps, subtract(gaps, [(s, e)])))
        by_cause[f"all gaps during {name}"] = \
            by_cause.get(f"all gaps during {name}", 0) + ps
        by_cause["all gaps outside any perfbench span"] -= ps
    busy_total = length(busy)

    def top(table, n, share_of=None):
        rows = sorted(table.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
        return [[name if share_of is None else
                 f"{name} ({100 * ps / share_of:.1f}% of busy)", ps / PS]
                for name, ps in rows]

    return {
        "steps": len(steps),
        "window_s": (window[1] - window[0]) / PS,
        "busy_s": sum(busy_ps) / len(busy_ps) / PS,
        "busy_first_chip_s": busy_total / PS,
        "collective_s": length(collective) / PS,
        "collective_exposed_s":
            length(subtract(collective, compute)) / PS,
        "collective_ops": sorted(collective_ops),
        "device_ops": top(by_op, TOP_OPS, busy_total) +
            top(by_kind, TOP_KINDS, busy_total),
        "idle_gaps": top(by_cause, len(by_cause)) + [
            [f"longest gap {i + 1}, mostly during {cause}", ps / PS]
            for i, (ps, cause) in enumerate(longest)],
    }


def _leaves(ops):
    """The events that hold no other event."""
    out, ordered = [], sorted(ops, key=lambda o: (o[1], -o[2], o[0]))
    for i, op in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is None or nxt[2] > op[2]:
            out.append(op)
    return out


def _cause(gap: Interval, spans) -> str:
    """What the host was doing for most of an idle gap: the
    `perfbench.*` span that overlaps it longest (the earlier one on a
    tie)."""
    best, best_ps = "no perfbench span", 0
    for s, e, name in spans:
        ps = min(e, gap[1]) - max(s, gap[0])
        if ps > best_ps:
            best, best_ps = name, ps
    return best


def reduce_file(path: str, n_chips: int) -> Dict[str, Any]:
    return reduce(read_events(path), n_chips)


def describe(planes) -> str:
    out = []
    for plane, lines in planes.items():
        out.append(f"plane {plane!r}")
        for line, events in lines.items():
            names: Dict[str, int] = defaultdict(int)
            for name, s, e in events:
                names[name] += e - s
            common = sorted(names.items(), key=lambda kv: -kv[1])[:12]
            out.append(f"  line {line!r}: {len(events)} events; " +
                       ", ".join(f"{n[:60]} {ps / 1e9:.3f}ms"
                                 for n, ps in common))
    return "\n".join(out)


if __name__ == "__main__":
    import json
    events = read_events(sys.argv[1])
    print(describe(events))
    print(json.dumps(reduce(events, int(sys.argv[2])
                            if len(sys.argv) > 2 else 1), indent=1))
