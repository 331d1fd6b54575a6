#!/usr/bin/env python
"""Where one benchmark cell's set-up went, from the program's own
spans and counters (PERF.md section 5's set-up tables are made here).

    python3 scripts/setup_table.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

runs the cell through `perfbench.run.run_cell` (the chip required),
prints one `{"phase": "setup_table"}` line read from
`horovod_tpu.metrics.snapshot()` and the ring, and the run's result
line last. An untraced run's line holds `setup_s`, so the table then
says what of it no span or counter holds.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from perfbench import run  # noqa: E402 — first: set-up counts from here


def table(snap, events, setup_s=None) -> dict:
    """Seconds by span, by program and phase, and cache requests."""
    spans = {k[0]: v for k, v in
             snap.get("hvd_host_span_seconds_total", {}).items()}
    by_program: dict = {}
    for (phase, program), secs in snap.get(
            "hvd_jit_seconds_total", {}).items():
        by_program.setdefault(program, {})[phase] = secs
    for (program,), n in snap.get("hvd_jit_programs_total", {}).items():
        by_program.setdefault(program, {})["programs"] = n
    phases = {p: sum(row.get(p, 0.0) for row in by_program.values())
              for p in ("trace", "lower", "backend")}
    out = {
        "pre_init_s": snap.get("hvd_init_started_after_seconds",
                               {}).get(()),
        "spans_s": spans,
        "jit_s": phases,
        "by_program": by_program,
        "cache_requests": {k[0]: v for k, v in snap.get(
            "hvd_compile_cache_requests_total", {}).items()},
        "ring_span_entries": sum(e[1] in ("span_begin", "span_end")
                                 for e in events),
    }
    if setup_s is not None and out["pre_init_s"] is not None:
        counted = (out["pre_init_s"] + spans.get("init", 0.0)
                   + sum(phases.values()))
        out.update(setup_s=setup_s, counted_s=counted,
                   uncounted_s=setup_s - counted)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    result = run.run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    from horovod_tpu import tracing
    from horovod_tpu.metrics import snapshot
    setup = result["metrics"].get("setup_s", {}).get("value")
    print(json.dumps({"phase": "setup_table", "cell": args.workload,
                      **table(snapshot(), tracing.ring_events(), setup)}),
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
