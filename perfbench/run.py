"""One run of one benchmark cell.

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout. Everything that belongs to one cell,
configuration, driver, model family or per-layer metric is a file of
its own, found by the name `BENCHMARK.json` or the cell's file gives
(perfbench/README.md); this module knows none of them. The last line
of standard output is the result object the driver reads.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()  # set-up is counted from here

import argparse
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))


class Cell:
    """One entry of the manifest's `workloads` with the files it names."""

    def __init__(self, name: str, root: str, manifest: Dict[str, Any]):
        entry = next((w for w in manifest["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise SystemExit(f"perfbench: no workload {name!r} in the "
                             "manifest")
        self.name, self.root = name, root
        self.chips = int(entry["chips"])
        self.spec = read_json(os.path.join(root, "workloads",
                                           name + ".json"))
        self.config = read_json(os.path.join(
            root, "configs", entry["config"] + ".json"))
        self.end_to_end = self._metrics(manifest["end_to_end"])
        self.per_layer = self._metrics(manifest["per_layer"])

    def _metrics(self, entries) -> Dict[str, str]:
        """name -> unit of the metrics this cell reports."""
        return {m["name"]: m["unit"] for m in entries
                if self.name in m.get("workloads", [self.name])}


def read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(root: str, kind: str, name: str):
    """The module `<root>/<kind>/<name>.py`. Loaded by path, so a
    metric's name may hold a dot and a test may point `root` at a
    directory of its own."""
    path = os.path.join(root, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name}".replace(".", "_"), path)
    if spec is None or not os.path.exists(path):
        raise SystemExit(f"perfbench: {path} does not exist")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def attached_chips(chips: int) -> List[Any]:
    """The TPU devices of this machine, which must number `chips`.
    There is no CPU fallback: a number from a CPU run is no device
    metric."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != chips:
        raise SystemExit(
            f"perfbench: the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} device(s) of platform "
            f"{devices[0].platform!r}. There is no CPU fallback.")
    return devices


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: str = HERE, manifest_path: Optional[str] = None,
             devices: Optional[Sequence[Any]] = None) -> Dict[str, Any]:
    """Run one cell and return the result object. `root`,
    `manifest_path` and `devices` are for the tests: the command line
    always reads this checkout and demands the cell's TPU chips."""
    manifest = read_json(manifest_path or os.path.join(
        os.path.dirname(HERE), "BENCHMARK.json"))
    cell = Cell(workload, root, manifest)
    if devices is None:
        devices = attached_chips(cell.chips)
    driver = load_module(root, "drivers", cell.spec["driver"])
    model = load_module(root, "models", cell.spec["model"])
    reference = load_module(root, "reference", cell.spec["model"])
    run = driver.run(cell=cell, model=model, reference=reference,
                     devices=list(devices), seed=seed, seconds=seconds,
                     trace=trace, process_start=PROCESS_START,
                     out_dir=os.path.join(root, "out"))

    if trace:
        wanted, found = cell.per_layer, {}
        for name in wanted:
            value = load_module(root, "layer_metrics", name).compute(
                run.context)
            if value is not None:   # nothing to read: left out
                found[name] = value
    else:
        wanted, found = cell.end_to_end, run.end_to_end
        missing = sorted(set(wanted) - set(found))
        if missing:
            raise SystemExit(f"perfbench: driver {cell.spec['driver']!r} "
                             f"reported no {missing}")
    result = {
        "correct": run.correct, "attempted": run.attempted,
        "failed": run.failed,
        "metrics": {n: {"value": found[n], "unit": wanted[n]}
                    for n in wanted if n in found},
        "device": run.device,
    }
    if trace and run.breakdown:
        result["breakdown"] = run.breakdown
    return result


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
