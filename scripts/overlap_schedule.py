#!/usr/bin/env python
"""What the TPU compiler makes of a cell's gradient all-reduces under a
set of compile options: no chip, nothing runs.

    python scripts/overlap_schedule.py --workload mistral7b-l4.jit-dp4 \\
        [--sets sets.json] [--threshold BYTES] [--hlo-dir DIR]

Compiles the cell's own step (`perfbench/models/<model>.py` `build` +
`build_train_step`, the cell's batch a chip) for a described `v5e:2x2`
with the installed TPU compiler, once for every option set of
`sets.json` (`{"name": {"option": "value", ...}, ...}`; without the
file: no option, and what `build_train_step` picks for the mesh), and
prints a JSON line a set:

  sync      the all-reduce instructions left synchronous, by the
            computation that holds them (`entry`, or a loop's body)
  pairs     each asynchronous start / done pair: the reduced shape,
            its bytes, the computation, and the instructions scheduled
            between start and done, counted by kind (what the
            all-reduce can run beside)
  temp_bytes, argument_bytes
            `memory_analysis()` of the compiled step: the proxy for
            `peak_hbm_gb` (the chip reads differently, but a schedule
            that needs more temporaries here needs more there)

A schedule is no time: how much a pair hides only a chip run says.
This is how ASYNC_REDUCE_OPTIONS and the memory limit beside them
(horovod_tpu/parallel/train.py) were found, and how the set is checked
against the next libtpu. Run by no
benchmark cell and no test.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from collections import Counter
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench.trace_reduce import KIND, LAYOUT, OPCODE  # noqa: E402

INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$")
LOOP_BODY = re.compile(r"body=%([\w.\-]+)")
SHAPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")
START = re.compile(r"^(async-collective-start|all-reduce-start)")
DONE = re.compile(r"^(async-collective-done|all-reduce-done)")
# moved by the compiler's own bookkeeping, not work beside a collective
QUIET = {"get-tuple-element", "bitcast", "tuple", "constant",
         "parameter", "copy-start", "copy-done", "slice-start",
         "slice-done", "reshape"}
ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s32": 4, "u32": 4,
            "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8}


def computations(hlo: str):
    """(name, [(instruction name, kind, result text)]) of the entry
    computation and of every loop body of a compiled module's text, in
    schedule order. (A fusion's own computation is no schedule: the
    all-reduce inside an asynchronous collective fusion is the pair.)"""
    scheduled = set(LOOP_BODY.findall(hlo))
    name, body = None, []
    for line in hlo.splitlines():
        if line.endswith("{") and not line.startswith((" ", "HloModule")):
            entry = line.startswith("ENTRY")
            head = line.split()[1 if entry else 0].lstrip("%")
            name = "entry" if entry else head if head in scheduled \
                else None
            body = []
        elif line.startswith("}") and name is not None:
            yield name, body
            name = None
        elif name is not None:
            found = INSTRUCTION.match(line)
            if not found:
                continue
            rest = LAYOUT.sub("", found.group(2))
            op = OPCODE.search(rest)
            if not op:
                continue
            opcode = op.group(1)
            fusion = KIND.search(line) if opcode == "fusion" else None
            kind = f"fusion {fusion.group(1)}" if fusion else opcode
            body.append((found.group(1), kind, rest[:op.start()]))


def first_shape(result: str):
    """('bf16[4,4096,4096]', bytes) of the first array in a result."""
    found = SHAPE.search(result)
    if not found:
        return result.strip(), 0
    n = 1
    for d in filter(None, found.group(2).split(",")):
        n *= int(d)
    return found.group(0), n * ITEMSIZE.get(found.group(1), 0)


def read_schedule(hlo: str):
    """The synchronous all-reduces and the start / done pairs of a
    compiled module's text."""
    sync, pairs = [], []
    for comp, body in computations(hlo):
        open_at = {}
        for i, (name, kind, result) in enumerate(body):
            if kind == "all-reduce":
                shapes = [m.group(0) for m in SHAPE.finditer(result)]
                sync.append({"in": comp, "shapes": shapes})
            elif START.match(name):
                open_at[START.sub("", name)] = i
            elif DONE.match(name):
                at = open_at.pop(DONE.sub("", name), None)
                if at is None:
                    continue
                shape, nbytes = first_shape(body[at][2])
                between = Counter(k for _, k, _ in body[at + 1:i]
                                  if k not in QUIET)
                pairs.append({"in": comp, "shape": shape,
                              "bytes": nbytes,
                              "lines_between": i - at - 1,
                              "between": dict(between.most_common())})
    return sync, pairs


def lowered_step(workload: str, threshold):
    """The cell's step, lowered for its chips of a described v5e 2x2,
    with no compile option of the library's own attached."""
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from horovod_tpu.parallel import build_train_step, train
    from perfbench import run as bench

    # a compile for a described chip cannot be read back from the
    # persistent cache: keep it off
    jax.config.update("jax_enable_compilation_cache", False)
    root = os.path.join(ROOT, "perfbench")
    cell = bench.Cell(workload, root, bench.read_json(
        os.path.join(ROOT, "BENCHMARK.json")))
    n = cell.chips
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:n]), ("data",))
    m = bench.load_module(root, "models", cell.spec["model"]).build(
        cell.config, cell.spec, n)

    def on(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    key = jax.random.PRNGKey(0)
    params, carry = jax.eval_shape(m.init, key)
    opt_state = jax.eval_shape(m.optimizer.init, params)
    batch = jax.eval_shape(
        lambda k: m.make_batch(k, cell.spec["batch_per_chip"] * n), key)
    batch = on(batch, P("data"))
    if m.carry_key:
        batch = {**batch, m.carry_key: on(carry, P())}
    def build():
        return build_train_step(
            m.loss_fn, m.optimizer, mesh, batch_spec=m.batch_spec,
            loss_has_aux=m.has_aux, donate=True,
            overlap_threshold=threshold, **m.step_kwargs)

    args = (on(params, P()), on(opt_state, P()), batch)
    shipped = build()
    rule = shipped.compiler_options(*args) \
        if hasattr(shipped, "compiler_options") else {}
    with mock.patch.object(train, "async_reduce_hbm_bytes",
                           lambda mesh: None):
        lowered = build().lower(*args)
    return lowered, rule, train.last_overlap_info()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", help="JSON file of named option sets")
    ap.add_argument("--threshold", type=int,
                    help="bucket bytes (default: the library's)")
    ap.add_argument("--hlo-dir",
                    help="write each set's compiled text here")
    args = ap.parse_args(argv)
    lowered, rule, plan = lowered_step(args.workload, args.threshold)
    if args.sets:
        with open(args.sets) as f:
            sets = json.load(f)
    else:
        sets = {"none": {}, "build_train_step": rule}
    print(json.dumps({"workload": args.workload,
                      "buckets": plan.get("buckets", 0),
                      "bucket_bytes": plan.get("bucket_bytes"),
                      "build_train_step": rule}), flush=True)
    for name, options in sets.items():
        t = time.perf_counter()
        try:
            compiled = lowered.compile(compiler_options=options or None)
        except Exception as e:
            print(json.dumps({"set": name, "error": str(e)[:300]}),
                  flush=True)
            continue
        hlo = compiled.as_text()
        if args.hlo_dir:
            os.makedirs(args.hlo_dir, exist_ok=True)
            with open(os.path.join(args.hlo_dir, name + ".txt"),
                      "w") as f:
                f.write(hlo)
        sync, pairs = read_schedule(hlo)
        mem = compiled.memory_analysis()
        print(json.dumps({
            "set": name, "options": sorted(options),
            "sync": len(sync), "pairs": len(pairs),
            "paired_bytes": sum(p["bytes"] for p in pairs),
            "temp_bytes": mem.temp_size_in_bytes,
            "argument_bytes": mem.argument_size_in_bytes,
            "compile_s": round(time.perf_counter() - t, 1),
            "sync_all_reduces": sync, "start_done_pairs": pairs}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
