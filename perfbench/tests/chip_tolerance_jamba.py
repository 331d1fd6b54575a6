"""How `reference/jamba.py`'s tolerances were calibrated, on the
chip:

    python3 -m perfbench.tests.chip_tolerance_jamba <cell> [seed ...] [probe ...]

prints the sample check of the cell at its published widths and its
sample length: the system as it is at every seed given (default 7),
then at the first seed with each fault of `test_jamba.probes`: the
scan's carry from chunk to chunk dropped, delta without its softplus,
no D skip, no dt / B / C norms, a conv that reads ahead, rope on the
attention layer, the attention layer one earlier in its period, every
matrix rounded to 3 bits of mantissa. A tolerance belongs
between the largest error of the first group and the smallest of the
second. Probes named after the seeds are the only ones run. Not a test
and not part of a run; it needs the cell's chips.
"""

import os
import sys

from perfbench import run
from perfbench.tests.test_jamba import load, probes
from perfbench.tests.test_xing4 import check

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(workload: str, seeds, only) -> None:
    from horovod_tpu.common import compile_cache
    from horovod_tpu.parallel.mesh import data_parallel_mesh

    compile_cache.enable()
    cell = run.Cell(workload, ROOT, run.read_json(
        os.path.join(os.path.dirname(ROOT), "BENCHMARK.json")))
    devices = run.attached_chips(cell.chips)
    jit_train, model, reference = load()
    mesh = data_parallel_mesh(devices)
    for name, change, fault in probes(cell.config):
        if only and name.replace(" ", "_") not in only:
            continue
        for seed in seeds if name == "as it is" else seeds[:1]:
            print(name, "seed", seed, flush=True)
            check(jit_train, model, reference, cell.config, cell.spec, mesh,
                  seed, change, fault)


if __name__ == "__main__":
    main(sys.argv[1], [int(s) for s in sys.argv[2:] if s.isdigit()] or [7],
         [s for s in sys.argv[2:] if not s.isdigit()])
