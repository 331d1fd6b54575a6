"""How `reference/mellum.py`'s tolerances were calibrated, on the chip:

    python3 -m perfbench.tests.chip_tolerance_mellum <cell> [seed ...] [probe ...]

prints the sample check of the cell at its published widths and its
sample length: the system as it is at every seed given (default 7),
then at the first seed with each of the six faults of
`test_mellum.PROBES`: the windowed layers run causal without the
window, YaRN's attention factor dropped, a plain rope on the full
layers, the gates not renormalised, a sigmoid router in place of the
softmax, every matrix rounded to 3 bits of mantissa. A tolerance
belongs between the largest error of the first group and the smallest
of the second. Probes named after the seeds are the only ones run.
Not a test and not part of a run; it needs the cell's chips.
"""

import os
import sys

from perfbench import run
from perfbench.tests.test_mellum import PROBES, check_probe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(workload: str, seeds, only) -> None:
    from horovod_tpu.common import compile_cache
    from horovod_tpu.parallel.mesh import data_parallel_mesh

    compile_cache.enable()
    cell = run.Cell(workload, ROOT, run.read_json(
        os.path.join(os.path.dirname(ROOT), "BENCHMARK.json")))
    devices = run.attached_chips(cell.chips)
    driver = run.load_module(ROOT, "drivers", cell.spec["driver"])
    model = run.load_module(ROOT, "models", cell.spec["model"])
    reference = run.load_module(ROOT, "reference", cell.spec["model"])
    mesh = data_parallel_mesh(devices)
    for name, change, fault in PROBES:
        if only and name.replace(" ", "_") not in only:
            continue
        for seed in seeds if name == "as it is" else seeds[:1]:
            print(name, "seed", seed, flush=True)
            check_probe(driver, model, reference, cell.config, cell.spec,
                        mesh, seed, change, fault)


if __name__ == "__main__":
    main(sys.argv[1], [int(s) for s in sys.argv[2:] if s.isdigit()] or [7],
         [s for s in sys.argv[2:] if not s.isdigit()])
