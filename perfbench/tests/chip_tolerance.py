"""How the references' tolerances were calibrated, on the chip:

    python3 -m perfbench.tests.chip_tolerance <cell>

prints the sample check of the cell at its published widths three
times: the system as it is, with every weight matrix rounded to fp8
first, and with every norm gain doubled. A tolerance belongs between
the first line's error and the second's. Not a test and not part of a
run; it needs the cell's chips.
"""

import os
import sys

from perfbench import run
from perfbench.tests.test_reference import FAULTS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(workload: str) -> None:
    import jax
    from horovod_tpu.parallel.mesh import data_parallel_mesh

    cell = run.Cell(workload, ROOT, run.read_json(
        os.path.join(os.path.dirname(ROOT), "BENCHMARK.json")))
    devices = run.attached_chips(cell.chips)
    driver = run.load_module(ROOT, "drivers", cell.spec["driver"])
    model = run.load_module(ROOT, "models", cell.spec["model"])
    reference = run.load_module(ROOT, "reference", cell.spec["model"])
    mesh = data_parallel_mesh(devices)
    keys = jax.random.split(driver.seed_key(7))
    for fault in FAULTS:
        m = model.build(cell.config, cell.spec, cell.chips)
        if fault is not None:
            m.loss_fn = fault(m.loss_fn)
        print(getattr(fault, "__name__", "as it is"), flush=True)
        params, carry, sample = driver.weights_and_sample(
            m, mesh, *keys)
        driver.sample_check(m, reference, cell.config, mesh, params,
                            carry, sample)


if __name__ == "__main__":
    main(sys.argv[1])
