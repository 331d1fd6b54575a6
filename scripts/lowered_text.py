#!/usr/bin/env python
"""A cell's training step as lowered for a described v5e, with every
Pallas kernel's Mosaic payload printed as MLIR text without source
locations: no chip, nothing compiles or runs.

    python scripts/lowered_text.py --workload trinity-large-ep32tp4.jit-dp1 \\
        [--root CHECKOUT] > step.txt

A payload is MLIR bytecode that carries the file, line, column and
function of up to ten caller frames of each kernel operation, so a
line moved on a kernel's call path, or the checkout's path, changes
the raw lowered text and nothing the chip runs. Two checkouts whose
outputs are equal lower the same step: that is how a change to shared
model code shows that it leaves another cell's program alone. Run each
cell in a process of its own (the Pallas lowering reuses a payload
across cells of one process) and with one `PYTHONHASHSEED` for both
sides (a step built by iterating a set follows it).
"""

from __future__ import annotations

import argparse
import base64
import os
import re
import sys
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PAYLOAD = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def without_locations(text: str) -> str:
    """`text` with each Mosaic payload decoded and printed without
    debug information, on one line."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    context = mlir.make_ir_context()
    tpu.register_dialect(context)
    # the serialised payload names the versioned `stable_mosaic` dialect
    context.allow_unregistered_dialects = True
    printed = {}

    def plain(match):
        body = match.group(1)
        if body not in printed:
            with context:
                module = ir.Module.parse(base64.b64decode(body))
                printed[body] = module.operation.get_asm(
                    enable_debug_info=False).replace("\n", "\\0A")
        return "\\22body\\22: <" + printed[body] + ">"
    return PAYLOAD.sub(plain, text)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--root", default=HERE,
                    help="the checkout whose step is lowered")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    sys.path[:0] = [root, os.path.join(root, "scripts")]
    os.chdir(root)
    import jax
    import overlap_schedule
    overlap_schedule.ROOT = root
    # both kernel families engage where the backend reads "tpu"
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered, _, _ = overlap_schedule.lowered_step(args.workload, None)
        text = lowered.as_text()
    sys.stdout.write(without_locations(text))
    return 0


if __name__ == "__main__":
    sys.exit(main())
