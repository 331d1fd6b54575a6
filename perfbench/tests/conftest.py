"""CPU rehearsals of the benchmark: `pytest perfbench/tests -q`, by
hand (the repo's tier-1 command collects `tests/` only). Four virtual
CPU devices, so the four-chip cell's path is rehearsed too."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
