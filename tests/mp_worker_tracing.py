"""Worker for the 2-rank distributed-tracing integration test: runs
named negotiated allreduces with HOROVOD_TIMELINE set (every rank
writes a per-rank trace on a monotonic anchor, the rank named by
TRACING_WORKER_SLOW_RANK sleeps before each submit), then asserts its own
per-rank trace file exists. The test process merges the files
afterwards and checks the straggler report names rank 1."""

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu import tracing  # noqa: E402
from horovod_tpu.timeline import Timeline  # noqa: E402


def main():
    hvd.init()
    r, n = hvd.rank(), hvd.size()
    assert n == 2, n

    slow = int(os.environ.get("TRACING_WORKER_SLOW_RANK", "-1"))
    for step in range(6):
        tracing.set_step(step)
        if r == slow:
            time.sleep(0.15)
        out = hvd.allreduce(jnp.ones(256, jnp.float32), op=hvd.Sum,
                            name=f"grads_{step}")
        np.testing.assert_allclose(np.asarray(out), float(n))
    hvd.barrier()

    # Every rank records: rank 0 at the configured path, rank 1 at
    # the .rank1 sibling the merge step discovers.
    path = Timeline.rank_path(os.environ["HOROVOD_TIMELINE"], r)
    assert os.path.exists(path), path

    # The runtime skew histogram saw the same lateness the offline
    # report attributes: the NON-delayed rank (rank 0) arrives early
    # and waits, so its own lateness stays small; the delayed rank
    # observes its arrival delta behind rank 0.
    digest = tracing.trace_digest()
    assert digest["spans"].get("submit", {}).get("count", 0) >= 6
    hvd.shutdown()
    print("TRACING WORKER OK", flush=True)


main()
