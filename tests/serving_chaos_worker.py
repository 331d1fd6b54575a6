"""Serving-chaos pool member: launched per-rank by the elastic runner
(the probe-gated 2-rank chaos leg in tests/test_serving.py), it joins
the ServingFrontend living in the LAUNCHING test process over the
HMAC-signed control-plane wire and serves batches until the frontend
says stop.

Deliberately CONTROL-PLANE ONLY, like tests/journal_chaos_worker.py:
data-parallel inference runs a full forward replica per member — there
is no cross-member collective — so the whole serving lifecycle
(rendezvous, pool join, batch pull/push, the seeded mid-batch crash,
the gang restart, the rejoin) exercises on jaxlib builds whose CPU
backend cannot run cross-process collectives. The frontend outlives
the gang restart (it is not under the runner), which is exactly the
serving deployment shape: the driver-side frontend survives worker
churn and its retry accounting is what proves zero dropped requests.

Env contract (set by the test): SERVING_TEST_ADDR / SERVING_TEST_PORT
(the frontend endpoint), SERVING_TEST_SECRET (the endpoint's HMAC key
— distinct from the runner's own HOROVOD_SECRET), SERVING_TEST_DMODEL.
The seeded fault (HOROVOD_FAULTS=serving.batch:crash:...) arms from
env inside hvd.init() and fires mid-batch inside remote_worker_loop.
With SERVING_TEST_WEIGHTS_DIR set the member serves the two-arg
live-weight forward (bootstrap params deterministic from DMODEL, so
the launching frontend derives the identical tree) and hot-swaps from
that pipeline directory between pulls — a seeded
weights.adopt:crash is then a REAL process death mid-swap.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax.numpy as jnp  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu import serving  # noqa: E402

D = int(os.environ.get("SERVING_TEST_DMODEL", "8"))
WEIGHTS_DIR = os.environ.get("SERVING_TEST_WEIGHTS_DIR", "")


def forward(x):
    return jnp.tanh(x) * 2.0


def forward_weighted(params, x):
    return jnp.tanh(x @ params["w"]) + params["b"]


def bootstrap_params():
    # Deterministic in D: the launching test builds the same tree so
    # the structure digests agree across the wire.
    return {"w": jnp.eye(D), "b": jnp.zeros((D,))}


def main():
    standalone = os.environ.get("SERVING_TEST_STANDALONE") == "1"
    if standalone:
        # Plain-subprocess mode (the ungated kill test): no launcher,
        # so arm the seeded faults from env ourselves.
        from horovod_tpu import faults
        faults.configure_from_env()
        wid = os.environ.get("SERVING_TEST_WID",
                             f"pid{os.getpid()}")
    else:
        hvd.init()
        wid = f"rank{hvd.rank()}-pid{os.getpid()}"
    if WEIGHTS_DIR:
        n = serving.remote_worker_loop(
            os.environ["SERVING_TEST_ADDR"],
            int(os.environ["SERVING_TEST_PORT"]),
            forward_weighted, (D,), wid=wid,
            secret=os.environ.get("SERVING_TEST_SECRET", ""),
            params=bootstrap_params(), weights_dir=WEIGHTS_DIR)
    else:
        n = serving.remote_worker_loop(
            os.environ["SERVING_TEST_ADDR"],
            int(os.environ["SERVING_TEST_PORT"]),
            forward, (D,), wid=wid,
            secret=os.environ.get("SERVING_TEST_SECRET", ""))
    print(f"serving worker {wid}: served {n} batches", flush=True)
    if not standalone:
        hvd.shutdown()


if __name__ == "__main__":
    main()
