"""2-rank numerics chaos worker: a fixed-seed eager training loop
under the coordinated skip-step guard. The test arms
HOROVOD_FAULTS="numerics.grad:nan:at=N,rank=1" so ONE rank sees ONE
NaN gradient pre-reduction; the finite-flag riding the fused allreduce
must turn it into the SAME single skip on every rank, leaving
post-run parameters bitwise identical everywhere. Each rank asserts
its own skip counter and the cross-rank digest agreement, then prints
a line the test greps."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu import numerics  # noqa: E402

STEPS = int(os.environ.get("NUMERICS_TEST_STEPS", "6"))
EXPECT_SKIPS = int(os.environ.get("NUMERICS_TEST_EXPECT_SKIPS", "1"))


def main():
    hvd.init()
    assert numerics.guard_enabled(), \
        "worker must be launched with HOROVOD_NUMERICS_GUARD=1"
    opt = hvd.DistributedOptimizer(
        numerics.guard_non_finite(optax.sgd(0.1)))
    params = {"w": jnp.arange(4.0, dtype=jnp.float32)}
    opt_state = opt.init(params)

    for step in range(STEPS):
        # Deterministic, rank-INDEPENDENT gradients (of
        # 0.5*||w - t||^2), so replicas only stay bitwise identical if
        # the injected rank-local NaN skips on EVERY rank.
        target = jnp.full(4, float(step + 1), jnp.float32)
        grads = {"w": params["w"] - target}
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        assert bool(numerics.all_finite(params)), \
            f"params poisoned at step {step}"

    snap = hvd.metrics()
    skipped = int(sum(
        (snap.get("hvd_skipped_steps_total") or {}).values()))
    assert skipped == EXPECT_SKIPS, (skipped, EXPECT_SKIPS)
    assert numerics.consecutive_skips(opt_state) == 0

    digest = numerics.params_digest(params)
    digests = hvd.allgather_object(digest, name="final.digest")
    assert len(set(digests)) == 1, \
        f"replicas diverged: {[hex(d) for d in digests]}"

    # sanity: the run actually trained (a skip-everything run would
    # leave w at its init)
    assert not np.allclose(np.asarray(params["w"]), np.arange(4.0))

    print(f"numerics ok rank {hvd.rank()} skips {skipped} "
          f"digest {digest:#018x}", flush=True)
    hvd.shutdown()


if __name__ == "__main__":
    main()
