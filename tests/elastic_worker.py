"""Elastic training worker for integration tests: a toy training loop
under hvd.elastic.run that logs (epoch-world-size, step) progress to a
file per rank, commits every step, and exits after N total steps
(reference: the elastic integration scripts in test/integration/
elastic_common.py — progress-logging training driven by a rewritable
discovery script)."""

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import horovod_tpu as hvd  # noqa: E402

LOG = os.environ["ELASTIC_TEST_LOG"]
TOTAL_STEPS = int(os.environ.get("ELASTIC_TEST_STEPS", "40"))
STEP_SLEEP = float(os.environ.get("ELASTIC_TEST_SLEEP", "0.2"))


def log_line(msg):
    with open(f"{LOG}.{os.environ.get('HOROVOD_RANK', '?')}", "a") as f:
        f.write(msg + "\n")


DIE_AT = int(os.environ.get("ELASTIC_TEST_DIE_AT", "0"))


def main():
    hvd.init()
    state = hvd.elastic.JaxState(
        params={"w": jnp.zeros((2,))}, step=0,
        snapshot_path=f"{LOG}_snapshot.bin")

    # ELASTIC_TEST_WIDE=1: every step ALSO runs a bucket big enough
    # for the device-spanning ('proc','dev') path and asserts it
    # engaged with the CURRENT world size — resizes must rebuild the
    # wide mesh, not reuse a stale pre-resize one (the caches live on
    # ProcessSet instances, which re-init replaces).
    wide = os.environ.get("ELASTIC_TEST_WIDE") == "1"

    @hvd.elastic.run
    def train(state):
        while state.step < TOTAL_STEPS:
            # one "training step": an allreduce so failures/resizes
            # surface as collective errors
            g = hvd.allreduce(jnp.ones((2,)) * (state.step + 1),
                              name="grad")
            if wide:
                import jax
                from horovod_tpu.ops import dispatch
                big = hvd.allreduce(jnp.full((4096,), 1.0), name="big",
                                    op=hvd.Sum)
                np.testing.assert_allclose(
                    np.asarray(big), np.full(4096, float(hvd.size())))
                info = dispatch.last_allreduce_info()
                ndev = len(jax.local_devices())
                if hvd.size() > 1 and ndev > 1:
                    assert info.get("path") == "wide", info
                    assert info.get("mesh_shape") == {
                        "proc": hvd.size(), "dev": ndev}, (
                        info, hvd.size())
                    log_line(f"wide ok world {hvd.size()} "
                             f"devs {info['devices']}")
            state.params["w"] = state.params["w"] + np.asarray(g)
            state.step += 1
            log_line(f"step {state.step} world {hvd.size()} "
                     f"rank {hvd.rank()}")
            # failure injection (once): rank 1 dies hard at DIE_AT
            marker = f"{LOG}_died.marker"
            if (DIE_AT and state.step == DIE_AT and hvd.rank() == 1
                    and not os.path.exists(marker)):
                with open(marker, "w") as f:
                    f.write("died\n")
                os._exit(17)
            state.check_host_updates()
            state.commit()
            time.sleep(STEP_SLEEP)

    train(state)
    log_line(f"done world {hvd.size()} rank {hvd.rank()} "
             f"w0 {float(state.params['w'][0]):.1f}")
    # Chaos-test accounting: how many injected faults THIS incarnation
    # fired and how many elastic resets it survived (processes killed
    # mid-schedule obviously don't reach this line — their fires show
    # up in the driver-captured "faults: firing" log lines instead).
    snap = hvd.metrics()
    fired = sum((snap.get("hvd_faults_fired_total") or {}).values())
    resets = (snap.get("hvd_elastic_resets_total") or {}).get((), 0)
    log_line(f"stats rank {hvd.rank()} faults {int(fired)} "
             f"resets {int(resets)}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
