"""Journal-chaos elastic worker: the seeded soak behind
benchmarks/INCIDENT_chaos_r11.json.

Like tests/elastic_worker.py but deliberately CONTROL-PLANE ONLY: the
state broadcast is an identity function and no data-plane collective
runs, so the full elastic lifecycle (rendezvous, heartbeats, commit
snapshots, gang restarts, the journal) exercises on jaxlib builds
whose CPU backend cannot run cross-process collectives — the exact
container the committed incident artifact is generated in. The
committed-step watermark still measures real recovery semantics:
rank 0's pickle snapshot is the durable commit, and the journal's
durable-commit events are what `doctor incident` accounts loss
against.
"""

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import horovod_tpu as hvd  # noqa: E402

LOG = os.environ.get("ELASTIC_TEST_LOG", "/tmp/journal_chaos")
TOTAL_STEPS = int(os.environ.get("ELASTIC_TEST_STEPS", "18"))
STEP_SLEEP = float(os.environ.get("ELASTIC_TEST_SLEEP", "0.2"))


def log_line(msg):
    with open(f"{LOG}.{os.environ.get('HOROVOD_RANK', '?')}", "a") as f:
        f.write(msg + "\n")


# File-based lockstep pacing: with no data-plane collective to gate
# on, a healthy rank would race arbitrarily far ahead of a crashed or
# hung peer (and rank 0 could even finish the job while the peer is
# parked, turning the hang into a clean completion instead of a
# detected recovery). Each rank publishes its committed step; nobody
# starts step N+1 until every peer has committed N — the same
# lockstep a real allreduce enforces, built from the shared
# filesystem this single-host soak runs on.

def _publish_step(rank, step):
    tmp = f"{LOG}.pace.{rank}.tmp"
    with open(tmp, "w") as f:
        f.write(str(step))
    os.replace(tmp, f"{LOG}.pace.{rank}")


def _peer_floor(world, me):
    floor = None
    for r in range(world):
        if r == me:
            continue
        try:
            with open(f"{LOG}.pace.{r}") as f:
                v = int(f.read().strip() or "0")
        except (OSError, ValueError):
            v = 0
        floor = v if floor is None else min(floor, v)
    return floor if floor is not None else 1 << 30


def _pace_wait(state):
    me, world = hvd.rank(), hvd.size()
    while _peer_floor(world, me) < int(state.step) - 1:
        time.sleep(0.05)


def main():
    hvd.init()
    # params=None keeps JaxState.sync off the data-plane broadcast;
    # the weights live as a plain ObjectState attr and the identity
    # bcast_object keeps sync() collective-free (see docstring).
    state = hvd.elastic.JaxState(
        params=None, step=0, w=np.zeros((2,)),
        snapshot_path=f"{LOG}_snapshot.bin",
        snapshot_backend="pickle",
        bcast_object=lambda obj, root_rank=0: obj)

    @hvd.elastic.run
    def train(state):
        # (Re)entering the loop — fresh spawn, gang restart, or
        # resize — republish this rank's position first: a rank that
        # sat out a partial-world period (whole-slice blacklist)
        # otherwise leaves a stale pace file every peer would wait on
        # forever once it rejoins.
        _publish_step(hvd.rank(), int(state.step))
        while state.step < TOTAL_STEPS:
            _pace_wait(state)
            # one "training step": local-only compute (no cross-
            # process collective — see module docstring)
            state.w = state.w + 1.0
            state.step += 1
            log_line(f"step {state.step} world {hvd.size()} "
                     f"rank {hvd.rank()}")
            state.check_host_updates()
            state.commit()
            _publish_step(hvd.rank(), int(state.step))
            time.sleep(STEP_SLEEP)

    train(state)
    log_line(f"done world {hvd.size()} rank {hvd.rank()} "
             f"step {int(state.step)}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
