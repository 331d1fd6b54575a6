"""Worker for the 2-rank PowerSGD crash/restore test: eager-plane
DistributedGradientTransformation with Compression.powersgd — the warm
Q factors and the error-feedback residual live INSIDE the optax state,
so the ordinary elastic `JaxState(params, opt_state)` commit carries
them with zero extra plumbing. Three phases via
COMPRESSION_WORKER_PHASE:

  ref — 6 uninterrupted steps, record {loss, residual_norm}
  a   — 3 steps, commit through JaxState's pickle snapshot, hard-exit
        mid-"step 4" (os._exit: no atexit, no shutdown — the crash)
  b   — restore the commit, run the remaining 3 steps, record the
        same probe; the test pins resumed == ref

Per-rank batches differ (the reduction is load-bearing), parameters
stay replicated, and every step's reduced gradient is identical across
ranks — so both ranks can restore the shared snapshot file directly
(same machine in this harness; the driver's sync() broadcast covers
the multi-host case)."""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.elastic.state import JaxState  # noqa: E402
from horovod_tpu.ops.compression import Compression  # noqa: E402
from horovod_tpu.optim.distributed_optimizer import (  # noqa: E402
    DistributedGradientTransformation)


def loss_fn(params, batch):
    h = jnp.tanh(batch[:, None] * params["w1"][None, :])
    return jnp.mean((h @ params["w2"]) ** 2) + jnp.mean(
        params["b"] ** 2)


def init_params():
    # w2 (32x16 f32, 512 elements) is the powersgd-eligible leaf at
    # min_elements=256; w1/b bypass to the exact grouped path.
    return {"w1": jnp.arange(32.0) / 32.0,
            "w2": jnp.ones((32, 16)) * 0.1
            + jnp.arange(32.0 * 16).reshape(32, 16) * 1e-3,
            "b": jnp.zeros(3)}


def main():
    phase = os.environ["COMPRESSION_WORKER_PHASE"]
    outdir = os.environ["COMPRESSION_WORKER_DIR"]
    snap = os.path.join(outdir, "snap.pkl")

    hvd.init()
    r = hvd.rank()
    assert hvd.size() == 2

    opt = DistributedGradientTransformation(
        optax.adam(0.05),
        compression=Compression.powersgd(rank=2, min_elements=256,
                                         warmup_steps=0))
    params = init_params()
    opt_state = opt.init(params)
    assert opt_state.q and opt_state.e, "powersgd leaf not eligible?"
    batch = jnp.arange(8.0) + 8.0 * r  # per-rank shard
    probe = jnp.arange(8.0) * 0.5     # fixed, rank-independent

    def step(params, opt_state):
        grads = jax.grad(loss_fn)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    def run(params, opt_state, n):
        for _ in range(n):
            params, opt_state = step(params, opt_state)
        return params, opt_state

    state = JaxState(params=params, opt_state=opt_state,
                     snapshot_path=snap, snapshot_backend="pickle",
                     step=0)
    resumed = state.maybe_load_snapshot()

    if phase == "ref":
        assert not resumed
        params, opt_state = run(params, opt_state, 6)
    elif phase == "a":
        assert not resumed
        params, opt_state = run(params, opt_state, 3)
        state.params, state.opt_state, state.step = params, \
            opt_state, 3
        state.save()  # the commit (rank 0 writes the snapshot)
        hvd.barrier()  # both ranks see the durable commit
        print("COMPRESSION WORKER COMMITTED rank=%d step=3" % r,
              flush=True)
        sys.stdout.flush()
        os._exit(1)   # the crash: mid-"step 4", no shutdown
    elif phase == "b":
        assert resumed, "phase b found no snapshot to restore"
        assert int(state.step) == 3
        params, opt_state = state.params, state.opt_state
        # the residual survived the crash — it is gradient signal
        res0 = float(np.sqrt(sum(
            float((np.asarray(e, np.float64) ** 2).sum())
            for e in opt_state.e.values())))
        assert res0 > 0, "restored residual is zero"
        params, opt_state = run(params, opt_state, 3)
    else:
        raise SystemExit(f"unknown phase {phase!r}")

    res_norm = float(np.sqrt(sum(
        float((np.asarray(e, np.float64) ** 2).sum())
        for e in opt_state.e.values())))
    doc = {"loss": float(loss_fn(params, probe)),
           "residual_norm": res_norm,
           "powersgd_step": int(opt_state.step)}
    if r == 0:
        name = "ref.json" if phase == "ref" else "resumed.json"
        with open(os.path.join(outdir, name), "w") as f:
            json.dump(doc, f)
    hvd.barrier()
    hvd.shutdown()
    print(f"COMPRESSION WORKER OK rank={r} phase={phase} "
          f"loss={doc['loss']:.6f} residual={res_norm:.4f}",
          flush=True)


main()
