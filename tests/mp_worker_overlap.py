"""Worker for the 2-rank jit-overlap merged-timeline test: builds the
bucketed train step over a mesh spanning BOTH processes' devices with
a tracing.OverlapProbe attached, runs one unrecorded compile step
(compile cycles excluded from the artifact), then records a few
measured steps — per-bucket REDUCE spans land on this rank's timeline
lanes inside STEP envelopes, merged afterwards by the test with
tracing.merge into the cross-rank artifact."""

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# OVERLAP_WORKER_LOCAL_MESH=1: each rank runs the bucketed step over
# its OWN 8-virtual-device mesh instead of the cross-process global
# mesh — for jaxlibs whose CPU backend cannot run multiprocess
# computations (the data plane of the global mesh). Everything else —
# two real processes, per-rank timelines, control-plane clock
# calibration, the merge — is the real path; the committed
# benchmarks/TIMELINE_overlap_2proc_r06.json artifact records which
# mode produced it.
_LOCAL_MESH = os.environ.get("OVERLAP_WORKER_LOCAL_MESH") == "1"
if _LOCAL_MESH:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device"
                                 "_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu import tracing  # noqa: E402
from horovod_tpu.common.basics import state  # noqa: E402
from horovod_tpu.parallel import build_train_step  # noqa: E402
from horovod_tpu.parallel.mesh import data_parallel_mesh  # noqa: E402
from horovod_tpu.parallel.train import last_overlap_info  # noqa: E402
from horovod_tpu.timeline import Timeline  # noqa: E402


def main():
    hvd.init()
    r, n = hvd.rank(), hvd.size()
    assert n == 2, n
    if _LOCAL_MESH:
        mesh = data_parallel_mesh(jax.local_devices())
        assert mesh.devices.size == 8, mesh
    else:
        mesh = data_parallel_mesh()
        assert mesh.devices.size == 2, mesh

    def loss_fn(params, batch):
        h = jnp.tanh(batch[:, None] * params["w1"][None, :])
        h = h @ params["w2"]
        return jnp.mean((h * params["w3"][None, :]) ** 2)

    params = {"w1": jnp.arange(64.0) / 64.0,
              "w2": jnp.ones((64, 32)) * 0.1,
              "w3": jnp.ones(32)}
    opt = optax.sgd(0.01)
    opt_state = opt.init(params)

    probe = tracing.OverlapProbe()
    # Threshold sized so w2 (8 KiB f64 / 4 KiB f32) splits from the
    # small vectors: >= 2 buckets, reverse order (w3's bucket first).
    step = build_train_step(loss_fn, opt, mesh, donate=False,
                            overlap=True, overlap_threshold=2048,
                            overlap_probe=probe)
    batch_host = np.arange(16.0, dtype=np.float32)
    from jax.sharding import NamedSharding, PartitionSpec as P
    batch = jax.device_put(
        jnp.asarray(batch_host), NamedSharding(mesh, P("data")))
    jax.block_until_ready(batch)

    out = step(params, opt_state, batch)      # compile: unrecorded
    jax.block_until_ready(out)
    info = last_overlap_info()
    assert info["enabled"] and info["buckets"] >= 2, info
    assert probe.spans() == []                # disarmed => no spans

    probe.armed = True
    for s in range(4):
        tracing.set_step(s)
        t0 = time.monotonic_ns()
        params, opt_state, metrics = step(params, opt_state, batch)
        jax.block_until_ready(metrics["loss"])
        probe.step_span(t0, time.monotonic_ns())
    probe.armed = False

    spans = probe.spans()
    assert len(spans) >= 4 * info["buckets"], (len(spans), info)
    acct = probe.hidden_fraction()
    assert acct["spans"] == len(spans)

    tl = state().timeline
    assert tl is not None, "worker needs HOROVOD_TIMELINE set"
    wrote = probe.to_timeline(tl)
    assert wrote == len(spans)
    if not _LOCAL_MESH:
        # One negotiated eager collective per rank keeps the merge's
        # cross-rank span machinery engaged alongside the overlap
        # lanes (needs the cross-process data plane, absent in
        # local-mesh mode).
        hvd.allreduce(jnp.ones(8, jnp.float32), op=hvd.Sum,
                      name="overlap_sentinel")
        hvd.barrier()
    path = Timeline.rank_path(os.environ["HOROVOD_TIMELINE"], r)
    assert os.path.exists(path), path
    hvd.shutdown()
    print(f"OVERLAP WORKER OK rank={r} buckets={info['buckets']} "
          f"spans={len(spans)} "
          f"exposed={acct['exposed_comm_fraction']}", flush=True)


main()
