"""Historical-bug regression corpus: the defects this repo actually
shipped and later fixed, reconstructed in miniature, each asserting
the analyzer would now catch it at lint time.

AST tier (run_analysis; EXPECT-anchored):
  * PR 1 — the unlocked `_bytes_processed` accumulation raced between
    the caller thread and the controller's dispatch worker (HVD006).
  * PR 4 — `subprocess.Popen` spawned while holding `TaskService._lock`
    serialized every contender behind process startup (HVD003).
  * PR 6 — torch async handles submitted but never synchronized leaked
    their engine entries for the life of the session (HVD005).
  * PR 18 schema drift — the decode doctor keyed resume watermarks on
    a misspelled field, silently dropping every record it was written
    to count (HVD008).
  * PR 18 byte-identity flake — the trajectory consolidation walked
    per-round bench artifacts with an unsorted glob, so regenerated
    reports matched the committed bytes only when the filesystem
    happened to agree (HVD009).

Jaxpr tier (HVD007, traced by TestHistoricalRegressions through
analysis.jaxpr_verify.verify_traced — no EXPECT markers because these
are IR-level defects the AST pass cannot see, which is the point):
  * PR 8 bug #1 — the monolithic reduction leg emitted psums over
    size-1 mesh axes (identity wire: the full pack/reduce round trip
    with zero bytes to move, shipped in every world-1 step).
  * PR 8 bug #2 — the legacy-jax psum transpose re-reduced an
    already-reduced gradient over the same axis, so gradients arrived
    exactly |axis|x too large.
  * PR 13 — the first compression draft let the finite-flag ride the
    fp16-cast wire carrier (one fused n+1 psum in half precision).
    A veto count accumulated in a lossy dtype rounds n-1 up to n past
    a few hundred ranks, silently disabling the numerics guard at
    exactly the scale it exists for; HVD007's check (e) must flag the
    planned ride and the missing separate exact f32 vote.
"""

import glob
import json
import subprocess
import threading

import horovod_tpu as hvd
from horovod_tpu.ops import collective_ops

DETERMINISTIC_ENTRYPOINTS = ("pr18_trajectory_consolidate",)


class Pr1BytesProcessedRace:
    """PR 1: `self._bytes_processed += nbytes` from both the inline
    caller path and the controller's background dispatch worker, no
    lock — the fix made it a thread-safe Counter."""

    def __init__(self):
        self._bytes_processed = 0
        self._worker = threading.Thread(target=self._dispatch_loop,
                                        daemon=True)

    def _dispatch_loop(self):
        while True:
            self._bytes_processed += 1024  # EXPECT: HVD006

    def run_inline(self, nbytes):
        self._bytes_processed += nbytes


class Pr4PopenUnderLock:
    """PR 4: claim-then-spawn was the fix; the bug held the service
    lock across the process spawn."""

    def __init__(self):
        self._lock = threading.Lock()
        self._procs = []

    def spawn(self, cmd):
        with self._lock:
            proc = subprocess.Popen(cmd)  # EXPECT: HVD003
            self._procs.append(proc)
        return proc


class Pr6HandleLeak:
    """PR 6: handles submitted on the skip_synchronize path were never
    drained, so their engine entries (and torch meta) lived forever."""

    def __init__(self):
        self._should_sync = True

    def step(self, grads):
        h = hvd.grouped_allreduce_async(grads)  # EXPECT: HVD005
        if self._should_sync:
            return collective_ops.synchronize(h)
        return grads


def pr18_watermark_field_drift(events):
    """PR 18 schema drift: the decode doctor's watermark census read
    `w.get("tokn")` — a misspelling of the declared `token` field —
    which returned None for every record, so the resume-watermark
    count silently collapsed to zero and the doctor reported a clean
    decode tier while sequences were being replayed from scratch.
    HVD008's consumer leg must flag the read against the registry."""
    high = {}
    for w in events:
        if w["type"] == "seq_watermark":
            high[w["sid"]] = w.get("tokn")  # EXPECT: HVD008
    return high


def pr18_trajectory_consolidate(dir_):
    """PR 18 byte-identity flake: `bench --trajectory` consolidation
    walked the per-round artifacts with an unsorted glob, so the row
    order of the regenerated BENCH_trajectory.json depended on
    filesystem enumeration order and the byte-identity pin flaked.
    Declared in DETERMINISTIC_ENTRYPOINTS above so HVD009 seeds its
    reachability here and must flag the unsorted walk."""
    rows = []
    for seg in glob.glob(dir_ + "/BENCH_r*.json"):  # EXPECT: HVD009
        rows.append(seg)
    return json.dumps({"rows": rows}, sort_keys=True, indent=1)


def pr8_wire_gate_builder():
    """PR 8 bug #1, jaxpr tier: a traced step whose reduction runs
    over a size-1 mesh axis. Before the r08 wire gate, the monolithic
    leg emitted exactly this for every leaf at world 1 (12 dead
    size-1 all-reduces per transformer step); HVD007's check (a) must
    flag the size-1 reduce. Returns (jitted step, example args,
    mesh axis sizes) for analysis.jaxpr_verify.verify_traced."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(np.array(jax.devices("cpu")[:2]).reshape(2, 1),
                ("data", "one"))

    def local(g):
        g = lax.psum(g, "data")
        return lax.psum(g, "one")  # size-1 axis: identity wire

    step = jax.jit(shard_map(local, mesh=mesh, in_specs=P(),
                             out_specs=P()))
    args = (jax.ShapeDtypeStruct((4,), jnp.float32),)
    return step, args, {"data": 2, "one": 1}


def pr8_legacy_double_reduce_builder():
    """PR 8 bug #2, jaxpr tier: the legacy-jax psum transpose shape —
    a gradient already psum'd over an axis is psum'd over that same
    axis again, arriving |axis|x too large (measured 2.0x/4.0x per
    tp/sp axis in round 8). HVD007's check (d) must flag the double
    reduction."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(np.array(jax.devices("cpu")[:2]), ("data",))

    def local(g):
        s = lax.psum(g, "data")          # the real reduction
        return lax.psum(s, "data") * 0.5  # the transpose's re-reduce

    step = jax.jit(shard_map(local, mesh=mesh, in_specs=P(),
                             out_specs=P()))
    args = (jax.ShapeDtypeStruct((4,), jnp.float32),)
    return step, args, {"data": 2}


def pr13_flag_rides_compressed_carrier_builder():
    """PR 13, jaxpr tier: the first gradient-compression draft reused
    the dense flag-carrier packing verbatim, so a bucket cast to fp16
    for the wire carried its finite-flag as element n+1 OF THE FP16
    PSUM — the veto count crossed the network in half precision and
    no exact vote existed anywhere. HVD007's check (e) must flag both
    the planned ride and the missing separate f32 vote. Returns
    (jitted step, example args, mesh axis sizes, buggy plan) for
    analysis.jaxpr_verify.verify_traced(..., plan=...,
    numerics_guard=True)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map
    from horovod_tpu.parallel.train import OverlapPlan, WireGroup

    mesh = Mesh(np.array(jax.devices("cpu")[:2]), ("data",))

    def local(g, flag):
        # the draft's fused ride: cast, append the flag, one lossy psum
        wire = jnp.concatenate([g.astype(jnp.float16).ravel(),
                                flag.astype(jnp.float16)])
        red = lax.psum(wire, "data")
        return red[:-1].astype(jnp.float32), red[-1]

    step = jax.jit(shard_map(local, mesh=mesh,
                             in_specs=(P(), P()), out_specs=(P(), P())))
    args = (jax.ShapeDtypeStruct((16,), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.float32))
    plan = OverlapPlan(
        threshold=4096, guard=True, n_leaves=1,
        bucket_leaf_indices=((0,),), bucket_raxes=(("data",),),
        bucket_nbytes=(64,),
        wire=((WireGroup("float16", 17, True, None),),),
        digest="1:64|c=fp16", leaf_raxes=(("data",),),
        loose_inexact=(), bucket_compression=("fp16",))
    return step, args, {"data": 2}, plan
