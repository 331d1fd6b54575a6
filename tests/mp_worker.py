"""Worker script for launcher integration tests: exercises the eager
collective API across REAL processes (the reference's
`horovodrun -np 2 pytest` analog, SURVEY.md §4 tier 1)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# 64-bit rows of the dtype matrix need real x64 (this process is NOT
# under conftest.py's jax_enable_x64).
os.environ.setdefault("JAX_ENABLE_X64", "1")
# Pin the launch-overhead term to zero so the skewed-alltoall phase
# asserts the BYTE side of the auto heuristic deterministically (the
# launch-aware side is unit-tested in test_dispatch_kernels).
os.environ.setdefault("HOROVOD_LAUNCH_OVERHEAD_US", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


def main():
    nsz = int(os.environ.get("HOROVOD_SIZE", "1"))
    half = hvd.ProcessSet(list(range(max(nsz // 2, 1))))
    hvd.init(process_sets=[half])
    r, n = hvd.rank(), hvd.size()
    assert n == int(os.environ["HOROVOD_SIZE"]), (n, os.environ)
    print(f"worker rank={r} size={n} devices={jax.device_count()}")

    # allreduce (average)
    out = hvd.allreduce(jnp.array([float(r + 1), 2.0]), name="t0")
    expect = np.array([(sum(range(1, n + 1))) / n, 2.0])
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)

    # sum + prescale
    out = hvd.allreduce(jnp.array([1.0]), op=hvd.Sum,
                        prescale_factor=2.0, name="t1")
    np.testing.assert_allclose(np.asarray(out), [2.0 * n])

    # grouped allreduce, mixed dtypes
    outs = hvd.grouped_allreduce(
        [jnp.ones((3,), jnp.float32) * r, jnp.ones((2,), jnp.float64)],
        op=hvd.Sum, name="t2")
    np.testing.assert_allclose(np.asarray(outs[0]),
                               np.full(3, sum(range(n))))
    np.testing.assert_allclose(np.asarray(outs[1]), np.full(2, n))

    # broadcast
    out = hvd.broadcast(jnp.arange(4.0) * (r + 1), root_rank=1 % n,
                        name="t3")
    np.testing.assert_allclose(np.asarray(out),
                               np.arange(4.0) * ((1 % n) + 1))

    # uneven allgather
    out = hvd.allgather(jnp.full((r + 1, 2), float(r)), name="t4")
    expect = np.concatenate(
        [np.full((i + 1, 2), float(i)) for i in range(n)])
    np.testing.assert_allclose(np.asarray(out), expect)

    # alltoall with splits
    x = jnp.arange(float(n * 2)).reshape(n * 2)[:, None]
    out, recv = hvd.alltoall(x, splits=[2] * n, name="t5")
    assert out.shape[0] == 2 * n

    # UNEVEN alltoall: rank r sends (d+1)*(r+1) rows to dest d — both
    # the send and the receive split vectors differ per rank, all
    # carried through the negotiation metadata (reference:
    # MPI_Alltoallv semantics via HorovodAlltoallOp splits)
    sends = [(d + 1) * (r + 1) for d in range(n)]
    rows = sum(sends)
    x = jnp.full((rows, 2), float(r))
    out, recv = hvd.alltoall(x, splits=sends, name="t5u")
    want_recv = [(r + 1) * (src + 1) for src in range(n)]
    np.testing.assert_array_equal(np.asarray(recv), want_recv)
    assert out.shape == (sum(want_recv), 2)
    # block from src has value src
    off = 0
    for src in range(n):
        np.testing.assert_allclose(
            np.asarray(out[off:off + want_recv[src]]), float(src))
        off += want_recv[src]

    # SKEWED alltoall (the MoE hot path: most rows stay local). The
    # ragged exchange must move ~sum(cross splits) rows on the wire,
    # not n * maxsplit (reference: MPI_Alltoallv exact counts).
    sends = [64 if d == r else 1 for d in range(n)]
    x = jnp.concatenate(
        [jnp.full((sends[d], 2), float(10 * r + d)) for d in range(n)])
    out, recv = hvd.alltoall(x, splits=sends, name="t5s")
    want_recv = [64 if src == r else 1 for src in range(n)]
    np.testing.assert_array_equal(np.asarray(recv), want_recv)
    off = 0
    for src in range(n):
        np.testing.assert_allclose(
            np.asarray(out[off:off + want_recv[src]]),
            float(10 * src + r))
        off += want_recv[src]
    from horovod_tpu.ops import dispatch as _dispatch
    st = _dispatch.last_alltoall_stats()
    assert st["path"] == "ragged", st
    assert st["wire_rows"] == n - 1, st        # 1-row bucket per round
    assert st["padded_rows"] == n * 64, st     # what padding would move

    # reducescatter
    x = jnp.ones((2 * n, 3)) * (r + 1)
    out = hvd.reducescatter(x, op=hvd.Sum, name="t6")
    np.testing.assert_allclose(
        np.asarray(out), np.full((2, 3), sum(range(1, n + 1))))

    # hvd.flax.DistributedTrainState: rank-DIFFERENT init must equal
    # rank 0's after create (broadcast), and a step on rank-different
    # grads must keep params identical (averaged reduction).
    import optax
    st_flax = hvd.flax.DistributedTrainState.create(
        apply_fn=lambda v, x: x,
        params={"w": jnp.full((3,), float(r + 1))}, tx=optax.sgd(1.0))
    np.testing.assert_allclose(np.asarray(st_flax.params["w"]), 1.0)
    st_flax = st_flax.apply_gradients(
        grads={"w": jnp.full((3,), float(r))})
    want_w = 1.0 - sum(range(n)) / n
    np.testing.assert_allclose(np.asarray(st_flax.params["w"]),
                               want_w, rtol=1e-6)
    stats = hvd.flax.sync_batch_stats(
        {"m": jnp.full((2,), float(r))})
    np.testing.assert_allclose(np.asarray(stats["m"]),
                               sum(range(n)) / n)

    # grouped allgather (uneven dims per tensor) + grouped
    # reducescatter under ONE umbrella handle each (reference:
    # grouped_allgather / grouped_reducescatter in torch/mpi_ops.py)
    outs = hvd.grouped_allgather(
        [jnp.full((r + 1, 2), float(r)), jnp.full((1,), float(r))],
        name="t6g")
    np.testing.assert_allclose(
        np.asarray(outs[0]),
        np.concatenate([np.full((i + 1, 2), float(i))
                        for i in range(n)]))
    np.testing.assert_allclose(np.asarray(outs[1]),
                               np.arange(float(n)))
    outs = hvd.grouped_reducescatter(
        [jnp.ones((2 * n, 3)) * (r + 1), jnp.ones((n,)) * (r + 1)],
        op=hvd.Sum, name="t6gr")
    np.testing.assert_allclose(
        np.asarray(outs[0]), np.full((2, 3), sum(range(1, n + 1))))
    np.testing.assert_allclose(
        np.asarray(outs[1]), np.full((1,), sum(range(1, n + 1))))

    # sparse allreduce (BCOO): rank-dependent nnz, rank 0 contributes
    # ZERO rows (the empty-contribution edge of the uneven allgather),
    # every other rank touches row 1 (cross-rank duplicate coalescing)
    # (reference: torch mpi_ops sparse allreduce via allgather).
    from jax.experimental import sparse as jsparse
    if r == 0:
        sp = jsparse.BCOO(
            (jnp.zeros((0, 2)), jnp.zeros((0, 1), jnp.int32)),
            shape=(5, 2))
    else:
        sp = jsparse.BCOO(
            (jnp.full((2, 2), float(r)),
             jnp.array([[1], [min(r + 1, 4)]], jnp.int32)),
            shape=(5, 2))
    out = hvd.sparse_allreduce(sp, op=hvd.Sum, name="t7.sparse")
    want = np.zeros((5, 2))
    for rr in range(1, n):
        want[1] += rr
        want[min(rr + 1, 4)] += rr
    np.testing.assert_allclose(np.asarray(out.todense()), want)

    # dtype x op matrix on the negotiated path (reference analog:
    # test_torch.py's exhaustive dtype/op coverage under -np 2).
    # Rank r contributes full((r+2)); closed forms below.
    matrix_dtypes = [jnp.float32, jnp.float64, jnp.bfloat16,
                     jnp.float16, jnp.int32, jnp.int64, jnp.uint8]
    vals = [i + 2 for i in range(n)]
    for dt in matrix_dtypes:
        is_float = jnp.issubdtype(dt, jnp.floating)
        ops = [(hvd.Sum, float(sum(vals))),
               (hvd.Min, float(min(vals))),
               (hvd.Max, float(max(vals))),
               (hvd.Product, float(np.prod(vals)))]
        if is_float:
            ops.append((hvd.Average, sum(vals) / n))
        for op_, want in ops:
            x = jnp.full((4, 3), r + 2, dt)
            out = hvd.allreduce(x, op=op_,
                                name=f"mx.{np.dtype(dt).name}.{op_}")
            assert out.dtype == x.dtype, (out.dtype, dt)
            tol = 5e-2 if dt in (jnp.bfloat16, jnp.float16) else 1e-6
            np.testing.assert_allclose(
                np.asarray(out, np.float64), np.full((4, 3), want),
                rtol=tol)
    # bool allgather/broadcast (the reference covers bool paths too)
    out = hvd.allgather(jnp.asarray([r % 2 == 0] * 2), name="mx.bool")
    assert out.dtype == jnp.bool_ and out.shape[0] == 2 * n
    out = hvd.broadcast(jnp.asarray([True, False]), root_rank=0,
                        name="mx.bool.bc")
    assert bool(out[0]) and not bool(out[1])

    # SUBSET process-set eager ops dispatch inline (the negotiation is
    # world-scoped; waiting on non-members would hang) — must complete
    # with member-only semantics while the world controller is live.
    if r in half.ranks:
        out = hvd.allreduce(jnp.full((3,), float(r + 1)), op=hvd.Sum,
                            name="subset_ar", process_set=half)
        np.testing.assert_allclose(
            np.asarray(out),
            np.full(3, float(sum(i + 1 for i in half.ranks))))

    # barrier + broadcast_parameters + optimizer functions
    hvd.barrier()
    params = {"w": jnp.ones((2, 2)) * r}
    params = hvd.broadcast_parameters(params, root_rank=0)
    np.testing.assert_allclose(np.asarray(params["w"]), 0.0)

    # broadcast_object
    obj = hvd.broadcast_object({"epoch": r * 10}, root_rank=0)
    assert obj == {"epoch": 0}

    # allgather_object: rank-varying payload SIZES (uneven gather)
    got = hvd.allgather_object({"rank": r, "pad": "x" * (10 * (r + 1))})
    assert [g["rank"] for g in got] == list(range(hvd.size())), got
    assert all(len(g["pad"]) == 10 * (i + 1)
               for i, g in enumerate(got)), got

    print(f"worker rank={r}: ALL OK")
    hvd.shutdown()


if __name__ == "__main__":
    main()
