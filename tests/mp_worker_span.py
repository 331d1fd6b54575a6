"""Worker for the device-spanning eager data plane test: every
process owns SEVERAL devices (xla_force_host_platform_device_count>1
per process — the CPU stand-in for a multi-chip TPU host, SURVEY.md §4
technique 2), and the classic eager allreduce must reduce over ALL of
them, not one representative per process (round-3 verdict Missing #1).

Asserts on the mesh (every device of every process participates) and
on the summed payload (results correct through the wide kernel,
with and without fp16 compression, grouped and single)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Each PROCESS gets several virtual devices (set by the launching
# test via XLA_FLAGS; default here for direct runs).
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=2")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.common.basics import state  # noqa: E402
from horovod_tpu.ops import dispatch  # noqa: E402


def main():
    hvd.init()
    r, n = hvd.rank(), hvd.size()
    ndev_local = len(jax.local_devices())
    assert ndev_local > 1, (
        f"test setup: expected >1 local device, got {ndev_local}")

    st = state()
    pset = st.engine.pset_table.get(0)

    # 1) the device-spanning mesh covers EVERY device of EVERY process.
    dm = pset.device_mesh
    assert dm is not None, "device_mesh must exist with >1 local device"
    assert dict(dm.shape) == {"proc": n, "dev": ndev_local}, dm.shape
    assert int(dm.devices.size) == len(jax.devices()) == n * ndev_local
    procs_in_mesh = {d.process_index for d in dm.devices.flat}
    assert procs_in_mesh == set(range(n)), procs_in_mesh
    print(f"rank {r}: device mesh spans {int(dm.devices.size)} devices")

    # 2) big eager allreduce lands on the wide path and is correct.
    elems = 4096  # >= ndev * _WIDE_MIN_ELEMS_PER_DEV
    x = jnp.arange(elems, dtype=jnp.float32) + float(r)
    out = hvd.allreduce(x, name="span_sum", op=hvd.Sum)
    info = dispatch.last_allreduce_info()
    assert info.get("path") == "wide", info
    assert info.get("devices") == n * ndev_local, info
    expect = np.arange(elems, dtype=np.float32) * n + sum(range(n))
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)
    print(f"rank {r}: wide allreduce OK ({info})")

    # 3) grouped + fp16 compression through the wide kernel: the cast
    # folds into the same launch; MIXED raw dtypes (bf16 + f32) share
    # the fp16 wire and fuse into ONE wide program (wire-keyed fuse
    # rule), each output restored to its raw dtype.
    xs = [jnp.full((2048,), float(i + 1 + r),
                   jnp.bfloat16 if i % 2 else jnp.float32)
          for i in range(4)]
    outs = hvd.grouped_allreduce(xs, op=hvd.Average,
                                 compression=hvd.Compression.fp16)
    info = dispatch.last_allreduce_info()
    assert info.get("path") == "wide", info
    for i, o in enumerate(outs):
        assert o.dtype == (jnp.bfloat16 if i % 2 else jnp.float32), \
            (i, o.dtype)
        expect_v = sum(float(i + 1 + rr) for rr in range(n)) / n
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.full(2048, expect_v), rtol=3e-2)
    print(f"rank {r}: wide grouped+fp16 mixed-raw OK")

    # 4) small payloads stay on the flat path (auto floor) and agree.
    out = hvd.allreduce(jnp.full((8,), 1.0), name="small", op=hvd.Sum)
    info = dispatch.last_allreduce_info()
    assert info.get("path") == "flat", info
    np.testing.assert_allclose(np.asarray(out), np.full(8, float(n)))
    print(f"rank {r}: small-payload flat fallback OK")

    # 4.5) broadcast through the wide kernel: rank 0's bucket reaches
    # every rank with each chip moving 1/D of it (broadcast_parameters
    # is the startup whole-model move — it must span chips too).
    # non-root ranks hold GARBAGE, not zeros: a dropped root mask in
    # the kernel (degenerating to a plain sum) must fail this assert.
    big = (jnp.arange(4096, dtype=jnp.float32) if r == 0
           else jnp.full((4096,), -7.0 * (r + 1), jnp.float32))
    out = hvd.broadcast(big, root_rank=0, name="span_bcast")
    np.testing.assert_allclose(
        np.asarray(out), np.arange(4096, dtype=np.float32))
    print(f"rank {r}: wide broadcast OK")

    # 5) min/max through the wide kernel too.
    out = hvd.allreduce(jnp.full((4096,), float(r + 1)), name="span_max",
                        op=hvd.Max)
    assert dispatch.last_allreduce_info().get("path") == "wide"
    np.testing.assert_allclose(np.asarray(out), np.full(4096, float(n)))
    print(f"rank {r}: wide max OK")

    # 6) allgather through the wide kernel: ragged first dims, every
    # chip moves 1/D of the bucket (round-4 verdict Missing #1).
    rows_mine = 512 + 16 * r
    xg = jnp.full((rows_mine, 4), float(r), jnp.float32)
    out = hvd.allgather(xg, name="span_ag")
    info = dispatch.last_op_info("allgather")
    assert info.get("path") == "wide", info
    assert info.get("devices") == n * ndev_local, info
    expect_rows = sum(512 + 16 * rr for rr in range(n))
    assert out.shape == (expect_rows, 4), out.shape
    off = 0
    for rr in range(n):
        seg = np.asarray(out[off:off + 512 + 16 * rr])
        np.testing.assert_allclose(seg, np.full(seg.shape, float(rr)))
        off += 512 + 16 * rr
    print(f"rank {r}: wide allgather OK ({info})")

    # 7) reducescatter through the wide kernel: uneven first dim, each
    # rank gets its trimmed reduced block.
    d0 = 4 * n + 1  # uneven: low ranks get one extra row
    xs_rs = jnp.tile(jnp.arange(d0, dtype=jnp.float32)[:, None],
                     (1, 1024)) + float(r)
    out = hvd.reducescatter(xs_rs, name="span_rs", op=hvd.Sum)
    info = dispatch.last_op_info("reducescatter")
    assert info.get("path") == "wide", info
    from horovod_tpu.ops.dispatch import reducescatter_rows
    rows_all = reducescatter_rows(d0, n)
    my_off = sum(rows_all[:r])
    expect = (np.tile(np.arange(d0, dtype=np.float32)[:, None],
                      (1, 1024)) * n + sum(range(n)))
    np.testing.assert_allclose(
        np.asarray(out), expect[my_off:my_off + rows_all[r]], rtol=1e-6)
    print(f"rank {r}: wide reducescatter OK ({info})")

    # 8) alltoall through the wide kernel (uniform splits, padded
    # schedule forced so the wide padded kernel engages).
    from horovod_tpu.ops import dispatch as dsp
    dsp.set_alltoall_mode("padded")
    rows_a2a = 256
    xa = jnp.concatenate([
        jnp.full((rows_a2a, 2), float(r * 10 + dst), jnp.float32)
        for dst in range(n)])
    out, recv = hvd.alltoall(xa, splits=[rows_a2a] * n, name="span_a2a")
    np.testing.assert_array_equal(np.asarray(recv),
                                  np.full(n, rows_a2a))
    info = dispatch.last_op_info("alltoall")
    assert info.get("path") == "wide", info
    for src in range(n):
        seg = np.asarray(out[src * rows_a2a:(src + 1) * rows_a2a])
        np.testing.assert_allclose(
            seg, np.full(seg.shape, float(src * 10 + r)))
    dsp.set_alltoall_mode("auto")
    print(f"rank {r}: wide alltoall OK ({info})")

    # 8b) RAGGED alltoall rounds through the wide kernel too: skewed
    # splits, forced ragged schedule — each ppermute round's chunk
    # slabs across local chips.
    dsp.set_alltoall_mode("ragged")
    splits_r = [256 + 128 * ((r + dst) % 2) for dst in range(n)]
    xa2 = jnp.concatenate([
        jnp.full((splits_r[dst], 2), float(r * 100 + dst), jnp.float32)
        for dst in range(n)])
    out, recv = hvd.alltoall(xa2, splits=splits_r, name="span_a2a_rag")
    info = dispatch.last_op_info("alltoall")
    assert info.get("path") == "ragged", info
    stats = dsp.last_alltoall_stats()
    # every nonzero round must have taken the device-spanning kernel
    # (outputs are identical on the flat rounds — assert the path).
    assert stats.get("wide_rounds") == n - 1, stats
    off = 0
    for src in range(n):
        rows_src = 256 + 128 * ((src + r) % 2)
        assert int(recv[src]) == rows_src, (src, recv)
        seg = np.asarray(out[off:off + rows_src])
        np.testing.assert_allclose(
            seg, np.full(seg.shape, float(src * 100 + r)))
        off += rows_src
    dsp.set_alltoall_mode("auto")
    print(f"rank {r}: ragged wide alltoall OK")

    # 9) Adasum allreduce through the wide vhdd kernel (pow2 worlds) —
    # oracle-checked against the numpy fold.
    from horovod_tpu.ops.adasum import adasum_reference
    rng = np.random.RandomState(17)
    contribs = [rng.randn(3000).astype(np.float32) for _ in range(n)]
    out = hvd.allreduce(jnp.asarray(contribs[r]), name="span_adasum",
                        op=hvd.Adasum)
    info = dispatch.last_op_info("adasum")
    # pow2 AND non-pow2 sets take the device-spanning vhdd (the mixed
    # kernel handles any n via pow2 blocks + merges).
    assert info.get("path") == "vhdd_wide", info
    assert info.get("devices") == n * ndev_local, info
    expect = adasum_reference(contribs)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-4,
                               atol=2e-5)
    print(f"rank {r}: wide adasum OK ({info})")

    hvd.shutdown()
    print(f"rank {r}: SPAN ALL OK")


if __name__ == "__main__":
    main()
