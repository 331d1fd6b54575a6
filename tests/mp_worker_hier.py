"""Worker for hierarchical x device-spanning composition (round-4
verdict Missing #2): 4 processes x 2 virtual devices each, with the
topology env faked to 2 "hosts" x 2 processes — so
HOROVOD_HIERARCHICAL_ALLREDUCE factors the world as
('cross'=2, 'local'=2, 'dev'=2) and an eager allreduce must take the
hier_wide path (every chip busy, DCN phase moving 1/(local*dev) of
the bytes), not idle the second chip like the 2-axis hier mesh did."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=2")

rank = int(os.environ.get("HOROVOD_RANK", "0"))
# Fake a 2-host x 2-proc topology (the launcher put all 4 on this
# host; slice-alignment needs local_size < world size).
os.environ["HOROVOD_LOCAL_SIZE"] = "2"
os.environ["HOROVOD_LOCAL_RANK"] = str(rank % 2)
os.environ["HOROVOD_CROSS_SIZE"] = "2"
os.environ["HOROVOD_CROSS_RANK"] = str(rank // 2)
os.environ["HOROVOD_HIERARCHICAL_ALLREDUCE"] = "1"

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.ops import dispatch  # noqa: E402


def main():
    hvd.init()
    r, n = hvd.rank(), hvd.size()
    assert n == 4, f"test expects 4 ranks, got {n}"
    ndev = len(jax.local_devices())
    assert ndev == 2, ndev

    # 1) big allreduce: hierarchical AND device-spanning.
    elems = 8192
    x = jnp.arange(elems, dtype=jnp.float32) + float(r)
    out = hvd.allreduce(x, name="hier_sum", op=hvd.Sum)
    info = dispatch.last_allreduce_info()
    assert info.get("path") == "hier_wide", info
    assert info.get("mesh_shape") == {"cross": 2, "local": 2,
                                      "dev": 2}, info
    expect = np.arange(elems, dtype=np.float32) * n + sum(range(n))
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)
    print(f"rank {r}: hier_wide allreduce OK ({info})")

    # 2) grouped + fp16 wire through the same composed program.
    xs = [jnp.full((2048,), float(i + 1 + r), jnp.float32)
          for i in range(3)]
    outs = hvd.grouped_allreduce(xs, op=hvd.Average,
                                 compression=hvd.Compression.fp16)
    assert dispatch.last_allreduce_info().get("path") == "hier_wide"
    for i, o in enumerate(outs):
        assert o.dtype == jnp.float32
        want = sum(float(i + 1 + rr) for rr in range(n)) / n
        np.testing.assert_allclose(np.asarray(o),
                                   np.full(2048, want), rtol=1e-2)
    print(f"rank {r}: hier_wide grouped+fp16 OK")

    # 2b) allgather composes too: ragged rows through the
    # ('cross','local','dev') staged gather.
    rows_mine = 512 + 16 * r
    out = hvd.allgather(jnp.full((rows_mine, 4), float(r), jnp.float32),
                        name="hier_ag")
    info = dispatch.last_op_info("allgather")
    assert info.get("path") == "hier_wide", info
    assert info.get("mesh_shape") == {"cross": 2, "local": 2,
                                      "dev": 2}, info
    off = 0
    for rr in range(n):
        seg = np.asarray(out[off:off + 512 + 16 * rr])
        np.testing.assert_allclose(seg, np.full(seg.shape, float(rr)))
        off += 512 + 16 * rr
    print(f"rank {r}: hier_wide allgather OK ({info})")

    # 3) span knob off -> the 2-axis hier path (representative chips).
    dispatch.set_span_devices("0")
    out = hvd.allreduce(jnp.full((8192,), 1.0, jnp.float32),
                        name="hier_narrow", op=hvd.Sum)
    info = dispatch.last_allreduce_info()
    assert info.get("path") == "hier", info
    np.testing.assert_allclose(np.asarray(out), np.full(8192, float(n)))
    dispatch.set_span_devices("auto")
    print(f"rank {r}: hier narrow fallback OK")

    hvd.shutdown()
    print(f"rank {r}: HIER ALL OK")


if __name__ == "__main__":
    main()
