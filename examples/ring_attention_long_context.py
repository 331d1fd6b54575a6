#!/usr/bin/env python
"""Long-context sequence parallelism: exact ring attention over a
`seq` mesh axis.

The reference has no sequence-parallel layer (SURVEY.md §5.7 — it
predates the long-context era); this example shows the capability the
TPU rebuild adds on top of the same collective substrate: each device
holds 1/sp of the sequence, K/V blocks rotate around the ring
(`ppermute` over ICI) while partial attention accumulates with exact
log-sum-exp merging — memory per device is O(L/sp), results are
bitwise-identical in math to full attention.

Run (CPU demo, 8 virtual devices):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/ring_attention_long_context.py --seq-parallel 8
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.parallel import MeshSpec, build_mesh
from horovod_tpu.parallel.ring_attention import (dense_attention,
                                                 ring_attention)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-parallel", type=int, default=0,
                    help="ring size (default: all devices)")
    ap.add_argument("--seq-len", type=int, default=4096,
                    help="TOTAL sequence length across the ring")
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--verify", action="store_true",
                    help="cross-check against full attention "
                         "(gathers the whole sequence — small L only)")
    args = ap.parse_args()

    sp = args.seq_parallel or len(jax.devices())
    mesh = build_mesh(MeshSpec(data=1, seq=sp))
    L, H, D = args.seq_len, args.heads, args.head_dim
    assert L % sp == 0, "--seq-len must divide by the ring size"
    print(f"ring attention: {sp} devices x {L // sp} tokens "
          f"= {L} total, {H} heads x {D}")

    rng = np.random.default_rng(0)
    shape = (args.batch, L, H, D)
    q, k, v = (jnp.asarray(rng.standard_normal(shape, dtype=np.float32))
               for _ in range(3))
    seq_sh = NamedSharding(mesh, P(None, "seq"))
    q, k, v = (jax.device_put(t, seq_sh) for t in (q, k, v))

    ring = jax.jit(shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq"),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq")))

    out = ring(q, k, v)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = ring(q, k, v)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    print(f"ring step: {dt * 1e3:.1f} ms "
          f"({args.batch * L} tokens, causal)")

    if args.verify:
        full = dense_attention(jnp.asarray(jax.device_get(q)),
                               jnp.asarray(jax.device_get(k)),
                               jnp.asarray(jax.device_get(v)))
        err = float(jnp.max(jnp.abs(jnp.asarray(jax.device_get(out))
                                    - full)))
        print(f"max |ring - full| = {err:.2e}")
        assert err < 2e-4, err
        print("ring attention verified against full attention")


if __name__ == "__main__":
    main()
