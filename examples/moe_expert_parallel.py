#!/usr/bin/env python
"""Expert parallelism: a Switch-style top-1 routed MoE layer over an
`expert` mesh axis, with token blocks exchanged by `all_to_all`.

The reference ships the alltoall PRIMITIVE an MoE needs
(hvd.alltoall with splits; SURVEY.md §2.6 'Expert parallel: primitive
only') but no routed layer; this example runs the full thing: local
router → capacity-bounded dispatch → all_to_all over ICI → per-expert
FFN → return all_to_all → weighted combine, with the Switch
load-balancing auxiliary loss.

Run (CPU demo, 8 virtual devices = 8-way expert parallelism):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/moe_expert_parallel.py --experts 16
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
from horovod_tpu.parallel.moe import moe_ffn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--experts", type=int, default=16,
                    help="total experts (sharded over the mesh)")
    ap.add_argument("--tokens", type=int, default=1024,
                    help="tokens PER DEVICE")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--d-ff", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args()

    ep = len(jax.devices())
    assert args.experts % ep == 0, \
        f"device count ({ep}) must divide --experts ({args.experts})"
    e_local = args.experts // ep
    mesh = build_mesh(MeshSpec(data=1, expert=ep))
    T, Dm, F = args.tokens, args.d_model, args.d_ff
    print(f"MoE: {args.experts} experts over {ep} devices "
          f"({e_local}/device), {T} tokens/device, d={Dm}, ff={F}")

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.standard_normal((ep * T, Dm), dtype=np.float32))
    router_w = jnp.asarray(
        rng.standard_normal((Dm, args.experts), dtype=np.float32) * 0.02)
    w_in = jnp.asarray(rng.standard_normal(
        (args.experts, Dm, F), dtype=np.float32) * 0.02)
    w_out = jnp.asarray(rng.standard_normal(
        (args.experts, F, Dm), dtype=np.float32) * 0.02)

    tok_sh = NamedSharding(mesh, P("expert"))        # tokens by device
    exp_sh = NamedSharding(mesh, P("expert"))        # experts by device
    rep_sh = NamedSharding(mesh, P())
    tokens = jax.device_put(tokens, tok_sh)
    router_w = jax.device_put(router_w, rep_sh)
    w_in = jax.device_put(w_in, exp_sh)
    w_out = jax.device_put(w_out, exp_sh)

    def fwd(t, r, wi, wo):
        out, aux = moe_ffn(t, r, wi, wo, axis_name="expert")
        # each device routes its own tokens: average the local
        # load-balance losses so the scalar is truly replicated
        return out, jax.lax.pmean(aux, "expert")

    step = jax.jit(shard_map(
        fwd, mesh=mesh,
        in_specs=(P("expert"), P(), P("expert"), P("expert")),
        out_specs=(P("expert"), P())))

    out, aux = step(tokens, router_w, w_in, w_out)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        out, aux = step(tokens, router_w, w_in, w_out)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / args.steps
    print(f"moe step: {dt * 1e3:.1f} ms, aux load-balance loss "
          f"{float(aux):.3f} (1.0 = perfectly balanced)")
    assert out.shape == tokens.shape
    print("expert-parallel MoE OK")


if __name__ == "__main__":
    main()
