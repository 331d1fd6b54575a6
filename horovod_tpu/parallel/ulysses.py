"""Ulysses-style sequence parallelism: all-to-all head/sequence swap.

The reference ships the primitive this is built from — `hvd.alltoall`
(reference: horovod/common/ops/nccl_operations.cc NCCLAlltoall;
SURVEY.md §5.7 names alltoall + process sets as the Ulysses building
blocks). Here the full pattern is provided natively:

  before attention:  sharded-by-seq, all heads local
                     → all_to_all → sharded-by-heads, full sequence
  after attention:   inverse swap.

Each device then runs *ordinary* attention (`attention()`, which keeps
its dense path under a live sequence axis) on a head slice of the full
sequence — no ring, one collective each way. Requires
heads % sp == 0; complements ring attention (which has no such
constraint and overlaps comm with compute).
"""

from __future__ import annotations

import jax
from jax import lax

from .mesh import SEQ_AXIS
from .ring_attention import attention


def scatter_heads(x: jax.Array, axis_name: str = SEQ_AXIS) -> jax.Array:
    """(B, L_local, H, D) sharded by seq → (B, L_full, H/sp, D) sharded
    by heads. Inside shard_map."""
    sp = lax.axis_size(axis_name)
    B, L, H, D = x.shape
    assert H % sp == 0, f"heads {H} not divisible by seq-parallel {sp}"
    # split head axis across devices, gather sequence axis.
    x = x.reshape(B, L, sp, H // sp, D)
    # all_to_all: split over axis 2 (head groups), concat over axis 1 (seq)
    out = lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                         tiled=True)
    return out.reshape(B, L * sp, H // sp, D)


def gather_heads(x: jax.Array, axis_name: str = SEQ_AXIS) -> jax.Array:
    """Inverse of scatter_heads: (B, L_full, H/sp, D) → (B, L_local,
    H, D)."""
    sp = lax.axis_size(axis_name)
    B, Lf, Hs, D = x.shape
    assert Lf % sp == 0
    x = x.reshape(B, sp, Lf // sp, Hs, D)
    out = lax.all_to_all(x, axis_name, split_axis=1, concat_axis=3,
                         tiled=True)
    return out.reshape(B, Lf // sp, Hs * sp, D)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str = SEQ_AXIS,
                      causal: bool = True) -> jax.Array:
    """Attention over the full sequence with inputs/outputs sharded by
    seq. Inside shard_map."""
    qh = scatter_heads(q, axis_name)
    kh = scatter_heads(k, axis_name)
    vh = scatter_heads(v, axis_name)
    oh = attention(qh, kh, vh, causal=causal)
    return gather_heads(oh, axis_name)
