"""Expert parallelism: MoE token routing over the `expert` mesh axis.

The reference exposes only the primitive (`hvd.alltoall` with splits —
SURVEY.md §2.6 "Expert parallel: primitive only; no router/MoE layer in
repo"). Per the survey's direction to "ship a reference MoE block to
prove it", this module provides a complete top-k routed MoE FFN with
capacity-based dispatch — static shapes throughout so XLA can tile it
onto the MXU (no dynamic token counts; overflow tokens drop, the
standard TPU-friendly formulation from GShard/Switch).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .mesh import EXPERT_AXIS


def top1_route(logits: jax.Array, n_experts: int, capacity: int
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Switch-style top-1 routing with capacity.

    logits: (T, E). Returns (dispatch (T, E, C) one-hot, combine
    (T, E, C) weights, aux_loss scalar)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)                       # (T,)
    onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.float32)
    # position of each token within its expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0           # (T,E)
    keep = (pos < capacity) & (onehot > 0)
    pos = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
    dispatch = keep[..., None] * jax.nn.one_hot(
        pos, capacity, dtype=jnp.float32)                     # (T,E,C)
    gate = jnp.max(probs * onehot, axis=-1, keepdims=True)    # (T,1)
    combine = dispatch * gate[..., None]
    # load-balancing aux loss (Switch eq. 4)
    density = jnp.mean(onehot, axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * n_experts
    return dispatch, combine, aux


def moe_ffn(tokens: jax.Array, router_w: jax.Array, w_in: jax.Array,
            w_out: jax.Array, capacity_factor: float = 1.25,
            axis_name: Optional[str] = EXPERT_AXIS
            ) -> Tuple[jax.Array, jax.Array]:
    """Top-1 routed MoE feed-forward.

    tokens: (T, D) local tokens (inside shard_map when axis_name is a
    live mesh axis; standalone otherwise).
    router_w: (D, E); w_in: (E_local, D, F); w_out: (E_local, F, D).
    E = E_local * ep. Returns (output (T, D), aux_loss)."""
    T, D = tokens.shape
    E_local = w_in.shape[0]
    ep = lax.axis_size(axis_name) if axis_name else 1
    E = E_local * ep
    capacity = max(1, int(capacity_factor * T / E))

    logits = tokens.astype(jnp.float32) @ router_w.astype(jnp.float32)
    dispatch, combine, aux = top1_route(logits, E, capacity)

    # gather tokens per expert: (E, C, D)
    xs = jnp.einsum("tec,td->ecd", dispatch,
                    tokens.astype(jnp.float32))
    if ep > 1:
        # exchange token blocks so each device holds all devices'
        # tokens for its local experts: (E,C,D) → (E_local, ep*C, D)
        xs = xs.reshape(ep, E_local, capacity, D)
        xs = lax.all_to_all(xs, axis_name, split_axis=0, concat_axis=2,
                            tiled=True)
        xs = xs.reshape(E_local, ep * capacity, D)
    else:
        xs = xs.reshape(E_local, capacity, D)

    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xs,
                               w_in.astype(jnp.float32)))
    ys = jnp.einsum("ecf,efd->ecd", h, w_out.astype(jnp.float32))

    if ep > 1:
        ys = ys.reshape(E_local, ep, capacity, D)
        ys = lax.all_to_all(ys, axis_name, split_axis=1, concat_axis=0,
                            tiled=True)
        ys = ys.reshape(E, capacity, D)

    out = jnp.einsum("tec,ecd->td", combine, ys)
    return out.astype(tokens.dtype), aux
