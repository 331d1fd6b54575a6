"""Pipeline parallelism: GPipe-style microbatch pipelining over the
`pipe` mesh axis, activations hopping stages via `ppermute`.

Not present in the reference (SURVEY.md §2.6 — pipeline parallel: not
present); provided here because the mesh/collective layer makes it
cheap and the task brief asks for the full parallelism suite.

Schedule: the classic (n_micro + n_stages - 1)-tick loop. Each tick
every stage processes one microbatch-activation and ppermutes it to
the next stage; stage 0 injects fresh microbatches, the last stage
emits results. Bubble fraction = (S-1)/(M+S-1). Runs inside shard_map
with the `pipe` axis manual; differentiable end-to-end (lax.scan +
ppermute have transposes), so one jax.grad over the whole pipelined
step yields correct gradients for every stage's weights.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from .mesh import PIPE_AXIS


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any,
                   x_micro: jax.Array,
                   axis_name: str = PIPE_AXIS) -> jax.Array:
    """Run microbatches through all pipeline stages.

    stage_fn(stage_params, act) -> act : applies THIS stage's chunk of
    the network (e.g. L/S transformer blocks).
    stage_params: this device's stage weights (sharded over `axis_name`
    outside shard_map).
    x_micro: (n_micro, mb, ...) microbatched input, identical on every
    stage (stage 0 is the only consumer).

    Returns (n_micro, mb, ...) outputs, valid on every stage (the last
    stage's results are broadcast back over the pipe axis with one
    psum-mask, so callers can compute loss uniformly).
    """
    n_stages = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1
    act_shape = x_micro.shape[1:]

    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        state, outputs = carry
        # stage 0 injects microbatch t (clamped; ticks >= n_micro feed
        # garbage that never reaches the output window).
        inject = x_micro[jnp.minimum(t, n_micro - 1)]
        inp = jnp.where(stage == 0, inject, state)
        out = stage_fn(stage_params, inp)
        # last stage emits microbatch t-(S-1) at tick t
        emit_idx = t - (n_stages - 1)
        is_emit = jnp.logical_and(stage == n_stages - 1, emit_idx >= 0)
        outputs = lax.cond(
            is_emit,
            lambda o: o.at[jnp.maximum(emit_idx, 0)].set(out),
            lambda o: o,
            outputs)
        state = lax.ppermute(out, axis_name, fwd_perm)
        return (state, outputs), None

    # carries become device-varying over the pipe axis on first tick;
    # start them varying (shard_map VMA typing).
    init_state = lax.pcast(jnp.zeros(act_shape, x_micro.dtype),
                           (axis_name,), to="varying")
    init_out = lax.pcast(jnp.zeros((n_micro,) + act_shape, x_micro.dtype),
                         (axis_name,), to="varying")
    (_, outputs), _ = lax.scan(tick, (init_state, init_out),
                               jnp.arange(ticks))
    # replicate results across the pipe axis: only the last stage holds
    # them; psum of a masked buffer is a broadcast.
    mask = (stage == n_stages - 1).astype(outputs.dtype)
    return lax.psum(outputs * mask, axis_name)


def stack_stage_params(layer_params: Any, n_stages: int) -> Any:
    """Reshape per-layer stacked params (L, ...) into (S, L/S, ...) so
    the leading dim can shard over the pipe axis."""
    def reshape(x):
        L = x.shape[0]
        assert L % n_stages == 0, (
            f"layer count {L} not divisible by {n_stages} stages")
        return x.reshape((n_stages, L // n_stages) + x.shape[1:])
    return jax.tree.map(reshape, layer_params)
