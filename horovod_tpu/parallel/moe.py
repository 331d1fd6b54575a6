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

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..metrics import REGISTRY as _METRICS
from . import row_movers as rm
from .mesh import EXPERT_AXIS


_m_moe_traces = _METRICS.counter(
    "hvd_moe_traces_total",
    "Times an expert layer was traced, by the dispatch it took: "
    "sorted_live_tiles (expert_share_ffn: pairs sorted by expert, "
    "their rows moved by the Pallas row movers and multiplied by the "
    "Pallas grouped matmuls, both over the live tiles only), "
    "sorted_ragged (the same with gathers over the whole buffer and "
    "lax.ragged_dot: off the TPU, f32, shapes the kernels do not "
    "take) or onehot_capacity (top1_route's (T, E, C) einsum).",
    ("dispatch",))
_m_moe_bound = _METRICS.gauge(
    "hvd_moe_pairs_bound",
    "Rows of the buffer the last traced expert_share_ffn gathers its "
    "(token, expert) pairs into: a static bound, at most tokens x "
    "min(k, experts held), which no batch can exceed.")
_m_moe_routers = _METRICS.counter(
    "hvd_moe_router_traces_total",
    "Times an expert layer's router was traced, by its kind: sigmoid "
    "(topk_sigmoid_route: sigmoid scores, bias-corrected top-k, gates "
    "normalised and scaled) or softmax (topk_softmax_route: a softmax "
    "over the router's width, top-k, gates renormalised).",
    ("router",))


def top1_route(logits: jax.Array, n_experts: int, capacity: int
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Switch-style top-1 routing with capacity.

    logits: (T, E). Returns (dispatch (T, E, C) one-hot, combine
    (T, E, C) weights, aux_loss scalar)."""
    _m_moe_traces.labels(dispatch="onehot_capacity").inc()
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


# ---------------------------------------------------------------------------
# Dropless top-k routing for one chip's share of the experts
# ---------------------------------------------------------------------------
#
# The layer is told which experts it holds (`first`, and as many as its
# weights stack) and how wide the router is. It scores every expert,
# chooses k a token, and computes the chosen (token, expert) pairs
# whose expert it holds: pairs sorted by expert, their rows gathered
# into one buffer of a static bound, grouped matmuls over it
# (`grouped_matmul.py`: on the TPU kernels that compute only the row
# tiles that hold rows), gathered back with the gates.
# No (T, E, C) one-hot and no capacity: a pair is left out only if
# the buffer's bound is set below what a batch can send, and then it
# is counted, never silent. With a live `expert` axis the rows would
# be exchanged between the chips first; that exchange is not written
# yet (ROADMAP B7), and nothing here stands in for it.

def topk_sigmoid_route(logits: jax.Array, bias: jax.Array, k: int,
                       scale: float) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid scores, the k experts with the largest score + bias a
    token, and their gates: the chosen scores (without the bias)
    normalised to sum 1 and multiplied by `scale` (DeepSeek-V3's
    `noaux_tc` with one group). logits: (T, E) float32, bias: (E,),
    which only moves the choice and gets no gradient. Returns
    (experts (T, k) int32, gates (T, k) float32)."""
    _m_moe_routers.labels(router="sigmoid").inc()
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, experts = lax.top_k(scores + lax.stop_gradient(bias), k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    gates = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), gates * scale


def topk_softmax_route(logits: jax.Array, k: int
                       ) -> Tuple[jax.Array, jax.Array]:
    """A softmax over every expert of the router, in float32, the k
    most probable experts a token, and their gates: the chosen
    probabilities divided by their sum (Qwen-MoE's and DeepSeek-V2's
    `norm_topk_prob`). logits: (T, E). Returns (experts (T, k) int32,
    gates (T, k) float32); the gradient reaches the logits through the
    gates."""
    _m_moe_routers.labels(router="softmax").inc()
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, experts = lax.top_k(probs, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), gates


def max_pairs(tokens: int, k: int, held: int) -> int:
    """The most (token, expert) pairs that can land on `held` experts:
    a token chooses k different experts."""
    return tokens * min(k, held)


# Dispatch and combine move whole rows in both directions: pair p =
# (token t, choice j) sits in buffer row slots[t, j] where landed[t, j],
# and buffer row s holds pair order[s] where valid[s]. Left to
# autodiff, each gather's transpose is a scatter-add of tens of
# thousands of rows, which the TPU serialises (PERF.md, PR 31: 4.6 ms a
# layer each); written out, the transposes are gathers too. Where the
# grouped matmuls' kernels engage (`tile` is the buffer's tile), the
# rows are moved by `row_movers.py`: `rows_in` fills the rows of the
# live tiles that hold a pair (zero in a live tile's padding, dead
# tiles unwritten), `rows_out` and `rows_dot` read the landed pairs'
# rows only, so the work follows the batch's routing and not the
# buffer's bound (PERF.md, PR 36). Else (`tile` None: off the TPU, f32 callers) the
# same as `jnp` gathers over the whole buffer, one a choice j, T rows
# each: a (T, k, D) intermediate would be tiled with k on the sublanes
# and copied to be summed.

class _Routing(NamedTuple):
    """Where a batch's pairs sit. order, valid: (rows,) of the buffer;
    slots, landed: (T, k) of the pairs; live: (1,) the rows of the
    tiles that hold a group, the rest of the buffer is dead."""
    order: jax.Array
    valid: jax.Array
    slots: jax.Array
    landed: jax.Array
    live: jax.Array


def _choice_rows(x, route, j):
    """x[slots[:, j]] in float32, zero where choice j did not land."""
    return jnp.where(route.landed[:, j, None], x[route.slots[:, j]],
                     0).astype(jnp.float32)


def _token_of_row(route):
    """Buffer row -> the token whose pair it holds, -1 where none."""
    return jnp.where(route.valid, route.order // route.slots.shape[1], -1)


def _row_of_pair(route):
    """(T, k) pair -> its buffer row, -1 where it did not land."""
    return jnp.where(route.landed, route.slots, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _permute(tokens, route, tile):
    """tokens (T, D) -> the dispatch buffer (rows, D): row s holds the
    token of pair order[s]. A row of a live tile that holds no pair is
    finite (some token's row, or zero from the kernel), and nothing
    downstream reads what comes of it; a dead tile's rows are
    unwritten where the kernel moves the rows."""
    if tile is None:
        return tokens[route.order // route.slots.shape[1]]
    return rm.rows_in(rm.pack_rows(tokens), _token_of_row(route), route.live,
                      width=tokens.shape[1], tile_m=tile)


def _permute_fwd(tokens, route, tile):
    return _permute(tokens, route, tile), route


def _permute_bwd(tile, route, d_xs):
    if tile is None:
        d_tokens = sum(_choice_rows(d_xs, route, j)
                       for j in range(route.slots.shape[1])
                       ).astype(d_xs.dtype)
    else:
        d_tokens = rm.rows_out(d_xs, _row_of_pair(route))
    return d_tokens, None


_permute.defvjp(_permute_fwd, _permute_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _unpermute(ys, gates, route, tile):
    """The gated sum back at the tokens, float32: out[t] = sum over the
    landed choices j of gates[t, j] * ys[slots[t, j]]. ys: (rows, D),
    gates: (T, k). Only rows that hold a pair are read."""
    if tile is None:
        return sum(gates[:, j, None] * _choice_rows(ys, route, j)
                   for j in range(gates.shape[1]))
    return rm.rows_out(ys, _row_of_pair(route), gates,
                       out_dtype=jnp.float32)


def _unpermute_fwd(ys, gates, route, tile):
    return _unpermute(ys, gates, route, tile), (ys, gates, route)


def _unpermute_bwd(tile, res, d_out):
    """d_ys is exactly zero in every row that holds no pair: that is
    what keeps such rows out of the weights' gradients."""
    ys, gates, route = res
    k = gates.shape[1]
    row_gate = jnp.where(route.valid, gates.reshape(-1)[route.order], 0.0)
    if tile is None:
        d_ys = d_out.astype(ys.dtype)[route.order // k].astype(
            jnp.float32) * row_gate[:, None]
        d_gates = jnp.stack(
            [jnp.sum(_choice_rows(ys, route, j) * d_out, axis=-1)
             for j in range(k)], axis=1)
        return d_ys.astype(ys.dtype), d_gates, None
    # the products first: they are the last reader of ys, whose buffer
    # is then free for d_ys (the order the gathers above leave XLA, too)
    d_gates = rm.rows_dot(ys, _row_of_pair(route), d_out)
    d_ys = rm.rows_in(rm.pack_rows(d_out), _token_of_row(route), route.live,
                      width=ys.shape[1], tile_m=tile, scale=row_gate)
    return d_ys, d_gates, None


_unpermute.defvjp(_unpermute_fwd, _unpermute_bwd)


def expert_share_ffn(tokens: jax.Array, experts: jax.Array,
                     gates: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                     w_down: jax.Array, first: int,
                     tile_m: Optional[int] = None) -> jax.Array:
    """The held experts' part of sum_i gate_i * E_i(token), with
    E(h) = (silu(h W_g) * (h W_u)) W_d.

    tokens: (T, D); experts, gates: (T, k) from a top-k router over its
    full width (`topk_sigmoid_route`, `topk_softmax_route`); w_gate /
    w_up: (held, D, F), w_down: (held, F, D): expert `first + i` of the
    router is row i. The dispatch buffer takes `max_pairs` pairs, which
    no batch can exceed: no pair is dropped. `tile_m`: rows of a tile
    of the buffer (`grouped_matmul.TILE_M` if None); a group costs its
    whole tiles, so a tile well above the rows an expert expects makes
    a step's time follow the batch's routing less. Returns the output,
    (T, D) float32."""
    from ..tracing import device_scope
    from . import grouped_matmul as gm
    T, D = tokens.shape
    k = experts.shape[1]
    held = w_gate.shape[0]
    bound = max_pairs(T, k, held)
    # The buffer: every group in whole tiles and at least one, so that
    # a row tile of the grouped matmul belongs to one expert.
    tile = tile_m or gm.TILE_M
    n_rows = -(-bound // tile) * tile + held * tile
    # one rule for the grouped matmuls and the row movers
    kernels = gm.kernels_engage(
        jax.ShapeDtypeStruct((n_rows, D), tokens.dtype), w_gate, tile
    ) and rm.supported(T, k, D)
    _m_moe_traces.labels(dispatch="sorted_live_tiles" if kernels
                         else "sorted_ragged").inc()
    _m_moe_bound.set(bound)
    i32 = jnp.int32

    with device_scope("hvd.moe.route"):
        local = experts.reshape(-1) - first                   # (T * k,)
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held).astype(i32)
        pairs = jnp.arange(T * k, dtype=i32)
        _, by_expert = lax.sort((key, pairs), num_keys=1)     # stable
        _, position = lax.sort((by_expert, pairs), num_keys=1)  # inverse
        sizes = jnp.sum(key[:, None] == jnp.arange(held, dtype=i32),
                        axis=0, dtype=i32)                    # (held,)
        # the groups as the buffer holds them: every group padded to
        # whole tiles
        starts = jnp.cumsum(sizes) - sizes
        padded = jnp.maximum(-(-sizes // tile) * tile, tile)
        padded_starts = jnp.cumsum(padded) - padded
        # pair -> buffer row
        group = jnp.minimum(key, held - 1)
        landed = mine.reshape(T, k)
        slots = jnp.where(
            mine, padded_starts[group] + position - starts[group], 0
        ).reshape(T, k)
        # buffer row -> pair
        row = jnp.arange(n_rows, dtype=i32)
        tile_group, live_tiles = gm.tile_groups(padded, n_rows // tile,
                                                tile)
        row_group = tile_group[row // tile]
        rank = row - padded_starts[row_group]
        valid = (rank >= 0) & (rank < sizes[row_group])
        order = by_expert[jnp.clip(starts[row_group] + rank, 0, T * k - 1)]
        route = _Routing(order, valid, slots, landed, live_tiles * tile)
        mover_tile = tile if kernels else None
        xs = _permute(tokens, route, mover_tile)             # (n_rows, D)

    with device_scope("hvd.moe.experts"):
        # bf16 in and out, f32 accumulation inside, like every other
        # matmul of the model; the SwiGLU and the gated sum are f32.
        # No mask on these buffers: a row that holds no pair is a dead
        # tile's, which the kernels neither write, compute nor read
        # back (the SwiGLU and its backward run inside them, on live
        # tiles alone), or padding inside a live tile, finite, whose
        # outputs no pair reads and whose cotangent `_unpermute` makes
        # exactly zero.
        if kernels:
            act = gm.grouped_swiglu_kernels(xs, w_gate, w_up, padded,
                                            tile_m=tile)
            ys = gm.grouped_matmul_kernels(act, w_down, padded, tile_m=tile)
        else:
            act = gm.swiglu(lax.ragged_dot(xs, w_gate, padded),
                            lax.ragged_dot(xs, w_up, padded)
                            ).astype(tokens.dtype)
            ys = lax.ragged_dot(act, w_down, padded)

    with device_scope("hvd.moe.route"):
        out = _unpermute(ys, gates, route, mover_tile)
    return out
