"""Grouped matmul for the TPU: rows that belong to different experts
multiplied by their own expert's weights in one kernel, and only the
row tiles that hold rows are computed.

    out[r] = x[r] @ w[group of r]

The caller lays the rows out in tiles of `tile_m`: group g owns
`group_rows[g]` consecutive rows, a whole number of tiles and at least
one, groups in order, and whatever is left of the buffer after the
last group is dead. A dropless expert layer needs exactly this: its
buffer has a static bound that no batch can exceed (tokens x k rows),
of which a batch fills an eighth on average, and `lax.ragged_dot`
multiplies every row of it whatever the group sizes say (on the v5e
the dead seven eighths took longer than the attention projections;
PERF.md section 6, PR 31). Here a dead tile costs one empty grid step:
its blocks name the last live tile's, so nothing is loaded either.

Three kernels under one `custom_vjp`: `x @ w[g]` (forward),
`dy @ w[g].T` (dx) and, for the weights, the sum over a group's tiles
of `x_tile.T @ dy_tile`. The row tile is innermost in every grid, so a
group's weight block stays resident while its tiles pass. bf16 in and
out with f32 accumulation, like every other matmul of the model. The
rows of dead tiles are never written: the caller masks them. Outputs
declare the varying-mesh-axes type of their inputs, so the kernels
trace inside `shard_map` with the replication checker on.

The caller (`moe.expert_share_ffn`) picks by what it observes
(`kernels_engage`): the kernels on a TPU for bf16 operands in whole
lanes, else `lax.ragged_dot`, which computes the same thing from the
same layout (the CPU tests' oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# Rows of a tile. A group's weights stay resident over its tiles, so
# the tile decides only the padding (half a tile a group on average)
# and the share of a grid step's fixed cost.
TILE_M = 256
# Elements of the weight block a grid step keeps in VMEM (bf16, double
# buffered) and of the f32 accumulator of the weight gradient.
_W_BLOCK = 2 * 1024 * 1024
_ACC_BLOCK = 1024 * 1024
_VMEM_LIMIT = 64 * 1024 * 1024
_F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))          # a @ b
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_TN = (((0,), (0,)), ((), ()))          # a.T @ b


def column_tile(columns: int, depth: int, budget: int) -> int:
    """Largest multiple of 128 that divides `columns` with `depth` x
    tile elements within `budget`; 0 where there is none."""
    for tile in range(columns // LANES * LANES, 0, -LANES):
        if columns % tile == 0 and depth * tile <= budget:
            return tile
    return 0


def supported(x_shape, w_shape, tile_m: int = TILE_M) -> bool:
    """Shapes the kernels take: rows in whole tiles, both widths of
    the weights in whole lanes and small enough for one block."""
    (m, k), (_, k2, n) = x_shape, w_shape
    return (k == k2 and m % tile_m == 0 and tile_m % LANES == 0
            and column_tile(n, k, _W_BLOCK) > 0
            and column_tile(k, n, _W_BLOCK) > 0
            and column_tile(n, k, _ACC_BLOCK) > 0)


def tile_groups(group_rows: jax.Array, n_tiles: int, tile_m: int):
    """(group of every tile (n_tiles,), live tiles (1,)) from the rows
    every group owns. Dead tiles carry the last group."""
    ends = jnp.cumsum(group_rows) // tile_m                   # (G,)
    tiles = jnp.arange(n_tiles, dtype=jnp.int32)
    group = jnp.sum(tiles[:, None] >= ends[None, :], axis=1,
                    dtype=jnp.int32)
    return (jnp.minimum(group, group_rows.shape[0] - 1),
            ends[-1:].astype(jnp.int32))


def _rows_kernel(group_ref, active_ref, x_ref, w_ref, o_ref, *, dims):
    """One row tile against one column block of its group's weights."""
    @pl.when(pl.program_id(1) < active_ref[0])
    def _():
        o_ref[...] = lax.dot_general(
            x_ref[...], w_ref[...], dims,
            preferred_element_type=_F32).astype(o_ref.dtype)


def _weights_kernel(group_ref, active_ref, x_ref, dy_ref, dw_ref, acc_ref):
    """x_tile.T @ dy_tile summed over the tiles of a group, which are
    consecutive: the accumulator starts at a group's first tile and is
    written at its last."""
    t = pl.program_id(1)
    last = active_ref[0] - 1
    group = group_ref[t]
    first_of_group = jnp.logical_or(
        t == 0, group_ref[jnp.maximum(t - 1, 0)] != group)
    last_of_group = jnp.logical_or(
        t == last, group_ref[jnp.minimum(t + 1, last)] != group)

    @pl.when(t < active_ref[0])
    def _():
        @pl.when(first_of_group)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
        acc_ref[...] += lax.dot_general(x_ref[...], dy_ref[...], _TN,
                                        preferred_element_type=_F32)

        @pl.when(last_of_group)
        def _():
            dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def _vma(*xs):
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _clamped(t, active_ref):
    """A dead tile names the last live one: no new block is loaded or
    written for it."""
    return jnp.minimum(t, active_ref[0] - 1)


def _rows_call(x, w, tile_group, active, *, tile_m, transpose_w, name,
               interpret):
    """x (M, K) @ w[g] (K, N) -> (M, N), or with `transpose_w`
    x (M, N) @ w[g].T -> (M, K)."""
    m = x.shape[0]
    _, k, n = w.shape
    if transpose_w:
        cols = column_tile(k, n, _W_BLOCK)
        w_spec = pl.BlockSpec(
            (None, cols, n),
            lambda c, t, grp, act: (grp[_clamped(t, act)], c, 0))
        out_cols, dims = k, _NT
    else:
        cols = column_tile(n, k, _W_BLOCK)
        w_spec = pl.BlockSpec(
            (None, k, cols),
            lambda c, t, grp, act: (grp[_clamped(t, act)], 0, c))
        out_cols, dims = n, _NN
    row_spec = pl.BlockSpec((tile_m, x.shape[1]),
                            lambda c, t, grp, act: (_clamped(t, act), 0))
    out_spec = pl.BlockSpec((tile_m, cols),
                            lambda c, t, grp, act: (_clamped(t, act), c))
    return pl.pallas_call(
        functools.partial(_rows_kernel, dims=dims),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(out_cols // cols, m // tile_m),
            in_specs=[row_spec, w_spec], out_specs=out_spec),
        out_shape=jax.ShapeDtypeStruct((m, out_cols), x.dtype,
                                       vma=_vma(x, w)),
        compiler_params=_params(), interpret=interpret, name=name,
    )(tile_group, active, x, w)


def _weights_call(x, dy, tile_group, active, n_groups, *, tile_m, dtype,
                  interpret):
    """sum over a group's tiles of x_tile.T @ dy_tile -> (G, K, N)."""
    m, k = x.shape
    n = dy.shape[1]
    cols = column_tile(n, k, _ACC_BLOCK)
    x_spec = pl.BlockSpec((tile_m, k),
                          lambda c, t, grp, act: (_clamped(t, act), 0))
    dy_spec = pl.BlockSpec((tile_m, cols),
                           lambda c, t, grp, act: (_clamped(t, act), c))
    dw_spec = pl.BlockSpec(
        (None, k, cols),
        lambda c, t, grp, act: (grp[_clamped(t, act)], 0, c))
    return pl.pallas_call(
        _weights_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // cols, m // tile_m),
            in_specs=[x_spec, dy_spec], out_specs=dw_spec,
            scratch_shapes=[pltpu.VMEM((k, cols), _F32)]),
        out_shape=jax.ShapeDtypeStruct((n_groups, k, n), dtype,
                                       vma=_vma(x, dy)),
        compiler_params=_params(), interpret=interpret,
        name="hvd_grouped_matmul_dw",
    )(tile_group, active, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _grouped(x, w, tile_group, active, tile_m, interpret):
    return _rows_call(x, w, tile_group, active, tile_m=tile_m,
                      transpose_w=False, name="hvd_grouped_matmul_fwd",
                      interpret=interpret)


def _grouped_fwd(x, w, tile_group, active, tile_m, interpret):
    return (_grouped(x, w, tile_group, active, tile_m, interpret),
            (x, w, tile_group, active))


def _grouped_bwd(tile_m, interpret, residuals, dy):
    x, w, tile_group, active = residuals
    dx = _rows_call(dy, w, tile_group, active, tile_m=tile_m,
                    transpose_w=True, name="hvd_grouped_matmul_dx",
                    interpret=interpret)
    dw = _weights_call(x, dy, tile_group, active, w.shape[0],
                       tile_m=tile_m, dtype=w.dtype, interpret=interpret)
    return dx, dw, None, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul_kernels(x: jax.Array, w: jax.Array,
                           group_rows: jax.Array, *,
                           tile_m: int = TILE_M,
                           interpret: bool = False) -> jax.Array:
    """The kernels' path: x (M, K), w (G, K, N), group_rows (G,) int32,
    every entry a positive multiple of `tile_m`, their sum at most M.
    Rows past the last group come back unwritten."""
    if not supported(x.shape, w.shape, tile_m):
        raise ValueError(
            f"grouped matmul does not take x {x.shape}, w {w.shape} at "
            f"tiles of {tile_m} rows: it needs rows in whole tiles and "
            f"both widths of w in multiples of {LANES}")
    tile_group, active = tile_groups(group_rows, x.shape[0] // tile_m,
                                     tile_m)
    # Replicated weights meet rows that vary over the data axes: typed
    # varying here, so that the weight gradient the backward kernel
    # gives is summed over those axes by the cast's own transpose.
    missing = tuple(_vma(x, group_rows) - jax.typeof(w).vma)
    if missing:
        w = lax.pcast(w, missing, to="varying")
    return _grouped(x, w, tile_group, active, int(tile_m), bool(interpret))


def kernels_engage(x: jax.Array, w: jax.Array, tile_m: int = TILE_M) -> bool:
    """The engagement rule, on what the call observes: TPU backend,
    bf16 operands, shapes the kernels take."""
    return (jax.default_backend() == "tpu"
            and x.dtype == w.dtype == jnp.bfloat16
            and supported(x.shape, w.shape, tile_m))
