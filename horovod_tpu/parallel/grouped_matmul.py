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

Two `custom_vjp`s. The plain product (`grouped_matmul_kernels`) is
three kernels: `x @ w[g]` (forward), `dy @ w[g].T` (dx) and, for the
weights, the sum over a group's tiles of `x_tile.T @ dy_tile` (dw).
The expert's gate and up projections (`grouped_swiglu_kernels`) are a
pair that keeps the SwiGLU inside: one forward kernel that multiplies
a row tile by the same column block of both weights and writes both
products (the backward's residuals) and silu(gate) * up from them as
they are stored; a backward that walks the same tile table three
times: the activation's cotangent back through the SwiGLU to the two
products' cotangents (elementwise, over the products' own buffers),
`d_hg @ w_gate[g].T + d_hu @ w_up[g].T` as one f32 sum and one write,
and dw for each weight. Nothing between two kernels is XLA's, which
knows only the buffer's static shape: its loop fusions and the `add`
of two dx walked the dead tiles too and took longer than the matmuls'
share of them (PERF.md section 6, PR 38).

The row tile is innermost in every grid, so a group's weight block
stays resident while its tiles pass. bf16 in and out with f32
accumulation, like every other matmul of the model. The rows of dead
tiles are never written and never read: whatever they hold reaches
no live row and no weight gradient. Outputs declare the
varying-mesh-axes type of their inputs, so the kernels trace inside
`shard_map` with the replication checker on.

The caller (`moe.expert_share_ffn`) picks by what it observes
(`kernels_engage`): the kernels on a TPU for bf16 operands in whole
lanes, else `lax.ragged_dot` and `swiglu`, which compute the same
thing from the same layout (the CPU tests' oracle).
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
# Elements of a block of the SwiGLU's backward, which is elementwise:
# five such blocks in bf16, double buffered, and their f32 temporaries.
_ACT_BLOCK = 512 * 1024
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


def swiglu(h_gate, h_up):
    """silu(h_gate) * h_up in f32, from the two products as stored."""
    return jax.nn.silu(h_gate.astype(_F32)) * h_up.astype(_F32)


def _swiglu_kernel(group_ref, active_ref, x_ref, wg_ref, wu_ref, hg_ref,
                   hu_ref, act_ref):
    """One row tile against the same column block of its group's gate
    and up weights: both products as the backward's residuals, and the
    activation from them as they are stored."""
    @pl.when(pl.program_id(1) < active_ref[0])
    def _():
        x = x_ref[...]
        for w_ref, h_ref in ((wg_ref, hg_ref), (wu_ref, hu_ref)):
            h_ref[...] = lax.dot_general(
                x, w_ref[...], _NN,
                preferred_element_type=_F32).astype(h_ref.dtype)
        act_ref[...] = swiglu(hg_ref[...], hu_ref[...]).astype(act_ref.dtype)


def _swiglu_dh_kernel(group_ref, active_ref, dact_ref, hg_ref, hu_ref,
                      dhg_ref, dhu_ref):
    """The activation's cotangent back through the SwiGLU, in f32:
    d silu(h) = s (1 + h (1 - s)) with s = sigmoid(h)."""
    @pl.when(pl.program_id(1) < active_ref[0])
    def _():
        h_gate, h_up, d_act = (ref[...].astype(_F32)
                               for ref in (hg_ref, hu_ref, dact_ref))
        s = jax.nn.sigmoid(h_gate)
        dhg_ref[...] = (d_act * h_up * (s * (1.0 + h_gate * (1.0 - s)))
                        ).astype(dhg_ref.dtype)
        dhu_ref[...] = (d_act * (h_gate * s)).astype(dhu_ref.dtype)


def _swiglu_dx_kernel(group_ref, active_ref, dhg_ref, dhu_ref, wg_ref,
                      wu_ref, dx_ref):
    """d_hg @ w_gate[g].T + d_hu @ w_up[g].T: one f32 sum, one write."""
    def product(d_ref, w_ref):
        return lax.dot_general(d_ref[...], w_ref[...], _NT,
                               preferred_element_type=_F32)

    @pl.when(pl.program_id(1) < active_ref[0])
    def _():
        dx_ref[...] = (product(dhg_ref, wg_ref) + product(dhu_ref, wu_ref)
                       ).astype(dx_ref.dtype)


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


def _clamped(t, active_ref):
    """A dead tile names the last live one: no new block is loaded or
    written for it."""
    return jnp.minimum(t, active_ref[0] - 1)


def _tile_spec(tile_m, cols, whole=False):
    """Of row tile t its column block c, or its `whole` width."""
    return pl.BlockSpec(
        (tile_m, cols),
        lambda c, t, grp, act: (_clamped(t, act), 0 if whole else c))


def _weight_spec(rows, cols, by_rows=False):
    """Of the weights of tile t's group column block c, or row block c
    (`by_rows`: the block of w that column block c of w.T is)."""
    return pl.BlockSpec(
        (None, rows, cols),
        lambda c, t, grp, act: (grp[_clamped(t, act)],
                                *((c, 0) if by_rows else (0, c))))


def _tiles_call(kernel, name, blocks, tile_group, active, operands,
                in_specs, out_specs, out_shapes, *, interpret,
                scratch_shapes=(), reuse=None):
    """`kernel` over a grid of (column blocks, row tiles), the row tile
    innermost so that a group's weight block stays resident while its
    tiles pass. out_shapes: ((shape, dtype), ...), one or several;
    `reuse`: {operand: the output that takes its buffer}."""
    vma = _vma(*operands)
    outs = [jax.ShapeDtypeStruct(shape, dtype, vma=vma)
            for shape, dtype in out_shapes]
    single = len(outs) == 1
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(blocks, tile_group.shape[0]),
            in_specs=in_specs,
            out_specs=out_specs[0] if single else out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=outs[0] if single else outs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        input_output_aliases={2 + i: o for i, o in (reuse or {}).items()},
        interpret=interpret, name=name,
    )(tile_group, active, *operands)


def _rows_call(x, w, tile_group, active, *, tile_m, transpose_w, name,
               interpret):
    """x (M, K) @ w[g] (K, N) -> (M, N), or with `transpose_w`
    x (M, N) @ w[g].T -> (M, K)."""
    _, k, n = w.shape
    if transpose_w:
        cols = column_tile(k, n, _W_BLOCK)
        w_spec, out_cols, dims = _weight_spec(cols, n, by_rows=True), k, _NT
    else:
        cols = column_tile(n, k, _W_BLOCK)
        w_spec, out_cols, dims = _weight_spec(k, cols), n, _NN
    return _tiles_call(
        functools.partial(_rows_kernel, dims=dims), name, out_cols // cols,
        tile_group, active, (x, w),
        [_tile_spec(tile_m, x.shape[1], whole=True), w_spec],
        [_tile_spec(tile_m, cols)], [((x.shape[0], out_cols), x.dtype)],
        interpret=interpret)


def _weights_call(x, dy, tile_group, active, n_groups, *, tile_m, dtype,
                  interpret):
    """sum over a group's tiles of x_tile.T @ dy_tile -> (G, K, N)."""
    k, n = x.shape[1], dy.shape[1]
    cols = column_tile(n, k, _ACC_BLOCK)
    return _tiles_call(
        _weights_kernel, "hvd_grouped_matmul_dw", n // cols, tile_group,
        active, (x, dy),
        [_tile_spec(tile_m, k, whole=True), _tile_spec(tile_m, cols)],
        [_weight_spec(k, cols)], [((n_groups, k, n), dtype)],
        interpret=interpret, scratch_shapes=[pltpu.VMEM((k, cols), _F32)])


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


def _swiglu_products(x, w_gate, w_up, tile_group, active, tile_m,
                     interpret):
    """(x @ w_gate[g], x @ w_up[g], their SwiGLU), each (M, F)."""
    _, k, n = w_gate.shape
    cols = column_tile(n, k, _W_BLOCK)
    return _tiles_call(
        _swiglu_kernel, "hvd_grouped_matmul_swiglu_fwd", n // cols,
        tile_group, active, (x, w_gate, w_up),
        [_tile_spec(tile_m, k, whole=True)] + 2 * [_weight_spec(k, cols)],
        3 * [_tile_spec(tile_m, cols)], 3 * [((x.shape[0], n), x.dtype)],
        interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _swiglu(x, w_gate, w_up, tile_group, active, tile_m, interpret):
    return _swiglu_products(x, w_gate, w_up, tile_group, active, tile_m,
                            interpret)[2]


def _swiglu_fwd(x, w_gate, w_up, tile_group, active, tile_m, interpret):
    h_gate, h_up, act = _swiglu_products(x, w_gate, w_up, tile_group,
                                         active, tile_m, interpret)
    return act, (x, w_gate, w_up, h_gate, h_up, tile_group, active)


def _swiglu_bwd(tile_m, interpret, residuals, d_act):
    x, w_gate, w_up, h_gate, h_up, tile_group, active = residuals
    _, k, n = w_gate.shape
    cols = column_tile(n, tile_m, _ACT_BLOCK)
    d_hg, d_hu = _tiles_call(
        _swiglu_dh_kernel, "hvd_grouped_matmul_swiglu_dh", n // cols,
        tile_group, active, (d_act, h_gate, h_up),
        3 * [_tile_spec(tile_m, cols)], 2 * [_tile_spec(tile_m, cols)],
        2 * [(d_act.shape, d_act.dtype)], interpret=interpret,
        reuse={1: 0, 2: 1})             # each cotangent over its product
    cols = column_tile(k, n, _W_BLOCK)
    dx = _tiles_call(
        _swiglu_dx_kernel, "hvd_grouped_matmul_swiglu_dx", k // cols,
        tile_group, active, (d_hg, d_hu, w_gate, w_up),
        2 * [_tile_spec(tile_m, n, whole=True)]
        + 2 * [_weight_spec(cols, n, by_rows=True)],
        [_tile_spec(tile_m, cols)], [(x.shape, x.dtype)],
        interpret=interpret)
    dw_gate, dw_up = (
        _weights_call(x, d_h, tile_group, active, w_gate.shape[0],
                      tile_m=tile_m, dtype=w_gate.dtype,
                      interpret=interpret) for d_h in (d_hg, d_hu))
    return dx, dw_gate, dw_up, None, None


_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def _laid_out(x, group_rows, tile_m, *weights):
    """(tile_group, active, the weights typed for the call), the
    shapes checked."""
    rows_vary = _vma(x, group_rows)
    typed = []
    for w in weights:
        if not supported(x.shape, w.shape, tile_m):
            raise ValueError(
                f"grouped matmul does not take x {x.shape}, w {w.shape} "
                f"at tiles of {tile_m} rows: it needs rows in whole tiles "
                f"and both widths of w in multiples of {LANES}")
        # Replicated weights meet rows that vary over the data axes:
        # typed varying here, so that the weight gradient the backward
        # kernel gives is summed over those axes by the cast's own
        # transpose.
        missing = tuple(rows_vary - jax.typeof(w).vma)
        typed.append(lax.pcast(w, missing, to="varying") if missing else w)
    return (*tile_groups(group_rows, x.shape[0] // tile_m, tile_m), typed)


def grouped_matmul_kernels(x: jax.Array, w: jax.Array,
                           group_rows: jax.Array, *,
                           tile_m: int = TILE_M,
                           interpret: bool = False) -> jax.Array:
    """The kernels' path: x (M, K), w (G, K, N), group_rows (G,) int32,
    every entry a positive multiple of `tile_m`, their sum at most M.
    Rows past the last group come back unwritten."""
    tile_group, active, (w,) = _laid_out(x, group_rows, tile_m, w)
    return _grouped(x, w, tile_group, active, int(tile_m), bool(interpret))


def grouped_swiglu_kernels(x: jax.Array, w_gate: jax.Array,
                           w_up: jax.Array, group_rows: jax.Array, *,
                           tile_m: int = TILE_M,
                           interpret: bool = False) -> jax.Array:
    """silu(x @ w_gate[g]) * (x @ w_up[g]) over the layout of
    `grouped_matmul_kernels`, x (M, K) and both weights (G, K, F):
    each product rounded to x's dtype, the SwiGLU in f32 from those,
    the result (M, F) rounded once. Rows past the last group come back
    unwritten, and their cotangent is neither read nor written."""
    tile_group, active, (w_gate, w_up) = _laid_out(x, group_rows, tile_m,
                                                   w_gate, w_up)
    return _swiglu(x, w_gate, w_up, tile_group, active, int(tile_m),
                   bool(interpret))


def kernels_engage(x: jax.Array, w: jax.Array, tile_m: int = TILE_M) -> bool:
    """The engagement rule, on what the call observes: TPU backend,
    bf16 operands, shapes the kernels take."""
    return (jax.default_backend() == "tpu"
            and x.dtype == w.dtype == jnp.bfloat16
            and supported(x.shape, w.shape, tile_m))
