"""Row movers of the dropless expert layer for the TPU: the rows that
go from the tokens into the dispatch buffer and from the buffer back
to the tokens, moved a row at a time by what the batch's routing says.

    rows_in:   out[s] = src[index[s]]                          (index[s] >= 0)
    rows_out:  out[t] = sum_j gates[t, j] * buf[code[t, j]]    (code[t, j] >= 0)
    rows_dot:  out[t, j] = <buf[code[t, j]], against[t]>       (code[t, j] >= 0)

The buffer has a static bound no batch can exceed, of which a batch
fills an eighth or less (`moe.expert_share_ffn`). A gather XLA writes
moves every row of the bound. Here a grid step walks the codes that
name a source and no other: a group's rows come first in its tiles,
and `rows_out` / `rows_dot` sort a step's landed pairs to the front (a
stable sort of a thousand keys a step, which keeps the order of
(t, j)). A grid step of `rows_in` past the live tiles
(`grouped_matmul.tile_groups`) costs nothing: its blocks name the last
live step's and its rows are never written, as the grouped matmuls
leave theirs.

The source stays in HBM (`memory_space=pl.ANY`) and a row costs a DMA.
A slice of a tiled array in HBM is whole tiles of 8 rows, so the two
directions differ. Into the buffer the source is the tokens, a small
array: `pack_rows` lays every row out as tiles of its own (two bf16
columns a 32-bit word), and a row is fetched alone, straight to the
place in VMEM it is unpacked from; the combine's backward takes them
scaled by a number a row (`rows_in(scale=)`). Out of the buffer, which
the grouped matmuls wrote as they write, a row is fetched as its group
of `GROUP` rows through the 32-bit view of two rows a word, `RING`
fetches in flight, and picked out of the group in VMEM into an f32
stage that starts at zero: the gated sum adds a token's choices in the
order j = 0..k-1, and what a scratch held before is never read.
`rows_dot` picks the same rows and multiplies each with its token's
row of an f32 block, unrounded: the gated sum's gradient to its gates.

Outputs declare the varying-mesh-axes type of their inputs, as the
grouped matmuls do. `moe.expert_share_ffn` engages these where it
engages those; off the TPU its `jnp` gathers compute the same.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .grouped_matmul import LANES, _vma

GROUP = 8            # rows of a tile in HBM: the least a DMA may slice
CODES = 1024         # int32s of a block in SMEM: XLA's tile of a 1D array
STEP_ROWS = 256      # destination rows of a grid step, at most
RING = 32            # fetches out of the buffer in flight
CHUNK = 256          # lanes of a packed row's chunk: two columns a word
MAX_CHOICES = 8      # choices a token: CODES / (the least STEP_ROWS, 128)
_VMEM_LIMIT = 64 * 1024 * 1024
_F32, _I32, _U32 = jnp.float32, jnp.int32, jnp.uint32


def supported(tokens: int, k: int, width: int) -> bool:
    """Shapes the movers take, which their caller checks: tokens in
    whole 16-row tiles of a bf16 block, rows in whole lanes of packed
    words (the last chunk may be half full), at most `MAX_CHOICES`."""
    return (tokens % 16 == 0 and width % (2 * LANES) == 0
            and 1 <= k <= MAX_CHOICES)


def step_rows(rows: int, codes_a_row: int = 1) -> int:
    """Destination rows of a grid step: the largest divisor of `rows`
    within `STEP_ROWS` whose codes fit one block of SMEM."""
    return math.gcd(rows, min(STEP_ROWS, CODES // codes_a_row))


def _div(x, by: int):
    """x // by for x >= 0. `//` and `%` go through `lax.sign`, whose
    Mosaic lowering re-traces it and, for a value that varies over a
    mesh axis, meets a `pvary` it cannot lower."""
    return lax.div(x, jnp.int32(by))


def _loop(n, body, carry=0):
    """`lax.fori_loop` over 0..n with an int32 index whatever x64 says."""
    return lax.fori_loop(jnp.int32(0), jnp.asarray(n, _I32), body, carry)


def _halves(word):
    """The two bf16 columns of a packed word, as float32."""
    return (pltpu.bitcast(word << 16, _F32),
            pltpu.bitcast(word & jnp.uint32(0xFFFF0000), _F32))


def _pack_kernel(x_ref, o_ref):
    half = x_ref.shape[1] // 2
    # a bf16 is the high half of its float32
    bits = pltpu.bitcast(x_ref[...].astype(jnp.bfloat16).astype(_F32), _U32)
    word = (bits[:, :half] >> 16) | (bits[:, half:] & jnp.uint32(0xFFFF0000))
    for c in range(0, half, CHUNK):          # the last may be half full
        o_ref[:, c // CHUNK, :min(CHUNK, half - c)] = word[:, c:c + CHUNK]


def pack_rows(src: jax.Array) -> jax.Array:
    """(T, D) bf16, or f32 to be rounded to it -> (T, 8 x, 256) uint32:
    column c of a row's first half in the low bits of word c, column c
    of its second half in the high bits, D / 512 chunks of 256 words,
    the last half full if D / 256 is odd (padding chunks up to whole
    tiles of 8, and a half-full chunk's high lanes, are never written
    or read). A row is then tiles of its own, which a DMA fetches alone,
    and a word unpacks into lanes that stay where they are."""
    tokens, width = src.shape
    rows = step_rows(tokens)
    chunks = -(-width // (2 * CHUNK) // GROUP) * GROUP
    return pl.pallas_call(
        _pack_kernel, grid=(tokens // rows,),
        in_specs=[pl.BlockSpec((rows, width), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, chunks, CHUNK), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((tokens, chunks, CHUNK), _U32,
                                       vma=_vma(src)),
        compiler_params=_params(), name="hvd_moe_rows_pack")(src)


def _in_kernel(live_ref, count_ref, code_ref, src_ref, *refs):
    """One grid step of `rows_in`: its first `count` rows each take
    the packed row their code names, straight into the stage."""
    scale_ref = refs[0] if len(refs) == 4 else None
    o_ref, stage, sem = refs[-3:]
    rows, half = o_ref.shape[0], o_ref.shape[1] // 2
    step = pl.program_id(0)
    count = count_ref[step]

    def fetch(e):
        return pltpu.make_async_copy(src_ref.at[code_ref[e]], stage.at[e],
                                     sem)

    @pl.when(step < live_ref[0])
    def _():
        _loop(count, lambda e, c: (fetch(e).start(), c)[1])
        _loop(count, lambda e, c: (fetch(e).wait(), c)[1])
        # what the stage held before stays behind the mask
        filled = lax.broadcasted_iota(_I32, (rows, 1), 0) < count
        for c in range(-(-half // CHUNK)):
            low, high = (jnp.where(filled, x, 0.0)
                         for x in _halves(stage[:, c, :]))
            if scale_ref is not None:
                low, high = low * scale_ref[...], high * scale_ref[...]
            at, n = c * CHUNK, min(CHUNK, half - c * CHUNK)
            o_ref[:, at:at + n] = low[:, :n].astype(o_ref.dtype)
            o_ref[:, half + at:half + at + n] = high[:, :n].astype(o_ref.dtype)


def _lane_sum(x):
    """(1, D) -> (1, LANES): the lane-wide chunks of a row added up."""
    parts = [x[:, c:c + LANES] for c in range(0, x.shape[1], LANES)]
    while len(parts) > 1:
        parts = [a + b for a, b in zip(parts[::2], parts[1::2])] + (
            parts[-1:] if len(parts) % 2 else [])
    return parts[0]


def _out_kernel(count_ref, code_ref, pair_ref, *refs, k, mode):
    """One grid step of `rows_out` / `rows_dot`: its first `count`
    codes each name a row of the buffer, fetched as its group of rows
    and picked out. `stage` is the step's f32 result: a row a token
    (the sum), or a row a pair (the product, its lanes not yet added
    up)."""
    side_ref = refs[0] if mode else None
    src_ref, o_ref, scr, stage, sems = refs[-5:]
    # two rows of the 16-bit buffer share a 32-bit word of the tile
    src = src_ref.bitcast(_U32)
    words = scr.shape[1]
    count = count_ref[pl.program_id(0)]

    def fetch(e):
        slot = lax.rem(e, jnp.int32(RING))
        group = pl.multiple_of(_div(code_ref[e], GROUP) * words, words)
        return pltpu.make_async_copy(src.at[pl.ds(group, words)],
                                     scr.at[slot], sems.at[slot])

    def one(e, carry):
        fetch(e).wait()
        at = lax.rem(code_ref[e], jnp.int32(GROUP))
        word = scr[lax.rem(e, jnp.int32(RING)), pl.ds(_div(at, 2), 1), :]
        shift = (lax.rem(at, jnp.int32(2)) * 16).astype(_U32)
        row = pltpu.bitcast((word >> shift) << 16, _F32)
        token = pl.ds(_div(pair_ref[e], k), 1)
        if mode == "dot":
            stage[pl.ds(pair_ref[e], 1), :] = _lane_sum(
                row * side_ref[token, :])
        else:
            stage[token, :] += side_ref[e] * row if mode == "gate" else row

        @pl.when(e + RING < count)
        def _():
            fetch(e + RING).start()
        return carry

    stage[...] = jnp.zeros_like(stage)
    _loop(jnp.minimum(count, RING), lambda e, c: (fetch(e).start(), c)[1])
    _loop(count, one)
    if mode == "dot":
        o_ref[...] = jnp.sum(stage[...], axis=1, keepdims=True)
    else:
        o_ref[...] = stage[...].astype(o_ref.dtype)


def _step_blocks(codes: jax.Array, dtype=_I32) -> jax.Array:
    """(steps, codes a step) -> (steps x CODES,): every grid step's
    codes at the front of a block of its own."""
    return jnp.pad(codes.astype(dtype),
                   ((0, 0), (0, CODES - codes.shape[1]))).reshape(-1)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=_VMEM_LIMIT)


def rows_in(packed: jax.Array, index: jax.Array, live_rows: jax.Array, *,
            width: int, tile_m: int,
            scale: Optional[jax.Array] = None) -> jax.Array:
    """out[s] = src[index[s]] in bf16 for the rows s < live_rows[0], a
    whole number of tiles of `tile_m`; zero where index[s] < 0; rows
    past the live ones come back unwritten. packed: `pack_rows` of src
    (T, `width`), index: (rows,) int32, in which the rows of a tile
    that have a source come before its rows that have none (a group
    fills its tiles from the front). With `scale` (rows,) float32:
    scale[s] times the row, rounded."""
    rows = step_rows(tile_m)
    code = index.astype(_I32).reshape(-1, rows)
    steps = code.shape[0]

    def at(*tail):
        # a step past the live ones names the last live step's blocks
        return lambda s, live, count: (jnp.minimum(s, live[0] - 1), *tail)
    operands = [_step_blocks(jnp.maximum(code, 0)), packed]
    in_specs = [pl.BlockSpec((CODES,), at(), memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY)]
    if scale is not None:
        operands.append(scale.astype(_F32).reshape(-1, 1))
        in_specs.append(pl.BlockSpec((rows, 1), at(0)))
    live = (live_rows // rows).astype(_I32)
    return pl.pallas_call(
        _in_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(steps,), in_specs=in_specs,
            out_specs=pl.BlockSpec((rows, width), at(0)),
            scratch_shapes=[pltpu.VMEM((rows,) + packed.shape[1:], _U32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((steps * rows, width), jnp.bfloat16,
                                       vma=_vma(*operands, live)),
        compiler_params=_params(), name="hvd_moe_rows_in",
    )(live, jnp.sum(code >= 0, axis=1, dtype=_I32), *operands)


def _rows_out(buf, code, side, *, mode, out_dtype, name):
    """The walk `rows_out` and `rows_dot` share: a step's landed pairs
    sorted to the front, its codes, pairs and gates in SMEM, the
    buffer in HBM."""
    tokens, k = code.shape
    width = buf.shape[1]
    wide = 1 << (k - 1).bit_length()        # codes a token in SMEM
    rows = step_rows(tokens, wide)
    fill = ((0, 0), (0, wide - k))
    code = jnp.pad(code.astype(_I32), fill, constant_values=-1).reshape(
        -1, rows * wide)
    pair = jnp.broadcast_to(jnp.arange(rows * wide, dtype=_I32), code.shape)
    operands = [(code < 0).astype(_I32), code, pair]
    if mode == "gate":
        operands.append(jnp.pad(side.astype(_F32), fill).reshape(code.shape))
    # a step's landed pairs first, in the order of (t, j): stable
    _, code, pair, *gate = lax.sort(operands, dimension=1, num_keys=1)
    smem = pl.BlockSpec((CODES,), lambda s, count: (s,),
                        memory_space=pltpu.SMEM)
    block = pl.BlockSpec((rows, width), lambda s, count: (s, 0))
    operands = [_step_blocks(jnp.maximum(code, 0)), _step_blocks(pair)] + [
        _step_blocks(g, _F32) for g in gate]
    in_specs = [smem] * len(operands)
    if mode == "dot":
        operands.append(side.astype(_F32))
        in_specs.append(block)
        out = (rows * wide, 1)              # a pair's product
        stage = (rows * wide, LANES)
    else:
        out = stage = (rows, width)         # a token's sum
    operands.append(buf)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    return pl.pallas_call(
        functools.partial(_out_kernel, k=wide, mode=mode),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(code.shape[0],), in_specs=in_specs,
            out_specs=pl.BlockSpec(out, lambda s, count: (s, 0)),
            scratch_shapes=[pltpu.VMEM((RING, GROUP // 2, width), _U32),
                            pltpu.VMEM(stage, _F32),
                            pltpu.SemaphoreType.DMA((RING,))]),
        out_shape=jax.ShapeDtypeStruct((code.shape[0] * out[0], out[1]),
                                       out_dtype, vma=_vma(*operands)),
        compiler_params=_params(), name=name,
    )(jnp.sum(code >= 0, axis=1, dtype=_I32), *operands)


def rows_out(buf: jax.Array, code: jax.Array,
             gates: Optional[jax.Array] = None, *,
             out_dtype=None) -> jax.Array:
    """out[t] = sum over j with code[t, j] >= 0 of gates[t, j] *
    buf[code[t, j]] (gates of 1 if None), summed in f32 in the order
    of j and rounded once to `out_dtype`. buf: (rows, D) bf16, code,
    gates: (T, k). Only the rows a code names are read."""
    return _rows_out(buf, code, gates, mode=None if gates is None else "gate",
                     out_dtype=out_dtype or buf.dtype,
                     name="hvd_moe_rows_out")


def rows_dot(buf: jax.Array, code: jax.Array,
             against: jax.Array) -> jax.Array:
    """out[t, j] = <buf[code[t, j]], against[t]> in float32, zero where
    code[t, j] < 0: the product a gated sum's gradient to its gates is
    made of, against the sum's cotangent as it is, not rounded. buf:
    (rows, D) bf16, code: (T, k), against: (T, D)."""
    tokens, k = code.shape
    return _rows_out(buf, code, against, mode="dot", out_dtype=_F32,
                     name="hvd_moe_rows_dot").reshape(tokens, -1)[:, :k]
