"""Fused causal self-attention for the TPU: online softmax in VMEM, so
the f32 (B, H, L, L) scores and probabilities never reach HBM and the
key blocks above the diagonal are neither loaded nor computed.

Same mathematics as `ring_attention.dense_attention`: operands in the
dtype given (bf16 in training), f32 accumulation in every matmul, f32
running max and sum, exact softmax. Two kernels under one `custom_vjp`:
the forward (also what `jax.checkpoint` runs again) and the backward,
which rebuilds a block's scores and probabilities once from the saved
log-sum-exp (the one residual besides q, k, v and the output) and
gives dQ, dK and dV from them: 5 products a block and q head. It walks
as the forward does and holds a kv group's dK / dV for the whole
sequence in VMEM (`_bwd_kernel`). Where those would pass
`RESIDENT_KV_CAP` (`one_kernel_backward`: the sequence, the kv heads a
step and the two widths decide), the backward is two kernels, dQ and
dK/dV, that each rebuild the scores (7 products).
`hvd_attention_backward_traces_total{kernels}` counts which a trace got.

Layout. q, k, v arrive as the projections leave them, (B, L, H, D),
and are read as (B, L, H*D) with a head a D-wide column block: no
transpose to (B, H, L, D) and back, and K / V keep their own heads
(grouped-query attention: q head h reads kv head h // (H // Hkv)).
One grid step takes the q heads that share a kv head (`heads_per_step`),
so their K / V block is loaded once, and their dK / dV are summed
in VMEM; where every q head has a kv head of its own, a step
takes several heads of both side by side (`step_heads`).

Two head widths, both read from the shapes of the call: `dqk` of q and
k, `dv` of v (latent attention: 192 and 128; equal everywhere else).
q / k blocks, dQ and dK are `dqk` wide; v blocks, the output, dO, the
f32 accumulator and dV are `dv` wide, so a narrower v is neither
padded in HBM nor multiplied as zeros on the MXU. A column block is a
whole number of lanes: `dv` is, and so is `dqk` or an even number of
heads of it (two or four heads of 192 a step; the caller zero-pads q
and k where neither holds). With equal widths and grouped kv the
kernels are what they were with one width.

A sliding window (`window`: the keys a query sees, its own position
counted, so i - j < window) is the same kernels with a narrower
walk: a query block's grid steps start at the oldest key block its
window reaches (`_key_block`), a key block's (the dK/dV kernel's) at
its own query block and end where the window does, and the grid has
as many steps as a window can touch (`walk_steps`), so key blocks
wholly older than the window are neither loaded nor computed, as
blocks above the diagonal are; the one block the window's edge cuts
is masked. `window=None` traces the causal program, instruction for
instruction.

Blocks come from the shapes the call sees (`block_size`): the largest
multiple of 128 up to `BLOCK_CAP` that divides L, so seq 2048 runs
512-blocks and a seq-256 sample one 256-block. The running max and
sum live replicated over the 128 lanes, so that applying them to a
score block is a tile and not a lane broadcast a vector register (on
the v5e that alone took the forward from 1.46 to 0.82 ms at the
Mistral shape; PERF.md section 6, PR 30). Every output declares the
varying-mesh-axes type of its inputs, so the kernels trace inside
`shard_map` with the replication checker on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..metrics import REGISTRY as _METRICS

LANES = 128
# Query and key block. Swept on the v5e at (2, 32, 2048, 128) and
# (1, 32, 256, 128): PERF.md section 6, PR 30.
BLOCK_CAP = 512
# q heads that share a kv head go through one grid step, which loads
# their K / V block once; at most this many (the Mistral group of 4,
# measured; blocks of 512 x 4 heads of 256 still fit the compiler's
# default VMEM budget, tests/test_chip_compile.py).
HEADS_CAP = 4
# Where every q head has its own kv head there is no K / V block to
# share, and a grid step takes up to HEADS_CAP heads of both side by
# side: fewer, longer steps (at 32 heads of 192 / 128 and seq 4096 the
# three kernels read 15.5 ms with one head a step, 14.0 with two, 13.1
# with four; PERF.md section 6, PR 32), and an even number of heads of
# 192 is a whole number of lanes. At most this many K and V columns a
# step, both widths together: four heads of 256 / 128 fit the
# compiler's VMEM budget at blocks of 512, four of 256 / 256 do not.
KV_COLS_CAP = 1536
# Finite, so that a fully masked row of a diagonal block gives
# exp(MASK - m) = 0 and never inf - inf.
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
# The one backward kernel holds a grid step's kv heads' dK and dV for
# the whole sequence in VMEM as f32: it engages where they take at most
# this many bytes (`one_kernel_backward`). The decoder cells' take 2
# (Mistral), 16 (`trinity`) and 21 MB (`xing4`); seq 65,536 on one kv
# head of 128 / 128 would take 64 and keeps the two kernels.
RESIDENT_KV_CAP = 32 << 20
# The compiler's scoped VMEM budget for a kernel that declares none (the
# v5e's). The one backward kernel declares a limit only where it needs
# more (`_bwd_vmem`): a declared limit, of any size, moves what XLA keeps
# in VMEM around the call, which cost the Mistral cell's FFN backward
# 3 ms a step (PERF.md section 6, PR 40).
SCOPED_VMEM_DEFAULT = 16 << 20
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_F32 = jnp.float32

_m_backward = _METRICS.counter(
    "hvd_attention_backward_traces_total",
    "Backward passes of the attention kernels traced, by the kernels "
    "that run them: one (dQ, dK and dV from one rebuild of the scores) "
    "or two (dQ, then dK/dV: a kv group's dK / dV exceed RESIDENT_KV_CAP)"
    "; sparse_one / sparse_two: the block-sparse ones.", ("kernels",))


def block_size(seq: int, cap: int = BLOCK_CAP) -> int:
    """Largest multiple of 128 up to `cap` that divides `seq`; 0 where
    there is none (the caller keeps the dense path)."""
    for b in range(min(cap, seq) // LANES * LANES, 0, -LANES):
        if seq % b == 0:
            return b
    return 0


def supported(q_shape, k_shape, v_shape) -> bool:
    """The shapes the kernels take: self-attention (equal lengths),
    heads in whole groups, L in 128-blocks, the v head width a whole
    number of lanes, and a q / k head width of which `step_heads` makes
    column blocks of whole lanes (a whole number of lanes itself; or,
    where every q head has its own kv head, one of which two or four
    heads side by side are: 192)."""
    B, L, H, D = q_shape
    return (len(k_shape) == len(v_shape) == 4
            and tuple(v_shape[:3]) == tuple(k_shape[:3])
            and k_shape[0] == B and k_shape[1] == L
            and k_shape[3] == D and k_shape[2] > 0
            and H % k_shape[2] == 0
            and v_shape[3] > 0 and v_shape[3] % LANES == 0
            and block_size(L) > 0
            and step_heads(H, k_shape[2], D, v_shape[3]) is not None)


def heads_per_step(group: int, cap: int = HEADS_CAP) -> int:
    """q heads one grid step takes: all that share a kv head, up to
    `cap` (the largest divisor of the group within it)."""
    return max(c for c in range(1, min(group, cap) + 1) if group % c == 0)


def step_heads(H: int, Hkv: int, Dqk: int, Dv: int):
    """(q heads, kv heads, steps a kv head) one grid step takes; None
    where the q / k width gives no column block of whole lanes.
    Grouped kv: the q heads that share one kv head, `heads_per_step`
    of them, in as many steps as the group needs. Every q head with a
    kv head of its own: as many heads of both, side by side, as divide
    Hkv, stay within `HEADS_CAP` and `KV_COLS_CAP` and make whole
    lanes (of 192: two or four)."""
    group = H // Hkv
    if group > 1:
        hs = heads_per_step(group)
        return (hs, 1, group // hs) if Dqk % LANES == 0 else None
    fits = [n for n in range(1, min(Hkv, HEADS_CAP) + 1)
            if Hkv % n == 0 and n * Dqk % LANES == 0
            and (n == 1 or n * (Dqk + Dv) <= KV_COLS_CAP)]
    return (fits[-1], fits[-1], 1) if fits else None


def one_kernel_backward(q_shape, k_shape, v_shape) -> bool:
    """Whether one kernel takes the backward of a call `supported`
    takes: where the f32 dK / dV it holds for a grid step's kv heads
    over the whole sequence fit `RESIDENT_KV_CAP`; else dQ and dK/dV
    are two kernels that each rebuild the scores."""
    _, L, H, Dqk = q_shape
    Hkv, Dv = k_shape[2], v_shape[3]
    kvs = step_heads(H, Hkv, Dqk, Dv)[1]
    return L * kvs * (Dqk + Dv) * 4 <= RESIDENT_KV_CAP


def walk_steps(seq: int, blk: int, window=None) -> int:
    """Grid steps of one walk: every block of the sequence, or with a
    window the most blocks one can touch (a query block's keys reach
    back window - 1 positions from its first row; a key block's
    queries as far forward from its last)."""
    if window is None:
        return seq // blk
    return min(seq // blk, (window + blk - 2) // blk + 1)


def blocks_visited(seq: int, window=None):
    """(key blocks the forward kernel computes for one head, key
    blocks on or under the diagonal): what a window skips, as the grid
    and `_on_visible` have it."""
    blk = block_size(seq)
    n = seq // blk
    reach = seq if window is None else window - 1
    visited = sum(i - max(i * blk - reach, 0) // blk + 1 for i in range(n))
    return visited, n * (n + 1) // 2


def _key_block(i, j, blk: int, window):
    """The key block that step j of query block i's walk computes:
    block j, or with a window the oldest block the window reaches
    plus j. Steps past the diagonal compute nothing."""
    if window is None:
        return j
    return jnp.maximum(i * blk - (window - 1), 0) // blk + j


def _query_block(j, i, window):
    """The query block that step i of key block j's walk computes:
    block i (those before j compute nothing), or with a window block
    j + i (those past the sequence's end compute nothing)."""
    return i if window is None else j + i


def _visible(q_lo, k_lo, q_axis: int, shape, window=None):
    """Where key position <= query position, and with a window query
    - key < window, for a score block whose first query / key
    positions are q_lo / k_lo; queries run along `q_axis`."""
    qpos = q_lo + lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = k_lo + lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    if window is None:
        return kpos <= qpos
    return jnp.logical_and(kpos <= qpos, qpos - kpos < window)


def _tile(x, width: int):
    """A lane-replicated (rows, 128) value at `width` lanes."""
    return jnp.tile(x, (1, width // LANES))


def _column(row):
    """A (rows,) vector read from a stored row, as a lane-replicated
    (rows, 128) column."""
    return jnp.broadcast_to(jnp.expand_dims(row, -1),
                            (row.shape[0], LANES))


def _head_cols(heads: int, width: int):
    """The column slice of each head in a (rows, heads * width)
    block."""
    return [slice(g * width, (g + 1) * width) for g in range(heads)]


def _kv_index(heads: int, width: int):
    """The index of each kv head in a grid step's (rows, heads * width)
    K / V block or dK / dV accumulator: the whole of it where the
    step has one."""
    if heads == 1:
        return [...]
    return [(slice(None), cols) for cols in _head_cols(heads, width)]


def _on_visible(q_lo, bq, k_lo, bk, step, window=None, live=None):
    """Run `step(masked)` for a block with any visible key: unmasked
    where every key is visible to every query, masked on the
    diagonal and where the window's edge cuts the block, not at all
    above the diagonal or wholly older than the window. `live`: the
    walk's step names a block of the sequence."""
    def both(seen, reach):
        """`seen` under the causal mask, and where the walk has them
        `reach()` within the window and `live`."""
        if window is not None:
            seen = jnp.logical_and(seen, reach() < window)
        return seen if live is None else jnp.logical_and(seen, live)
    inside = both(k_lo + bk - 1 <= q_lo, lambda: q_lo + bq - 1 - k_lo)
    pl.when(inside)(lambda: step(False))
    outside = jnp.logical_not(inside)
    some = both(k_lo <= q_lo + bq - 1, lambda: q_lo - (k_lo + bk - 1))
    pl.when(jnp.logical_and(outside, some))(lambda: step(True))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc,
                *, scale: float, bq: int, bk: int, heads: int,
                kv_heads: int, dqk: int, dv: int, window=None):
    i, j = pl.program_id(2), pl.program_id(3)
    q_lo, k_lo = i * bq, _key_block(i, j, bk, window) * bk
    qcols, vcols = _head_cols(heads, dqk), _head_cols(heads, dv)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, MASK_VALUE)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(masked: bool):
        ks = [k_ref[ix] for ix in _kv_index(kv_heads, dqk)]
        vs = [v_ref[ix] for ix in _kv_index(kv_heads, dv)]
        keep = _visible(q_lo, k_lo, 0, (bq, bk), window) if masked \
            else None
        for g in range(heads):
            k, v = ks[g % kv_heads], vs[g % kv_heads]
            s = lax.dot_general(q_ref[:, qcols[g]], k, _NT,
                                preferred_element_type=_F32) * scale
            if masked:
                s = jnp.where(keep, s, MASK_VALUE)
            # The running max and sum are kept replicated over the
            # 128 lanes, so that subtracting them from a score block
            # or scaling the accumulator is a tile, not a lane
            # broadcast for every vector register of the block.
            m_prev, l_prev = m_sc[g], l_sc[g]
            m_next = jnp.maximum(m_prev, s.max(axis=1)[:, None])
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - _tile(m_next, bk))
            l_sc[g] = alpha * l_prev + p.sum(axis=1)[:, None]
            acc_sc[:, vcols[g]] = _tile(alpha, dv) * acc_sc[:, vcols[g]] \
                + jnp.dot(p.astype(v.dtype), v,
                          preferred_element_type=_F32)
            m_sc[g] = m_next

    _on_visible(q_lo, bq, k_lo, bk, step, window)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        for g in range(heads):
            l = l_sc[g]
            o_ref[:, vcols[g]] = (acc_sc[:, vcols[g]]
                                  * _tile(1.0 / l, dv)).astype(o_ref.dtype)
            # (bq, 128) lane-replicated column -> (1, bq) row, the
            # form both backward kernels read.
            lse_ref[g] = (m_sc[g] + jnp.log(l)).T[:1]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               dq_sc, *, scale: float, bq: int, bk: int, heads: int,
               kv_heads: int, dqk: int, dv: int, window=None):
    i, j = pl.program_id(2), pl.program_id(3)
    q_lo, k_lo = i * bq, _key_block(i, j, bk, window) * bk
    qcols, vcols = _head_cols(heads, dqk), _head_cols(heads, dv)

    @pl.when(j == 0)
    def _():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def step(masked: bool):
        ks = [k_ref[ix] for ix in _kv_index(kv_heads, dqk)]
        vs = [v_ref[ix] for ix in _kv_index(kv_heads, dv)]
        keep = _visible(q_lo, k_lo, 0, (bq, bk), window) if masked \
            else None
        for g in range(heads):
            k, v = ks[g % kv_heads], vs[g % kv_heads]
            s = lax.dot_general(q_ref[:, qcols[g]], k, _NT,
                                preferred_element_type=_F32) * scale
            if masked:
                s = jnp.where(keep, s, MASK_VALUE)
            p = jnp.exp(s - _tile(_column(lse_ref[g, 0]), bk))
            dp = lax.dot_general(do_ref[:, vcols[g]], v, _NT,
                                 preferred_element_type=_F32)
            ds = p * (dp - _tile(_column(di_ref[g, 0]), bk))
            dq_sc[:, qcols[g]] += jnp.dot(ds.astype(k.dtype), k,
                                          preferred_element_type=_F32)

    _on_visible(q_lo, bq, k_lo, bk, step, window)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        dq_ref[...] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref,
                dv_ref, dk_sc, dv_sc, *, scale: float, bq: int, bk: int,
                heads: int, kv_heads: int, dqk: int, dv: int,
                window=None):
    """Scores transposed, (bk, bq): keys along sublanes, so dV and dK
    are plain p^T @ dO and ds^T @ q, and lse / di broadcast as the
    rows they are stored as."""
    j, c, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    q_block = _query_block(j, i, window)
    q_lo, k_lo = q_block * bq, j * bk
    live = None if window is None else q_block < pl.num_programs(2)
    qcols, vcols = _head_cols(heads, dqk), _head_cols(heads, dv)

    @pl.when(jnp.logical_and(c == 0, i == 0))
    def _():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    kidx, vidx = _kv_index(kv_heads, dqk), _kv_index(kv_heads, dv)

    def step(masked: bool):
        ks, vs = [k_ref[ix] for ix in kidx], [v_ref[ix] for ix in vidx]
        keep = _visible(q_lo, k_lo, 1, (bk, bq), window) if masked \
            else None
        for g in range(heads):
            h = g % kv_heads
            k, v = ks[h], vs[h]
            q, do = q_ref[:, qcols[g]], do_ref[:, vcols[g]]
            st = lax.dot_general(k, q, _NT,
                                 preferred_element_type=_F32) * scale
            if masked:
                st = jnp.where(keep, st, MASK_VALUE)
            pt = jnp.exp(st - lse_ref[g])
            dv_sc[vidx[h]] += jnp.dot(pt.astype(do.dtype), do,
                                      preferred_element_type=_F32)
            dpt = lax.dot_general(v, do, _NT,
                                  preferred_element_type=_F32)
            dst = pt * (dpt - di_ref[g])
            dk_sc[kidx[h]] += jnp.dot(dst.astype(q.dtype), q,
                                      preferred_element_type=_F32)

    _on_visible(q_lo, bq, k_lo, bk, step, window, live)

    @pl.when(jnp.logical_and(c == pl.num_programs(3) - 1,
                             i == pl.num_programs(4) - 1))
    def _():
        dk_ref[...] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
                dk_ref, dv_ref, dq_sc, dk_sc, dv_sc, *, scale: float,
                bq: int, bk: int, heads: int, kv_heads: int, dqk: int,
                dv: int, window=None):
    """dQ, dK and dV from one rebuild of a block's scores and
    probabilities: 5 products a block and q head where `_dq_kernel`
    and `_dkv_kernel` do 7. The walk is `_dq_kernel`'s; the scores are
    transposed, (bk, bq), as `_dkv_kernel` has them, so dV and dK are
    plain p^T @ dO and ds^T @ q into the kv group's accumulators over
    the whole sequence, at the key block's rows, and lse / di
    broadcast as the rows they are stored as. dQ is accumulated
    transposed, K^T @ ds^T: the step transposes its K block once for
    all its q heads, not a score block a head, and dQ^T is turned back
    once a query block."""
    c, i, j = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    q_lo, k_lo = i * bq, _key_block(i, j, bk, window) * bk
    qcols, vcols = _head_cols(heads, dqk), _head_cols(heads, dv)
    kcols, vkcols = _head_cols(kv_heads, dqk), _head_cols(kv_heads, dv)

    @pl.when((c == 0) & (i == 0) & (j == 0))
    def _():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    @pl.when(j == 0)
    def _():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def step(masked: bool):
        rows = pl.ds(pl.multiple_of(k_lo, bk), bk)
        kt = k_ref[...].T                       # (kv_heads * dqk, bk)
        ks = [k_ref[ix] for ix in _kv_index(kv_heads, dqk)]
        vs = [v_ref[ix] for ix in _kv_index(kv_heads, dv)]
        keep = _visible(q_lo, k_lo, 1, (bk, bq), window) if masked \
            else None
        for g in range(heads):
            h = g % kv_heads
            k, v = ks[h], vs[h]
            q, do = q_ref[:, qcols[g]], do_ref[:, vcols[g]]
            st = lax.dot_general(k, q, _NT,
                                 preferred_element_type=_F32) * scale
            if masked:
                st = jnp.where(keep, st, MASK_VALUE)
            pt = jnp.exp(st - lse_ref[g])
            dv_sc[rows, vkcols[h]] += jnp.dot(pt.astype(do.dtype), do,
                                              preferred_element_type=_F32)
            dpt = lax.dot_general(v, do, _NT,
                                  preferred_element_type=_F32)
            dst = (pt * (dpt - di_ref[g])).astype(q.dtype)
            dk_sc[rows, kcols[h]] += jnp.dot(dst, q,
                                             preferred_element_type=_F32)
            dq_sc[qcols[g], :] += jnp.dot(kt[kcols[h]], dst,
                                          preferred_element_type=_F32)

    _on_visible(q_lo, bq, k_lo, bk, step, window)

    @pl.when(j == pl.num_programs(4) - 1)
    def _():
        dq_ref[...] = (dq_sc[...].T * scale).astype(dq_ref.dtype)

    @pl.when((c == pl.num_programs(2) - 1) & (i == pl.num_programs(3) - 1)
             & (j == pl.num_programs(4) - 1))
    def _():
        dk_ref[...] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _vma(*xs):
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def _params(n_parallel: int, n_grid: int, vmem_limit_bytes=None):
    return pltpu.CompilerParams(dimension_semantics=(
        ("parallel",) * n_parallel
        + ("arbitrary",) * (n_grid - n_parallel)),
        vmem_limit_bytes=vmem_limit_bytes)


def _plan(q, k, v):
    """What the three calls share: (B, L, H, Hkv, Dqk, Dv), `step_heads`
    (the q heads and the kv heads a grid step takes, the steps a kv
    block), the block."""
    B, L, H, Dqk = q.shape
    Hkv, Dv = k.shape[2], v.shape[3]
    return ((B, L, H, Hkv, Dqk, Dv), step_heads(H, Hkv, Dqk, Dv),
            block_size(L))


def _q_major(dims, heads, blk: int, window, by_kv: bool = False):
    """Grid and specs of the kernels that walk key blocks for a query
    block (forward, dQ, the one backward kernel): (grid, q / dQ spec,
    o / dO spec, k spec, v spec, lse / di row spec). One step takes
    `hs` q heads, a (blk, hs * Dqk) column block of q and a
    (blk, hs * Dv) one of the output, and the `kvs` kv heads they read.
    Keys past the diagonal are not loaded: their steps name the block
    already resident; keys older than a window are no step of the walk.
    The grid is (B, q head steps, query blocks, walk), or `by_kv`
    (B, kv head steps, per_kv, query blocks, walk): the same steps in
    the same order, with the q heads of one kv group innermost but
    for the walk, so that what a kv group accumulates stays resident."""
    B, L, H, Hkv, Dqk, Dv = dims
    hs, kvs, per_kv = heads

    def index(f):
        if not by_kv:
            return f
        return lambda b, h, c, i, j: f(b, h * per_kv + c, i, j)

    def q_cols(width):
        return pl.BlockSpec((None, blk, hs * width),
                            index(lambda b, c, i, j: (b, i, c)))

    def kv_cols(width):
        return pl.BlockSpec(
            (None, blk, kvs * width),
            index(lambda b, c, i, j: (
                b, jnp.minimum(_key_block(i, j, blk, window), i),
                c // per_kv)))
    row_spec = pl.BlockSpec((None, hs, 1, blk),
                            index(lambda b, c, i, j: (b, c, 0, i)))
    heads_axes = (Hkv // kvs, per_kv) if by_kv else (H // hs,)
    return ((B, *heads_axes, L // blk, walk_steps(L, blk, window)),
            q_cols(Dqk), q_cols(Dv), kv_cols(Dqk), kv_cols(Dv), row_spec)


def _forward(q, k, v, scale: float, interpret: bool, window):
    dims, heads, blk = _plan(q, k, v)
    B, L, H, Hkv, Dqk, Dv = dims
    hs, kvs, _ = heads
    vma = _vma(q, k, v)
    grid, q_spec, o_spec, k_spec, v_spec, row_spec = _q_major(
        dims, heads, blk, window)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=blk, bk=blk,
                          heads=hs, kv_heads=kvs, dqk=Dqk, dv=Dv,
                          window=window),
        grid=grid,
        in_specs=[q_spec, k_spec, v_spec],
        out_specs=[o_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((B, L, H * Dv), q.dtype, vma=vma),
                   jax.ShapeDtypeStruct((B, H, 1, L), _F32, vma=vma)],
        scratch_shapes=[pltpu.VMEM((hs, blk, LANES), _F32),
                        pltpu.VMEM((hs, blk, LANES), _F32),
                        pltpu.VMEM((blk, hs * Dv), _F32)],
        compiler_params=_params(3, 4),
        interpret=interpret,
        name="hvd_fused_attention_fwd",
    )(q.reshape(B, L, H * Dqk), k.reshape(B, L, Hkv * Dqk),
      v.reshape(B, L, Hkv * Dv))
    return o.reshape(B, L, H, Dv), lse


def _backward(q, k, v, o, lse, do, scale: float, interpret: bool,
              window, one: bool):
    """dQ, dK, dV: with `one` from `_bwd_kernel`, else from
    `_dq_kernel` and `_dkv_kernel` (`one_kernel_backward` decides)."""
    dims, heads, blk = _plan(q, k, v)
    B, L, H, Hkv, Dqk, Dv = dims
    vma = _vma(q, k, v, do)
    di = jnp.sum(o.astype(_F32) * do.astype(_F32), axis=-1)   # (B, L, H)
    di = jnp.swapaxes(di, 1, 2)[:, :, None, :]                # (B, H, 1, L)
    args = (q.reshape(B, L, H * Dqk), k.reshape(B, L, Hkv * Dqk),
            v.reshape(B, L, Hkv * Dv), do.reshape(B, L, H * Dv), lse, di)
    kw = dict(scale=scale, bq=blk, bk=blk, heads=heads[0],
              kv_heads=heads[1], dqk=Dqk, dv=Dv, window=window)
    out_shape = [
        jax.ShapeDtypeStruct((B, L, H * Dqk), q.dtype, vma=vma),
        jax.ShapeDtypeStruct((B, L, Hkv * Dqk), k.dtype, vma=vma),
        jax.ShapeDtypeStruct((B, L, Hkv * Dv), v.dtype, vma=vma)]
    split = _one_kernel if one else _two_kernels
    dq, dk, dv = split(dims, heads, blk, window, kw, out_shape, interpret,
                       args)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


def _bwd_vmem(dims, heads, blk: int, itemsize: int):
    """VMEM the one backward kernel declares: None where it fits
    `SCOPED_VMEM_DEFAULT`, else what it holds: the f32 dK / dV and
    their output blocks (two buffers each), the q / dQ / dO / k / v
    blocks (two buffers each), dQ^T and six f32 score-sized blocks
    (the step at the cells' shapes needs 5-12 MiB beside the resident
    part; this counts 9.5-14)."""
    _, L, _, _, Dqk, Dv = dims
    hs, kvs, _ = heads
    need = (L * kvs * (Dqk + Dv) * (4 + 2 * itemsize)
            + 2 * blk * (hs * (2 * Dqk + Dv) + kvs * (Dqk + Dv)) * itemsize
            + blk * hs * Dqk * 4 + 6 * blk * blk * 4)
    return need if need > SCOPED_VMEM_DEFAULT else None


def _one_kernel(dims, heads, blk, window, kw, out_shape, interpret, args):
    """dQ, dK, dV as one call: q-major like dQ, a kv group's dK / dV
    over the whole sequence resident (their output blocks change with
    (b, kv group) alone, so Pallas writes them back once a group)."""
    _, L, _, _, Dqk, Dv = dims
    hs, kvs, _ = heads
    grid, q_spec, o_spec, k_spec, v_spec, row_spec = _q_major(
        dims, heads, blk, window, by_kv=True)

    def whole(width):
        return pl.BlockSpec((None, L, kvs * width),
                            lambda b, h, c, i, j: (b, 0, h))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, **kw),
        grid=grid,
        in_specs=[q_spec, k_spec, v_spec, o_spec, row_spec, row_spec],
        out_specs=[q_spec, whole(Dqk), whole(Dv)],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hs * Dqk, blk), _F32),
                        pltpu.VMEM((L, kvs * Dqk), _F32),
                        pltpu.VMEM((L, kvs * Dv), _F32)],
        compiler_params=_params(2, 5, _bwd_vmem(
            dims, heads, blk, out_shape[1].dtype.itemsize)),
        interpret=interpret,
        name="hvd_fused_attention_bwd",
    )(*args)


def _two_kernels(dims, heads, blk, window, kw, out_shape, interpret,
                 args):
    """dQ, then dK/dV: each rebuilds the scores of every visible block."""
    B, L, H, Hkv, Dqk, Dv = dims
    hs, kvs, per_kv = heads
    grid, q_spec, o_spec, k_spec, v_spec, row_spec = _q_major(
        dims, heads, blk, window)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=grid,
        in_specs=[q_spec, k_spec, v_spec, o_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=out_shape[0],
        scratch_shapes=[pltpu.VMEM((blk, hs * Dqk), _F32)],
        compiler_params=_params(3, 4),
        interpret=interpret,
        name="hvd_fused_attention_dq",
    )(*args)

    # dK/dV walks query blocks for a key block of `kvs` kv heads, their
    # q heads in `per_kv` steps of `hs`. Query blocks before the
    # diagonal see nothing of key block j: their steps name the first
    # that does. With a window the walk starts there, and its steps
    # past the sequence's end name the last block.
    def q_index(j, i):
        if window is None:
            return jnp.maximum(i, j)
        return jnp.minimum(_query_block(j, i, window), L // blk - 1)

    def qg_cols(width):
        return pl.BlockSpec(
            (None, blk, hs * width),
            lambda b, h, j, c, i: (b, q_index(j, i), h * per_kv + c))

    def kvg_cols(width):
        return pl.BlockSpec((None, blk, kvs * width),
                            lambda b, h, j, c, i: (b, j, h))
    rowg_spec = pl.BlockSpec(
        (None, hs, 1, blk),
        lambda b, h, j, c, i: (b, h * per_kv + c, 0, q_index(j, i)))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid=(B, Hkv // kvs, L // blk, per_kv,
              walk_steps(L, blk, window)),
        in_specs=[qg_cols(Dqk), kvg_cols(Dqk), kvg_cols(Dv), qg_cols(Dv),
                  rowg_spec, rowg_spec],
        out_specs=[kvg_cols(Dqk), kvg_cols(Dv)],
        out_shape=out_shape[1:],
        scratch_shapes=[pltpu.VMEM((blk, kvs * Dqk), _F32),
                        pltpu.VMEM((blk, kvs * Dv), _F32)],
        compiler_params=_params(3, 5),
        interpret=interpret,
        name="hvd_fused_attention_dkv",
    )(*args)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attention(q, k, v, scale, interpret, window):
    return _forward(q, k, v, scale, interpret, window)[0]


def _attention_fwd(q, k, v, scale, interpret, window):
    o, lse = _forward(q, k, v, scale, interpret, window)
    return o, (q, k, v, o, lse)


def _attention_bwd(scale, interpret, window, residuals, do):
    q, k, v, o, lse = residuals
    one = one_kernel_backward(q.shape, k.shape, v.shape)
    _m_backward.labels(kernels="one" if one else "two").inc()
    return _backward(q, k, v, o, lse, do, scale, interpret, window, one)


_attention.defvjp(_attention_fwd, _attention_bwd)


def fused_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           scale: float, *, window=None,
                           interpret: bool = False) -> jax.Array:
    """Causal self-attention, q (B, L, H, Dqk), k (B, L, Hkv, Dqk),
    v (B, L, Hkv, Dv) with H a multiple of Hkv, for shapes `supported`
    takes; the output is (B, L, H, Dv). `window`: a query sees its own
    position and the window - 1 before it (None: all before it).
    `interpret` runs the kernels in Pallas's interpreter (the CPU
    tests)."""
    if window is not None and int(window) < 1:
        raise ValueError(f"a window holds at least the query's own "
                         f"position; got {window}")
    if not supported(q.shape, k.shape, v.shape):
        raise ValueError(
            f"fused attention does not take q {q.shape}, k {k.shape}, "
            f"v {v.shape}: it needs equal lengths in 128-blocks, "
            f"head widths (q / k, v) in whole {LANES}-lane column "
            f"blocks, heads in whole groups")
    return _attention(q, k, v, float(scale), bool(interpret),
                      None if window is None else int(window))
