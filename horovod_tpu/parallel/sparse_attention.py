"""Causal attention over key blocks that each query chooses from the
data (InfLLM-v2's trainable sparse attention, arXiv:2506.07900, the
`minicpm4` mixer): a query token keeps `topk` blocks of `block` keys,
the same for the q heads that share a kv head, and attends to the
keys at or before it inside them.

The selection (`select_blocks`; float32, no gradient: its result is
indices):

    c_m     = mean(k[stride m : stride m + kernel])       a kv head
    p_{t,h} = softmax over the m whose whole span is at or before t
              of q_{t,h} . c_m * scale
    r_{t,m} = sum of p_{t,h,m} over the q heads of the group
    R_{t,b} = max of r_{t,m} over the pooled keys that overlap block b
    R_{t,b} = +inf for the first `init_blocks` blocks and for the
              `window // block` blocks that end at the query's own
    sel_t   = the `topk` blocks b <= t // block of largest R_{t,b}
              (all of them where there are fewer; ties to the lower b)

A query with no visible pooled key has R = 0 outside its forced
blocks. Up to `dense_len` positions every block would be kept anyway
and the layer is plain causal attention: `sparse_attention` hands
those calls to `ring_attention.attention` as they are.

The attention over the selection has two paths, picked by what the
call observes (`kernels_engage`: TPU, bf16, the fused kernels' shapes,
one kv head a grid step). Pallas kernels under one `custom_vjp` that
are `fused_attention.py`'s with two changes: the walk over key blocks
follows a scalar-prefetched table of the blocks that some query of the
query block selected, compacted to the front with its count, so a
kernel block no query of a query block chose is neither loaded nor
computed; and inside a visited block a token-level mask, unpacked from
one int32 word a (query, kernel block) whose bit s says whether the
query selected the kernel block's s-th selection block, keeps the
result exact where the queries of one block chose differently. The
forward is `hvd_sparse_attention_fwd`; the backward is one kernel,
`hvd_sparse_attention_bwd`, that walks the forward's table and gives
dQ, dK and dV from one rebuild of a visited block's scores, with a kv
group's f32 dK / dV over the sequence resident in VMEM. Where those
pass `RESIDENT_KV_CAP` (`fused_attention.one_kernel_backward`, a rule
on shapes) it is two, `_dq` over the same walk and `_dkv` over the
query blocks that visited a key block, each rebuilding the scores;
`hvd_attention_backward_traces_total{kernels="sparse_one"|"sparse_two"}`
counts which a trace got. No (L x L) array reaches HBM. Which blocks a
step visits is a device value (`block_tables`); from shapes only the
selected count is known. Everywhere else `_masked_attention`, the
same function as one masked softmax in `jax.numpy` (the tests' oracle;
it materialises the scores as `dense_attention` does).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tracing import device_scope
from .fused_attention import (BLOCK_CAP, LANES, MASK_VALUE, _bwd_vmem,
                              _column, _head_cols, _m_backward, _params,
                              _tile, _vma, block_size, one_kernel_backward,
                              step_heads)
from .fused_attention import supported as _fused_supported
from .ring_attention import _m_key_blocks, _m_traces, attention

_F32, _I32 = jnp.float32, jnp.int32
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
WORD_BITS = 32
# Query tokens whose pooled scores (heads x tokens x pooled keys,
# float32) are alive at once in `select_blocks`.
SELECT_TOKENS = 1024
# The selection's name for `jax.checkpoint` policies: a layer that is
# recomputed in the backward pass may keep it (a bool a query and
# block) instead of selecting again.
SELECTION = "hvd_sparse_selection"


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    """The constants of the layer (InfLLM-v2's `sparse_config`)."""
    kernel_size: int = 32      # keys a pooled key averages
    kernel_stride: int = 16    # keys between two pooled keys
    block: int = 64            # keys a selected block holds
    topk: int = 64             # blocks a query keeps, forced ones counted
    init_blocks: int = 1       # leading blocks every query keeps
    window: int = 2048         # keys before the query it always keeps
    dense_len: int = 8192      # up to here the layer is dense

    def __post_init__(self):
        if self.kernel_size % self.kernel_stride \
                or self.block % self.kernel_stride \
                or self.window < self.block or self.window % self.block \
                or self.init_blocks + self.window // self.block > self.topk:
            raise ValueError(
                f"{self}: pooled keys and blocks are whole strides, the "
                f"window whole blocks and at least the query's own, and "
                f"the forced blocks at most topk")

    @property
    def window_blocks(self) -> int:
        return self.window // self.block


def selected_blocks(seq: int, spec: SparseSpec) -> int:
    """Blocks one kv head's queries keep over a sequence, from shapes:
    topk a token, all there are where there are fewer."""
    return sum(min(spec.topk, t // spec.block + 1) for t in range(seq))


# ---------------------------------------------------------------------------
# Selection
# ---------------------------------------------------------------------------

def _pooled_keys(k, spec: SparseSpec):
    """(B, L, Hkv, D) -> (B, M, Hkv, D) float32 means of `kernel_size`
    keys every `kernel_stride`, M = (L - kernel_size) // stride + 1."""
    B, L, Hkv, D = k.shape
    stride, spans = spec.kernel_stride, spec.kernel_size // spec.kernel_stride
    strides = k.astype(_F32).reshape(B, L // stride, stride, Hkv, D).sum(2)
    M = L // stride - spans + 1
    return sum(strides[:, j:j + M] for j in range(spans)) / spec.kernel_size


def _block_scores(r, n_blocks: int, spec: SparseSpec):
    """(..., M) pooled scores -> (..., n_blocks): the largest over the
    pooled keys whose span overlaps the block, m in [ratio b - reach,
    ratio b + ratio - 1]. Scores are probabilities: an absent pooled
    key reads 0."""
    ratio = spec.block // spec.kernel_stride
    reach = (spec.kernel_size - 1) // spec.kernel_stride
    length = ratio * n_blocks + reach
    r = jnp.pad(r, [(0, 0)] * (r.ndim - 1)
                + [(reach, length - reach - r.shape[-1])])
    return functools.reduce(jnp.maximum, (
        r[..., off:off + ratio * n_blocks:ratio]
        for off in range(ratio + reach)))


def _select_chunk(q, pooled, first, spec: SparseSpec, scale: float,
                  n_blocks: int):
    """Block mask (B, Hkv, T, n_blocks) of the T queries from position
    `first` on. q: (B, T, Hkv, G, D); pooled: (B, M, Hkv, D)."""
    T, M = q.shape[1], pooled.shape[1]
    t = first + jnp.arange(T, dtype=_I32)
    s = jnp.einsum("btngd,bmnd->bngtm", q.astype(_F32), pooled,
                   precision=lax.Precision.HIGHEST) * scale
    ends = spec.kernel_stride * jnp.arange(M, dtype=_I32) \
        + spec.kernel_size - 1
    visible = ends[None, :] <= t[:, None]                       # (T, M)
    top = jnp.max(jnp.where(visible, s, -jnp.inf), axis=-1, keepdims=True)
    e = jnp.where(visible, jnp.exp(s - jnp.where(
        jnp.isfinite(top), top, 0.0)), 0.0)
    p = e / jnp.maximum(e.sum(-1, keepdims=True),
                        jnp.finfo(_F32).tiny)
    R = _block_scores(p.sum(axis=2), n_blocks, spec)        # (B, Hkv, T, nb)
    b = jnp.arange(n_blocks, dtype=_I32)[None, :]
    own = (t // spec.block)[:, None]
    forced = (b < spec.init_blocks) | (b > own - spec.window_blocks)
    R = jnp.where(b <= own, jnp.where(forced, jnp.inf, R), -jnp.inf)
    value, index = lax.top_k(R, min(spec.topk, n_blocks))
    chosen = (index[..., None] == b[0]) & (value > -jnp.inf)[..., None]
    return jnp.any(chosen, axis=-2)


def select_blocks(q: jax.Array, k: jax.Array, spec: SparseSpec
                  ) -> jax.Array:
    """(B, Hkv, L, L // block) bool: whether query t of the group of kv
    head n keeps block b (module docstring). q: (B, L, H, D) with H a
    multiple of Hkv, k: (B, L, Hkv, D), L in whole blocks. No
    gradient."""
    B, L, H, D = q.shape
    Hkv = k.shape[2]
    if L % spec.block or H % Hkv:
        raise ValueError(f"block selection takes sequences in whole "
                         f"blocks of {spec.block} and heads in whole "
                         f"groups; got q {q.shape}, k {k.shape}")
    scale = float(D ** -0.5)
    q, k = lax.stop_gradient(q), lax.stop_gradient(k)
    pooled = _pooled_keys(k, spec)
    T = max(t for t in range(spec.block, min(L, SELECT_TOKENS) + 1,
                             spec.block) if L % t == 0)
    chunks = jnp.moveaxis(q.reshape(B, L // T, T, Hkv, H // Hkv, D), 1, 0)
    masks = lax.map(
        lambda xs: _select_chunk(xs[0], pooled, xs[1], spec, scale,
                                 L // spec.block),
        (chunks, jnp.arange(0, L, T, dtype=_I32)))
    return jnp.moveaxis(masks, 0, 2).reshape(B, Hkv, L, L // spec.block)


# ---------------------------------------------------------------------------
# The oracle: one masked softmax
# ---------------------------------------------------------------------------

def _masked_attention(q, k, v, chosen, spec: SparseSpec, scale: float):
    B, L, H, _ = q.shape
    reps = H // k.shape[2]
    seen = jnp.repeat(chosen, spec.block, axis=-1) \
        & jnp.tril(jnp.ones((L, L), bool))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(_F32),
                   jnp.repeat(k, reps, axis=2).astype(_F32)) * scale
    p = jax.nn.softmax(
        jnp.where(jnp.repeat(seen, reps, axis=1), s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      jnp.repeat(v, reps, axis=2).astype(_F32)
                      ).astype(q.dtype)


# ---------------------------------------------------------------------------
# The kernels' walk
# ---------------------------------------------------------------------------

def kernel_block(seq: int, spec: SparseSpec) -> int:
    """Queries and keys of a kernel block: the fused kernels' block,
    capped so that its selection blocks are the bits of one word; 0
    where the sequence has none."""
    blk = block_size(seq, min(BLOCK_CAP, WORD_BITS * spec.block))
    return blk if blk and blk % spec.block == 0 else 0


def block_tables(chosen: jax.Array, blk: int, spec: SparseSpec,
                 transposed: bool = True):
    """What the kernels read of a selection (B, Hkv, L, L // block):

      words  (B, Hkv, L // blk, 1, L) int32: bit s of words[.., j, 0, t]
             says whether query t keeps selection block j * per + s
      walks  for the kernels that walk key blocks for a query block
             and (`transposed`, else None) for the one that walks query
             blocks for a key block, each (table (B * Hkv * n * n,)
             int32, the visited blocks of every row in ascending order
             at the front; count (B * Hkv * n,) int32).
    """
    B, Hkv, L, _ = chosen.shape
    n, per = L // blk, blk // spec.block
    bits = jnp.sum(
        chosen.reshape(B, Hkv, L, n, per).astype(_I32)
        << jnp.arange(per, dtype=_I32), axis=-1, dtype=_I32)
    visited = jnp.any(bits.reshape(B, Hkv, n, blk, n) != 0, axis=3)

    def walk(rows):                                 # (B, Hkv, n, n) bool
        order = jnp.argsort(jnp.logical_not(rows), axis=-1, stable=True)
        return (order.astype(_I32).reshape(-1),
                jnp.sum(rows, axis=-1, dtype=_I32).reshape(-1))
    words = jnp.moveaxis(bits, 2, 3)[:, :, :, None, :]
    return words, walk(visited), (walk(jnp.swapaxes(visited, 2, 3))
                                  if transposed else None)


def _keep(word, axis: int, shape, block: int, q_lo=None, k_lo=None):
    """Where the query's word has the bit of the key's selection block
    and, on the diagonal (`q_lo` given), key <= query. Keys run along
    `axis` of `shape`."""
    along = lax.broadcasted_iota(_I32, shape, axis)
    keep = (lax.shift_right_logical(word, along // block) & 1) == 1
    if q_lo is None:
        return keep
    qpos = q_lo + lax.broadcasted_iota(_I32, shape, 1 - axis)
    return jnp.logical_and(keep, k_lo + along <= qpos)


def _walked(table_ref, count_ref, row, n, step):
    """(whether step `step` of walk `row` names a visited block, the
    block it names: past the count the last visited one)."""
    count = count_ref[row]
    return step < count, table_ref[row * n + jnp.minimum(step, count - 1)]


def _on_walk(live, diagonal, step):
    """Run `step(diagonal)` where the walk's step is live: the causal
    mask only on the diagonal block."""
    pl.when(jnp.logical_and(live, diagonal))(lambda: step(True))
    pl.when(jnp.logical_and(live, jnp.logical_not(diagonal)))(
        lambda: step(False))


def _fwd_kernel(table_ref, count_ref, q_ref, k_ref, v_ref, w_ref, o_ref,
                lse_ref, m_sc, l_sc, acc_sc, *, scale: float, blk: int,
                heads: int, per_kv: int, kv_heads: int, d: int, dv: int,
                block: int):
    b, c, i, j = (pl.program_id(a) for a in range(4))
    n = pl.num_programs(3)
    live, k_block = _walked(table_ref, count_ref,
                            (b * kv_heads + c // per_kv) * n + i, n, j)
    qcols, vcols = _head_cols(heads, d), _head_cols(heads, dv)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, MASK_VALUE)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(diagonal: bool):
        k, v = k_ref[...], v_ref[...]
        word = _tile(_column(w_ref[0]), blk)
        keep = _keep(word, 1, (blk, blk), block,
                     *((i * blk, k_block * blk) if diagonal else ()))
        for g in range(heads):
            s = lax.dot_general(q_ref[:, qcols[g]], k, _NT,
                                preferred_element_type=_F32) * scale
            s = jnp.where(keep, s, MASK_VALUE)
            m_prev, l_prev = m_sc[g], l_sc[g]
            m_next = jnp.maximum(m_prev, s.max(axis=1)[:, None])
            alpha = jnp.exp(m_prev - m_next)
            # a query that kept nothing of the blocks so far holds
            # exp(MASK - MASK) = 1 a key; the first key it keeps (its
            # own, at the latest) wipes that with alpha = 0
            p = jnp.exp(s - _tile(m_next, blk))
            l_sc[g] = alpha * l_prev + p.sum(axis=1)[:, None]
            acc_sc[:, vcols[g]] = _tile(alpha, dv) * acc_sc[:, vcols[g]] \
                + jnp.dot(p.astype(v.dtype), v,
                          preferred_element_type=_F32)
            m_sc[g] = m_next

    _on_walk(live, k_block == i, step)

    @pl.when(j == n - 1)
    def _():
        for g in range(heads):
            l = l_sc[g]
            o_ref[:, vcols[g]] = (acc_sc[:, vcols[g]]
                                  * _tile(1.0 / l, dv)).astype(o_ref.dtype)
            lse_ref[g] = (m_sc[g] + jnp.log(l)).T[:1]


def _dq_kernel(table_ref, count_ref, q_ref, k_ref, v_ref, w_ref, do_ref,
               lse_ref, di_ref, dq_ref, dq_sc, *, scale: float, blk: int,
               heads: int, per_kv: int, kv_heads: int, d: int, dv: int,
               block: int):
    b, c, i, j = (pl.program_id(a) for a in range(4))
    n = pl.num_programs(3)
    live, k_block = _walked(table_ref, count_ref,
                            (b * kv_heads + c // per_kv) * n + i, n, j)
    qcols, vcols = _head_cols(heads, d), _head_cols(heads, dv)

    @pl.when(j == 0)
    def _():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def step(diagonal: bool):
        k, v = k_ref[...], v_ref[...]
        word = _tile(_column(w_ref[0]), blk)
        keep = _keep(word, 1, (blk, blk), block,
                     *((i * blk, k_block * blk) if diagonal else ()))
        for g in range(heads):
            s = lax.dot_general(q_ref[:, qcols[g]], k, _NT,
                                preferred_element_type=_F32) * scale
            s = jnp.where(keep, s, MASK_VALUE)
            p = jnp.exp(s - _tile(_column(lse_ref[g, 0]), blk))
            dp = lax.dot_general(do_ref[:, vcols[g]], v, _NT,
                                 preferred_element_type=_F32)
            ds = p * (dp - _tile(_column(di_ref[g, 0]), blk))
            dq_sc[:, qcols[g]] += jnp.dot(ds.astype(k.dtype), k,
                                          preferred_element_type=_F32)

    _on_walk(live, k_block == i, step)

    @pl.when(j == n - 1)
    def _():
        dq_ref[...] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(table_ref, count_ref, q_ref, k_ref, v_ref, w_ref, do_ref,
                lse_ref, di_ref, dk_ref, dv_ref, dk_sc, dv_sc, *,
                scale: float, blk: int, heads: int, d: int, dv: int,
                block: int):
    """Scores transposed, (keys, queries), as `fused_attention`'s: the
    word of a query is the row it is stored as."""
    b, h, j, c, i = (pl.program_id(a) for a in range(5))
    n = pl.num_programs(4)
    live, q_block = _walked(table_ref, count_ref,
                            (b * pl.num_programs(1) + h) * n + j, n, i)
    qcols, vcols = _head_cols(heads, d), _head_cols(heads, dv)

    @pl.when(jnp.logical_and(c == 0, i == 0))
    def _():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def step(diagonal: bool):
        k, v = k_ref[...], v_ref[...]
        word = jnp.broadcast_to(w_ref[...], (blk, blk))
        keep = _keep(word, 0, (blk, blk), block,
                     *((q_block * blk, j * blk) if diagonal else ()))
        for g in range(heads):
            q, do = q_ref[:, qcols[g]], do_ref[:, vcols[g]]
            st = lax.dot_general(k, q, _NT,
                                 preferred_element_type=_F32) * scale
            st = jnp.where(keep, st, MASK_VALUE)
            pt = jnp.exp(st - lse_ref[g])
            dv_sc[...] += jnp.dot(pt.astype(do.dtype), do,
                                  preferred_element_type=_F32)
            dpt = lax.dot_general(v, do, _NT,
                                  preferred_element_type=_F32)
            dst = pt * (dpt - di_ref[g])
            dk_sc[...] += jnp.dot(dst.astype(q.dtype), q,
                                  preferred_element_type=_F32)

    _on_walk(live, q_block == j, step)

    @pl.when(jnp.logical_and(c == pl.num_programs(3) - 1, i == n - 1))
    def _():
        dk_ref[...] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _bwd_kernel(table_ref, count_ref, q_ref, k_ref, v_ref, w_ref, do_ref,
                lse_ref, di_ref, dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc,
                *, scale: float, blk: int, heads: int, d: int, dv: int,
                block: int):
    """dQ, dK and dV from one rebuild of a visited block's scores and
    probabilities: 5 products a block and q head where `_dq_kernel` and
    `_dkv_kernel` do 7, and one unpacking of its token mask. The walk is
    the forward's table; the scores are transposed, (keys, queries), as
    `_dkv_kernel` has them, so the word of a query is the row it is
    stored as, and dV and dK go into the kv group's accumulators over
    the whole sequence at the visited block's rows, in `_dkv_kernel`'s
    order (head steps, query blocks ascending, heads). dQ is
    accumulated transposed, K^T @ ds^T (`fused_attention._bwd_kernel`'s
    form): one K transpose a step, dQ^T turned back once a query
    block."""
    b, h, c, i, j = (pl.program_id(a) for a in range(5))
    n = pl.num_programs(4)
    live, k_block = _walked(table_ref, count_ref,
                            (b * pl.num_programs(1) + h) * n + i, n, j)
    qcols, vcols = _head_cols(heads, d), _head_cols(heads, dv)

    @pl.when((c == 0) & (i == 0) & (j == 0))
    def _():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    @pl.when(j == 0)
    def _():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def step(diagonal: bool):
        rows = pl.ds(pl.multiple_of(k_block * blk, blk), blk)
        k, v = k_ref[...], v_ref[...]
        kt = k.T
        word = jnp.broadcast_to(w_ref[...], (blk, blk))
        keep = _keep(word, 0, (blk, blk), block,
                     *((i * blk, k_block * blk) if diagonal else ()))
        for g in range(heads):
            q, do = q_ref[:, qcols[g]], do_ref[:, vcols[g]]
            st = lax.dot_general(k, q, _NT,
                                 preferred_element_type=_F32) * scale
            st = jnp.where(keep, st, MASK_VALUE)
            pt = jnp.exp(st - lse_ref[g])
            dv_sc[rows, :] += jnp.dot(pt.astype(do.dtype), do,
                                      preferred_element_type=_F32)
            dpt = lax.dot_general(v, do, _NT,
                                  preferred_element_type=_F32)
            dst = (pt * (dpt - di_ref[g])).astype(q.dtype)
            dk_sc[rows, :] += jnp.dot(dst, q, preferred_element_type=_F32)
            dq_sc[qcols[g], :] += jnp.dot(kt, dst,
                                          preferred_element_type=_F32)

    _on_walk(live, k_block == i, step)

    @pl.when(j == n - 1)
    def _():
        dq_ref[...] = (dq_sc[...].T * scale).astype(dq_ref.dtype)

    @pl.when((c == pl.num_programs(2) - 1) & (i == n - 1) & (j == n - 1))
    def _():
        dk_ref[...] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _plan(q, k, v, spec: SparseSpec):
    B, L, H, D = q.shape
    Hkv, Dv = k.shape[2], v.shape[3]
    return ((B, L, H, Hkv, D, Dv), step_heads(H, Hkv, D, Dv),
            kernel_block(L, spec))


def _q_major(dims, heads, blk: int, by_kv: bool = False):
    """Grid and specs of the kernels that walk a query block's visited
    key blocks (forward, dQ, the one backward kernel): (grid, q / dQ,
    o / dO, k, v, word, lse / di row). Steps past the count name the
    last visited block, which is resident: nothing is loaded for them.
    The grid is (B, q head steps, query blocks, walk), or `by_kv`
    (B, kv heads, per_kv, query blocks, walk): the same steps in the
    same order, with what a kv group accumulates resident."""
    B, L, H, Hkv, D, Dv = dims
    hs, _, per_kv = heads
    n = L // blk

    def index(f):
        if not by_kv:
            return f
        return lambda b, h, c, i, j, *tc: f(b, h * per_kv + c, i, j, *tc)

    def key_block(b, c, i, j, table, count):
        return _walked(table, count, (b * Hkv + c // per_kv) * n + i, n,
                       j)[1]

    def q_cols(width):
        return pl.BlockSpec((None, blk, hs * width),
                            index(lambda b, c, i, j, *_: (b, i, c)))

    def kv_cols(width):
        return pl.BlockSpec(
            (None, blk, width),
            index(lambda b, c, i, j, *tc: (b, key_block(b, c, i, j, *tc),
                                           c // per_kv)))
    word_spec = pl.BlockSpec(
        (None, None, None, 1, blk),
        index(lambda b, c, i, j, *tc: (b, c // per_kv,
                                       key_block(b, c, i, j, *tc), 0, i)))
    row_spec = pl.BlockSpec((None, hs, 1, blk),
                            index(lambda b, c, i, j, *_: (b, c, 0, i)))
    heads_axes = (Hkv, per_kv) if by_kv else (H // hs,)
    return ((B, *heads_axes, n, n), q_cols(D), q_cols(Dv), kv_cols(D),
            kv_cols(Dv), word_spec, row_spec)


def _forward(q, k, v, words, walk, spec, scale: float, interpret: bool):
    dims, heads, blk = _plan(q, k, v, spec)
    B, L, H, Hkv, D, Dv = dims
    hs, _, per_kv = heads
    vma = _vma(q, k, v)
    grid, q_spec, o_spec, k_spec, v_spec, word_spec, row_spec = _q_major(
        dims, heads, blk)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, blk=blk, heads=hs,
                          per_kv=per_kv, kv_heads=Hkv, d=D, dv=Dv,
                          block=spec.block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=[q_spec, k_spec, v_spec, word_spec],
            out_specs=[o_spec, row_spec],
            scratch_shapes=[pltpu.VMEM((hs, blk, LANES), _F32),
                            pltpu.VMEM((hs, blk, LANES), _F32),
                            pltpu.VMEM((blk, hs * Dv), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((B, L, H * Dv), q.dtype, vma=vma),
                   jax.ShapeDtypeStruct((B, H, 1, L), _F32, vma=vma)],
        compiler_params=_params(3, 4),
        interpret=interpret,
        name="hvd_sparse_attention_fwd",
    )(*walk, q.reshape(B, L, H * D), k.reshape(B, L, Hkv * D),
      v.reshape(B, L, Hkv * Dv), words)
    return o.reshape(B, L, H, Dv), lse


def _backward(q, k, v, words, walk, walk_t, o, lse, do, spec,
              scale: float, interpret: bool):
    """dQ, dK, dV: from `_bwd_kernel` where there is no `walk_t` (the
    rule left it unbuilt, `one_kernel_backward`), else from `_dq_kernel`
    and `_dkv_kernel`."""
    dims, heads, blk = _plan(q, k, v, spec)
    B, L, H, Hkv, D, Dv = dims
    vma = _vma(q, k, v, do)
    di = jnp.sum(o.astype(_F32) * do.astype(_F32), axis=-1)   # (B, L, H)
    di = jnp.swapaxes(di, 1, 2)[:, :, None, :]                # (B, H, 1, L)
    args = (q.reshape(B, L, H * D), k.reshape(B, L, Hkv * D),
            v.reshape(B, L, Hkv * Dv), words, do.reshape(B, L, H * Dv),
            lse, di)
    kw = dict(scale=scale, blk=blk, heads=heads[0], d=D, dv=Dv,
              block=spec.block)
    out_shape = [
        jax.ShapeDtypeStruct((B, L, H * D), q.dtype, vma=vma),
        jax.ShapeDtypeStruct((B, L, Hkv * D), k.dtype, vma=vma),
        jax.ShapeDtypeStruct((B, L, Hkv * Dv), v.dtype, vma=vma)]
    if walk_t is None:
        dq, dk, dv = _one_kernel(dims, heads, blk, kw, out_shape,
                                 interpret, walk, args)
    else:
        dq, dk, dv = _two_kernels(dims, heads, blk, kw, out_shape,
                                  interpret, walk, walk_t, args)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


def _one_kernel(dims, heads, blk, kw, out_shape, interpret, walk, args):
    """dQ, dK, dV as one call over the forward's walk, a kv group's dK /
    dV over the whole sequence resident (their output blocks change
    with (b, kv head) alone: written back once a group)."""
    _, L, _, _, D, Dv = dims
    grid, q_spec, o_spec, k_spec, v_spec, word_spec, row_spec = _q_major(
        dims, heads, blk, by_kv=True)

    def whole(width):
        return pl.BlockSpec((None, L, width),
                            lambda b, h, c, i, j, *_: (b, 0, h))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=[q_spec, k_spec, v_spec, word_spec, o_spec, row_spec,
                      row_spec],
            out_specs=[q_spec, whole(D), whole(Dv)],
            scratch_shapes=[pltpu.VMEM((heads[0] * D, blk), _F32),
                            pltpu.VMEM((L, D), _F32),
                            pltpu.VMEM((L, Dv), _F32)]),
        out_shape=out_shape,
        compiler_params=_params(2, 5, _bwd_vmem(
            dims, heads, blk, out_shape[1].dtype.itemsize)),
        interpret=interpret,
        name="hvd_sparse_attention_bwd",
    )(*walk, *args)


def _two_kernels(dims, heads, blk, kw, out_shape, interpret, walk, walk_t,
                 args):
    """dQ over the forward's walk, then dK/dV over the transposed one:
    each rebuilds the scores of every visited block."""
    B, L, H, Hkv, D, Dv = dims
    hs, _, per_kv = heads
    n = L // blk
    grid, q_spec, o_spec, k_spec, v_spec, word_spec, row_spec = _q_major(
        dims, heads, blk)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, per_kv=per_kv, kv_heads=Hkv, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=[q_spec, k_spec, v_spec, word_spec, o_spec, row_spec,
                      row_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((blk, hs * D), _F32)]),
        out_shape=out_shape[0],
        compiler_params=_params(3, 4),
        interpret=interpret,
        name="hvd_sparse_attention_dq",
    )(*walk, *args)

    # dK/dV walks the query blocks that visit a key block, the q heads
    # of its kv head in `per_kv` steps of `hs`.
    def query_block(b, h, j, c, i, table, count):
        return _walked(table, count, (b * Hkv + h) * n + j, n, i)[1]

    def qg_cols(width):
        return pl.BlockSpec(
            (None, blk, hs * width),
            lambda b, h, j, c, i, *tc: (b, query_block(b, h, j, c, i, *tc),
                                        h * per_kv + c))

    def kvg_cols(width):
        return pl.BlockSpec((None, blk, width),
                            lambda b, h, j, c, i, *_: (b, j, h))
    rowg_spec = pl.BlockSpec(
        (None, hs, 1, blk),
        lambda b, h, j, c, i, *tc: (b, h * per_kv + c, 0,
                                    query_block(b, h, j, c, i, *tc)))
    wordg_spec = pl.BlockSpec(
        (None, None, None, 1, blk),
        lambda b, h, j, c, i, *tc: (b, h, j, 0,
                                    query_block(b, h, j, c, i, *tc)))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, Hkv, n, per_kv, n),
            in_specs=[qg_cols(D), kvg_cols(D), kvg_cols(Dv), wordg_spec,
                      qg_cols(Dv), rowg_spec, rowg_spec],
            out_specs=[kvg_cols(D), kvg_cols(Dv)],
            scratch_shapes=[pltpu.VMEM((blk, D), _F32),
                            pltpu.VMEM((blk, Dv), _F32)]),
        out_shape=out_shape[1:],
        compiler_params=_params(3, 5),
        interpret=interpret,
        name="hvd_sparse_attention_dkv",
    )(*walk_t, *args)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _attention(q, k, v, words, walk, walk_t, spec, scale, interpret):
    return _forward(q, k, v, words, walk, spec, scale, interpret)[0]


def _attention_fwd(q, k, v, words, walk, walk_t, spec, scale, interpret):
    o, lse = _forward(q, k, v, words, walk, spec, scale, interpret)
    return o, (q, k, v, words, walk, walk_t, o, lse)


def _attention_bwd(spec, scale, interpret, residuals, do):
    q, k, v, words, walk, walk_t, o, lse = residuals
    _m_backward.labels(
        kernels="sparse_one" if walk_t is None else "sparse_two").inc()
    return (*_backward(q, k, v, words, walk, walk_t, o, lse, do, spec,
                       scale, interpret), None, None, None)


_attention.defvjp(_attention_fwd, _attention_bwd)


def supported(q_shape, k_shape, v_shape, spec: SparseSpec) -> bool:
    """The shapes the kernels take: the fused kernels' (self-attention
    in 128-blocks, head widths in whole lanes, heads in whole groups),
    a kernel block of whole selection blocks, and one kv head a grid
    step (the q heads of a group share their selection; heads with a
    kv head each would go side by side with one word)."""
    if not (_fused_supported(q_shape, k_shape, v_shape)
            and kernel_block(q_shape[1], spec)):
        return False
    return step_heads(q_shape[2], k_shape[2], q_shape[3],
                      v_shape[3])[1] == 1


def kernels_engage(q, k, v, spec: SparseSpec) -> bool:
    """The engagement rule, on what the call observes: TPU backend,
    bf16 operands, shapes the kernels take."""
    return (jax.default_backend() == "tpu"
            and q.dtype == k.dtype == v.dtype == jnp.bfloat16
            and supported(q.shape, k.shape, v.shape, spec))


def selected_attention(q, k, v, chosen, spec: SparseSpec, *,
                       kernels: Optional[bool] = None,
                       interpret: bool = False) -> jax.Array:
    """softmax over the keys j <= t of the blocks `chosen` (B, Hkv, L,
    L // block) keeps for query t, of q_t . k_j / sqrt(D), times v:
    (B, L, H, Dv). `kernels` forces a path (the tests; None: the
    rule), `interpret` runs the kernels in Pallas's interpreter."""
    scale = float(q.shape[-1] ** -0.5)
    if kernels is None:
        kernels = kernels_engage(q, k, v, spec)
    if not kernels:
        return _masked_attention(q, k, v, chosen, spec, scale)
    words, walk, walk_t = block_tables(
        chosen, kernel_block(q.shape[1], spec), spec,
        transposed=not one_kernel_backward(q.shape, k.shape, v.shape))
    return _attention(q, k, v, words, walk, walk_t, spec, scale,
                      bool(interpret))


def sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     spec: SparseSpec) -> jax.Array:
    """The layer's core on one device's (B, L, H, D) blocks, k / v with
    one head a group of q heads, scores scaled by D^-0.5. Up to
    `spec.dense_len` positions: causal attention through `attention()`
    (`hvd.attn.core`). Longer: `select_blocks` (`hvd.attn.select`; its
    result carries the checkpoint name `SELECTION`), then
    `selected_attention` (`hvd.attn.sparse`)."""
    B, L = q.shape[:2]
    if L <= spec.dense_len:
        _m_traces.labels(path="sparse_dense").inc()
        with device_scope("hvd.attn.core"):
            return attention(q, k, v, causal=True)
    _m_traces.labels(path="sparse_blocks").inc()
    _m_key_blocks.labels(blocks="selected").inc(
        B * k.shape[2] * selected_blocks(L, spec))
    with device_scope("hvd.attn.select"):
        chosen = checkpoint_name(select_blocks(q, k, spec), SELECTION)
    with device_scope("hvd.attn.sparse"):
        return selected_attention(q, k, v, chosen, spec)
