"""Fused causal self-attention for the TPU: online softmax in VMEM, so
the f32 (B, H, L, L) scores and probabilities never reach HBM and the
key blocks above the diagonal are neither loaded nor computed.

Same mathematics as `ring_attention.dense_attention`: operands in the
dtype given (bf16 in training), f32 accumulation in both matmuls, f32
running max and sum, exact softmax. Three kernels under one
`custom_vjp`: forward (also what `jax.checkpoint` runs again), dK/dV,
dQ; the backward kernels rebuild the probabilities from the saved
log-sum-exp, the one residual besides q, k, v and the output.

Layout. q, k, v arrive as the projections leave them, (B, L, H, D),
and are read as (B, L, H*D) with a head a D-wide column block: no
transpose to (B, H, L, D) and back, and K / V keep their own heads
(grouped-query attention: q head h reads kv head h // (H // Hkv)).
One grid step takes the q heads that share a kv head (`heads_per_step`),
so their K / V block is loaded once, and the dK/dV kernel sums the
group in VMEM.

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

LANES = 128
# Query and key block. Swept on the v5e at (2, 32, 2048, 128) and
# (1, 32, 256, 128): PERF.md section 6, PR 30.
BLOCK_CAP = 512
# q heads that share a kv head go through one grid step, which loads
# their K / V block once; at most this many (the Mistral group of 4,
# measured; blocks of 512 x 4 heads of 256 still fit the compiler's
# default VMEM budget, tests/test_chip_compile.py).
HEADS_CAP = 4
# Finite, so that a fully masked row of a diagonal block gives
# exp(MASK - m) = 0 and never inf - inf.
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_F32 = jnp.float32


def block_size(seq: int, cap: int = BLOCK_CAP) -> int:
    """Largest multiple of 128 up to `cap` that divides `seq`; 0 where
    there is none (the caller keeps the dense path)."""
    for b in range(min(cap, seq) // LANES * LANES, 0, -LANES):
        if seq % b == 0:
            return b
    return 0


def supported(q_shape, k_shape, v_shape) -> bool:
    """The shapes the kernels take: self-attention (equal lengths),
    heads in whole groups, L in 128-blocks, D a whole number of
    lanes."""
    B, L, H, D = q_shape
    return (tuple(v_shape) == tuple(k_shape) and len(k_shape) == 4
            and k_shape[0] == B and k_shape[1] == L
            and k_shape[3] == D and k_shape[2] > 0
            and H % k_shape[2] == 0 and D % LANES == 0
            and block_size(L) > 0)


def heads_per_step(group: int, cap: int = HEADS_CAP) -> int:
    """q heads one grid step takes: all that share a kv head, up to
    `cap` (the largest divisor of the group within it)."""
    return max(c for c in range(1, min(group, cap) + 1) if group % c == 0)


def _visible(q_lo, k_lo, q_axis: int, shape):
    """Where key position <= query position, for a score block whose
    first query / key positions are q_lo / k_lo; queries run along
    `q_axis`."""
    qpos = q_lo + lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = k_lo + lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return kpos <= qpos


def _tile(x, width: int):
    """A lane-replicated (rows, 128) value at `width` lanes."""
    return jnp.tile(x, (1, width // LANES))


def _column(row):
    """A (rows,) vector read from a stored row, as a lane-replicated
    (rows, 128) column."""
    return jnp.broadcast_to(jnp.expand_dims(row, -1),
                            (row.shape[0], LANES))


def _head_cols(heads: int, dh: int):
    """The column slice of each head in a (rows, heads * dh) block."""
    return [slice(g * dh, (g + 1) * dh) for g in range(heads)]


def _on_visible(q_lo, bq, k_lo, bk, step):
    """Run `step(masked)` for a block with any visible key: unmasked
    where every key is visible to every query, masked on the
    diagonal, not at all above it."""
    inside = k_lo + bk - 1 <= q_lo
    pl.when(inside)(lambda: step(False))
    pl.when(jnp.logical_and(jnp.logical_not(inside),
                            k_lo <= q_lo + bq - 1))(lambda: step(True))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc,
                *, scale: float, bq: int, bk: int, heads: int, dh: int):
    i, j = pl.program_id(2), pl.program_id(3)
    q_lo, k_lo = i * bq, j * bk
    cols = _head_cols(heads, dh)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, MASK_VALUE)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(masked: bool):
        k, v = k_ref[...], v_ref[...]
        keep = _visible(q_lo, k_lo, 0, (bq, bk)) if masked else None
        for g in range(heads):
            s = lax.dot_general(q_ref[:, cols[g]], k, _NT,
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
            acc_sc[:, cols[g]] = _tile(alpha, dh) * acc_sc[:, cols[g]] \
                + jnp.dot(p.astype(v.dtype), v,
                          preferred_element_type=_F32)
            m_sc[g] = m_next

    _on_visible(q_lo, bq, k_lo, bk, step)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        for g in range(heads):
            l = l_sc[g]
            o_ref[:, cols[g]] = (acc_sc[:, cols[g]]
                                 * _tile(1.0 / l, dh)).astype(o_ref.dtype)
            # (bq, 128) lane-replicated column -> (1, bq) row, the
            # form both backward kernels read.
            lse_ref[g] = (m_sc[g] + jnp.log(l)).T[:1]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               dq_sc, *, scale: float, bq: int, bk: int, heads: int,
               dh: int):
    i, j = pl.program_id(2), pl.program_id(3)
    q_lo, k_lo = i * bq, j * bk
    cols = _head_cols(heads, dh)

    @pl.when(j == 0)
    def _():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def step(masked: bool):
        k, v = k_ref[...], v_ref[...]
        keep = _visible(q_lo, k_lo, 0, (bq, bk)) if masked else None
        for g in range(heads):
            s = lax.dot_general(q_ref[:, cols[g]], k, _NT,
                                preferred_element_type=_F32) * scale
            if masked:
                s = jnp.where(keep, s, MASK_VALUE)
            p = jnp.exp(s - _tile(_column(lse_ref[g, 0]), bk))
            dp = lax.dot_general(do_ref[:, cols[g]], v, _NT,
                                 preferred_element_type=_F32)
            ds = p * (dp - _tile(_column(di_ref[g, 0]), bk))
            dq_sc[:, cols[g]] += jnp.dot(ds.astype(k.dtype), k,
                                         preferred_element_type=_F32)

    _on_visible(q_lo, bq, k_lo, bk, step)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        dq_ref[...] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref,
                dv_ref, dk_sc, dv_sc, *, scale: float, bq: int, bk: int,
                heads: int, dh: int):
    """Scores transposed, (bk, bq): keys along sublanes, so dV and dK
    are plain p^T @ dO and ds^T @ q, and lse / di broadcast as the
    rows they are stored as."""
    j, c, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    q_lo, k_lo = i * bq, j * bk
    cols = _head_cols(heads, dh)

    @pl.when(jnp.logical_and(c == 0, i == 0))
    def _():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def step(masked: bool):
        k, v = k_ref[...], v_ref[...]
        keep = _visible(q_lo, k_lo, 1, (bk, bq)) if masked else None
        for g in range(heads):
            q, do = q_ref[:, cols[g]], do_ref[:, cols[g]]
            st = lax.dot_general(k, q, _NT,
                                 preferred_element_type=_F32) * scale
            if masked:
                st = jnp.where(keep, st, MASK_VALUE)
            pt = jnp.exp(st - lse_ref[g])
            dv_sc[...] += jnp.dot(pt.astype(do.dtype), do,
                                  preferred_element_type=_F32)
            dpt = lax.dot_general(v, do, _NT,
                                  preferred_element_type=_F32)
            dst = pt * (dpt - di_ref[g])
            dk_sc[...] += jnp.dot(dst.astype(q.dtype), q,
                                  preferred_element_type=_F32)

    _on_visible(q_lo, bq, k_lo, bk, step)

    @pl.when(jnp.logical_and(c == pl.num_programs(3) - 1,
                             i == pl.num_programs(4) - 1))
    def _():
        dk_ref[...] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _vma(*xs):
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def _params(n_parallel: int, n_grid: int):
    return pltpu.CompilerParams(dimension_semantics=(
        ("parallel",) * n_parallel
        + ("arbitrary",) * (n_grid - n_parallel)))


def _plan(q, k):
    """What the three calls share: (B, L, H, Hkv, D), the q heads a
    grid step takes, the steps of them a kv head, the block."""
    B, L, H, D = q.shape
    Hkv = k.shape[2]
    hs = heads_per_step(H // Hkv)
    return (B, L, H, Hkv, D), hs, H // Hkv // hs, block_size(L)


def _q_major(dims, hs: int, per_kv: int, blk: int):
    """Grid and specs of the kernels that walk key blocks for a query
    block (forward, dQ): (grid, q / o / dO spec, k / v spec, lse / di
    row spec). One step takes `hs` q heads of one kv head, a
    (blk, hs * D) column block. Keys past the diagonal are not loaded:
    their steps name the block already resident."""
    B, L, H, _, D = dims
    q_spec = pl.BlockSpec((None, blk, hs * D),
                          lambda b, c, i, j: (b, i, c))
    kv_spec = pl.BlockSpec(
        (None, blk, D),
        lambda b, c, i, j: (b, jnp.minimum(j, i), c // per_kv))
    row_spec = pl.BlockSpec((None, hs, 1, blk),
                            lambda b, c, i, j: (b, c, 0, i))
    return (B, H // hs, L // blk, L // blk), q_spec, kv_spec, row_spec


def _forward(q, k, v, scale: float, interpret: bool):
    dims, hs, per_kv, blk = _plan(q, k)
    B, L, H, Hkv, D = dims
    vma = _vma(q, k, v)
    grid, q_spec, kv_spec, row_spec = _q_major(dims, hs, per_kv, blk)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=blk, bk=blk,
                          heads=hs, dh=D),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((B, L, H * D), q.dtype, vma=vma),
                   jax.ShapeDtypeStruct((B, H, 1, L), _F32, vma=vma)],
        scratch_shapes=[pltpu.VMEM((hs, blk, LANES), _F32),
                        pltpu.VMEM((hs, blk, LANES), _F32),
                        pltpu.VMEM((blk, hs * D), _F32)],
        compiler_params=_params(3, 4),
        interpret=interpret,
        name="hvd_fused_attention_fwd",
    )(q.reshape(B, L, H * D), k.reshape(B, L, Hkv * D),
      v.reshape(B, L, Hkv * D))
    return o.reshape(B, L, H, D), lse


def _backward(q, k, v, o, lse, do, scale: float, interpret: bool):
    dims, hs, per_kv, blk = _plan(q, k)
    B, L, H, Hkv, D = dims
    vma = _vma(q, k, v, do)
    di = jnp.sum(o.astype(_F32) * do.astype(_F32), axis=-1)   # (B, L, H)
    di = jnp.swapaxes(di, 1, 2)[:, :, None, :]                # (B, H, 1, L)
    q3, do3 = q.reshape(B, L, H * D), do.reshape(B, L, H * D)
    k3, v3 = k.reshape(B, L, Hkv * D), v.reshape(B, L, Hkv * D)
    kw = dict(scale=scale, bq=blk, bk=blk, heads=hs, dh=D)

    grid, q_spec, kv_spec, row_spec = _q_major(dims, hs, per_kv, blk)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, L, H * D), q.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((blk, hs * D), _F32)],
        compiler_params=_params(3, 4),
        interpret=interpret,
        name="hvd_fused_attention_dq",
    )(q3, k3, v3, do3, lse, di)

    # dK/dV walks query blocks for a key block, a kv head's q heads in
    # `per_kv` steps of `hs`. Query blocks before the diagonal see
    # nothing of key block j: their steps name the first that does.
    qg_spec = pl.BlockSpec(
        (None, blk, hs * D),
        lambda b, h, j, c, i: (b, jnp.maximum(i, j), h * per_kv + c))
    rowg_spec = pl.BlockSpec(
        (None, hs, 1, blk),
        lambda b, h, j, c, i: (b, h * per_kv + c, 0, jnp.maximum(i, j)))
    kvg_spec = pl.BlockSpec((None, blk, D),
                            lambda b, h, j, c, i: (b, j, h))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid=(B, Hkv, L // blk, per_kv, L // blk),
        in_specs=[qg_spec, kvg_spec, kvg_spec, qg_spec, rowg_spec,
                  rowg_spec],
        out_specs=[kvg_spec, kvg_spec],
        out_shape=[jax.ShapeDtypeStruct((B, L, Hkv * D), k.dtype, vma=vma),
                   jax.ShapeDtypeStruct((B, L, Hkv * D), v.dtype, vma=vma)],
        scratch_shapes=[pltpu.VMEM((blk, D), _F32),
                        pltpu.VMEM((blk, D), _F32)],
        compiler_params=_params(3, 5),
        interpret=interpret,
        name="hvd_fused_attention_dkv",
    )(q3, k3, v3, do3, lse, di)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attention(q, k, v, scale, interpret):
    return _forward(q, k, v, scale, interpret)[0]


def _attention_fwd(q, k, v, scale, interpret):
    o, lse = _forward(q, k, v, scale, interpret)
    return o, (q, k, v, o, lse)


def _attention_bwd(scale, interpret, residuals, do):
    q, k, v, o, lse = residuals
    return _backward(q, k, v, o, lse, do, scale, interpret)


_attention.defvjp(_attention_fwd, _attention_bwd)


def fused_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           scale: float, *,
                           interpret: bool = False) -> jax.Array:
    """Causal self-attention, q (B, L, H, D), k / v (B, L, Hkv, D)
    with H a multiple of Hkv, for shapes `supported` takes.
    `interpret` runs the kernels in Pallas's interpreter (the CPU
    tests)."""
    if not supported(q.shape, k.shape, v.shape):
        raise ValueError(
            f"fused attention does not take q {q.shape}, k {k.shape}, "
            f"v {v.shape}: it needs equal lengths in 128-blocks, "
            f"head_dim a multiple of {LANES}, heads in whole groups")
    return _attention(q, k, v, float(scale), bool(interpret))
