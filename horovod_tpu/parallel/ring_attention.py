"""Ring attention: exact attention over sequences sharded on the `seq`
mesh axis, K/V blocks rotating around the ICI ring via `ppermute`.

Not present in the reference (SURVEY.md §5.7 — Horovod predates the
long-context era; its nearest primitives are alltoall + process sets).
This module supplies the capability the task brief makes first-class:
context parallelism for sequences too long for one chip's HBM.

Design (blockwise / flash-style, after Liu et al. 2023 "Ring
Attention with Blockwise Transformers"):
  - every device holds Q,K,V for its local sequence block;
  - S = seq_axis_size steps; each step computes blockwise attention of
    the resident Q against the currently-held K/V block, accumulating
    (numerator, denominator, running max) in f32 — the log-sum-exp
    merge keeps it exact, not approximate;
  - K/V then rotate one hop (`ppermute`), riding nearest-neighbor ICI
    so comm overlaps the next block's compute under XLA's
    latency-hiding scheduler.

Causality is by *global block position*: block j's keys are fully
visible to block i's queries when j < i, fully masked when j > i, and
triangularly masked when i == j.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..metrics import REGISTRY as _METRICS
from .mesh import SEQ_AXIS


def _blockwise_scores(q, k, scale):
    # q: (B, Lq, H, D), k: (B, Lk, H, D) -> (B, H, Lq, Lk)
    return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                      preferred_element_type=jnp.float32) * scale


def _merge(acc_num, acc_den, acc_max, scores, v):
    """log-sum-exp merge of one K/V block into the accumulators."""
    blk_max = jnp.max(scores, axis=-1, keepdims=True)       # (B,H,Lq,1)
    new_max = jnp.maximum(acc_max, blk_max)
    correction = jnp.exp(acc_max - new_max)
    p = jnp.exp(scores - new_max)                           # (B,H,Lq,Lk)
    num = acc_num * correction + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    den = acc_den * correction + jnp.sum(p, axis=-1, keepdims=True)
    return num, den, new_max


def _ring_body(q, k, v, axis_name: str, causal: bool, scale: float):
    """Runs inside shard_map: q,k,v are this device's blocks
    (B, L, H, D)."""
    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    B, Lq, H, D = q.shape
    qf = q.astype(jnp.float32)

    # Mark accumulators device-varying over the ring axis (shard_map
    # VMA typing: they become varying as soon as a varying block is
    # merged, so the carry must start varying too).
    # Derive accumulators from q so they carry exactly q's varying-axes
    # type (shard_map VMA): zeros/full literals would be unvarying and
    # fail the scan-carry type check under any enclosing mesh axes.
    acc_num = jnp.transpose(qf, (0, 2, 1, 3)) * 0.0     # (B,H,Lq,D)
    acc_den = acc_num[..., :1]                          # (B,H,Lq,1)
    acc_max = acc_den - jnp.inf

    perm = [(i, (i - 1) % n) for i in range(n)]  # send K/V to prev hop
    # so that at step s this device holds block (my_idx + s) % n.

    def step(s, carry):
        acc_num, acc_den, acc_max, k_cur, v_cur = carry
        src_idx = (my_idx + s) % n
        scores = _blockwise_scores(qf, k_cur.astype(jnp.float32), scale)
        if causal:
            qpos = my_idx * Lq + jnp.arange(Lq)[:, None]      # (Lq,1)
            kpos = src_idx * Lq + jnp.arange(k_cur.shape[1])[None, :]
            mask = (kpos <= qpos)[None, None]                 # (1,1,Lq,Lk)
            scores = jnp.where(mask, scores, -jnp.inf)
        blk_num, blk_den, blk_max = _merge(acc_num, acc_den, acc_max,
                                           scores, v_cur)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return blk_num, blk_den, blk_max, k_nxt, v_nxt

    acc_num, acc_den, acc_max, _, _ = lax.fori_loop(
        0, n, step, (acc_num, acc_den, acc_max, k, v))
    # Fully-masked rows (can't happen with causal self-attention over
    # aligned blocks, but guard den==0 anyway).
    out = acc_num / jnp.maximum(acc_den, 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # (B,L,H,D)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = SEQ_AXIS, causal: bool = True,
                   scale: Optional[float] = None) -> jax.Array:
    """Exact ring attention for inputs already sharded over
    `axis_name`. Must be called inside `shard_map` (or any context
    where `axis_name` is bound); q/k/v: (batch, local_len, heads,
    head_dim)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _ring_body(q, k, v, axis_name, causal, float(scale))


# Attention without a live sequence axis has two paths, and attention()
# picks by what it observes (`_flash_supported`): on a TPU, a causal
# self-attention call whose shapes the kernels of fused_attention.py
# take runs fused (no f32 (B, H, L, L) scores in HBM, no masked half);
# everything else, and every backend but the TPU, runs
# dense_attention. Both take a sliding window (`window`: a query sees
# its own position and the window - 1 before it): the fused kernels
# skip the key blocks wholly older than it as they skip those above
# the diagonal, the dense path masks them. A window that reaches the
# whole sequence is no window, and runs the causal program. The ring
# (a live sequence axis) takes none. The kernels declare their
# outputs' varying-axes types, so shard_map's replication checker
# stays on around them.
# History: rounds 4 and 5 measured JAX's stock Pallas flash kernel at
# its default 128-blocks on the flagship model (heads of 64, seq 512)
# and found it 27-37 % slower inside the remat'd layer scan;
# docs/benchmarks.md has those notes and PERF.md (PR 30) what the
# block-tuned kernel measures on the Mistral cells.


def flash_possible_cfg(head_dim: int, seq: int,
                       sp_live: bool = False) -> bool:
    """Whether a train-step builder must turn shard_map's replication
    checker off (`check_vma=not flash_possible_cfg(...)`) because a
    kernel that cannot type its outputs may trace in the model's
    attention. Never, since the fused kernels declare the varying
    axes of their outputs as those of q / k / v: the checker stays on
    for every config. Kept for the builders that ask
    (perfbench/models/transformer.py)."""
    del head_dim, seq, sp_live
    return False


def _fused_qk_width(q, k, v) -> int:
    """The q / k head width the fused kernels are handed; v always
    goes at its own. Equal widths go as they are (heads of 64 keep the
    dense path, as measured), and so does a q / k width the kernels
    take beside another v width (latent attention's 192 against 128,
    two heads a grid step). Any other is zero-padded to a whole number
    of 128 lanes: zero columns of q and k change no score."""
    from .fused_attention import LANES, supported
    width = q.shape[-1]
    if width == v.shape[-1] or supported(q.shape, k.shape, v.shape):
        return width
    return -(-width // LANES) * LANES


def _flash_supported(q, k, v, causal: bool) -> bool:
    """The engagement rule, on what the call observes: TPU backend,
    causal, shapes the kernels take with q / k at `_fused_qk_width`
    (self-attention, L in 128-blocks, head widths in whole lane
    blocks, heads in whole groups), bf16 operands (what the chip has
    measured; f32 callers keep the dense path and its matmul
    precision), and no live sequence-parallel axis on q (ring /
    Ulysses callers keep the path they were tested on)."""
    from . import fused_attention
    if not (q.ndim == k.ndim == v.ndim == 4
            and q.shape[-1] == k.shape[-1]):
        return False
    width = _fused_qk_width(q, k, v)
    return (jax.default_backend() == "tpu" and causal
            and q.dtype == k.dtype == v.dtype == jnp.bfloat16
            and fused_attention.supported(
                (*q.shape[:-1], width), (*k.shape[:-1], width), v.shape)
            and SEQ_AXIS not in jax.typeof(q).vma)


def flash_attention_path(q, k, v, causal: bool, scale: float,
                         window: Optional[int] = None):
    """The fused path: (B, L, H, D) in, k / v with H or fewer (grouped)
    heads, the output at v's head width. v may be narrower than q / k
    (latent attention: 192 / 128): it goes to the kernels as it is,
    and q and k do too where the kernels take their width, else
    zero-padded to `_fused_qk_width`. `scale` is the caller's, never
    derived from a padded width. Causal, with or without a sliding
    `window`; never bidirectional."""
    from .fused_attention import fused_causal_attention
    if not causal:
        raise ValueError("the fused attention kernels are causal, with "
                         "or without a sliding window; they compute no "
                         "bidirectional attention")
    extra = _fused_qk_width(q, k, v) - q.shape[-1]

    def padded(x):
        return x if extra == 0 else jnp.pad(
            x, ((0, 0),) * (x.ndim - 1) + ((0, extra),))
    return fused_causal_attention(padded(q), padded(k), v, scale,
                                  window=window)


def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    scale: Optional[float] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Plain attention, (B, L, H, D) in and out: f32 scores, mask,
    softmax, PV, all materialised. The oracle of the ring-attention
    and fused-kernel tests, and attention()'s path wherever the fused
    kernels do not engage. k / v may carry fewer heads than q
    (grouped-query): each is repeated over its group; the output has
    v's head width. `window` (causal only): query i sees keys j with
    i - window < j <= i."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    reps = q.shape[2] // k.shape[2]
    if reps > 1:
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
    scores = _blockwise_scores(q.astype(jnp.float32),
                               k.astype(jnp.float32), float(scale))
    if causal:
        L, Lk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((L, Lk), bool))
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((L, Lk), bool), -int(window))
        mask = mask[None, None]
        scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


_m_traces = _METRICS.counter(
    "hvd_attention_traces_total",
    "Times attention() was traced, by path: fused (the kernels of "
    "parallel/fused_attention.py), fused_padded_qk (q and k padded to "
    "whole lanes) or dense; with a window that masks something, "
    "fused_window or dense_window. sparse_attention() counts its own: "
    "sparse_dense (at or under dense_len) or sparse_blocks.", ("path",))
_m_key_blocks = _METRICS.counter(
    "hvd_attention_key_blocks_total",
    "Key blocks of one head's forward walk over the fused path's "
    "traces: visited (computed: on or under the diagonal and inside "
    "the window) and causal (on or under the diagonal), equal without "
    "a window. selected: blocks of SparseSpec.block keys the queries "
    "of a sparse_blocks trace keep, from shapes.", ("blocks",))


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = True,
              scale: Optional[float] = None,
              window: Optional[int] = None) -> jax.Array:
    """Softmax attention on one device's (B, L, H, D) blocks over keys
    known when traced (no live sequence axis): fused where
    `_flash_supported` says so, else `dense_attention`. k / v may carry
    fewer (grouped) heads, v another head width than q / k; `scale`
    defaults to q's own width's. `window`: a query sees its own position
    and the window - 1 before it (causal only; one that reaches the whole
    sequence is none). Keys chosen from the data: sparse_attention.py."""
    if window is not None:
        if not causal or int(window) < 1:
            raise ValueError(
                f"a sliding window is causal and holds at least the "
                f"query's own position; got causal={causal}, "
                f"window={window}")
        window = int(window) if window < q.shape[1] else None
    fused = _flash_supported(q, k, v, causal)
    path = "dense" if not fused else (
        "fused" if _fused_qk_width(q, k, v) == q.shape[-1]
        else "fused_padded_qk")
    if window is not None:
        path = "fused_window" if fused else "dense_window"
    _m_traces.labels(path=path).inc()
    if fused:
        from .fused_attention import blocks_visited
        visited, under = blocks_visited(q.shape[1], window)
        _m_key_blocks.labels(blocks="visited").inc(visited)
        _m_key_blocks.labels(blocks="causal").inc(under)
        return flash_attention_path(
            q, k, v, causal,
            float(q.shape[-1] ** -0.5 if scale is None else scale),
            window)
    return dense_attention(q, k, v, causal, scale, window)
