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


# Single-device flash-attention path (HOROVOD_FLASH_ATTENTION):
# Pallas fused kernel instead of materializing the (B,H,L,L) f32
# score matrix in HBM. Default OFF: standalone the kernel measures
# 2.65x faster fwd+bwd at seq 2048 on v5e, but INSIDE the remat'd
# layer scan it measured 27-37% SLOWER end-to-end (the checkpoint
# policy recomputes the kernel's forward and it serializes against
# XLA's fused pipeline) — see docs/benchmarks.md measured-reject
# note. "1" forces it (and requires check_vma=False on the enclosing
# shard_map — build_train_step threads this); "auto" tries it for
# supported shapes and falls back silently. Read at trace time, like
# the Adasum Pallas switch.
def _flash_mode() -> str:
    from ..common.config import env_value
    v = str(env_value("HOROVOD_FLASH_ATTENTION")).lower()
    v = {"true": "1", "yes": "1", "false": "0", "no": "0",
         "": "0"}.get(v, v)
    if v not in ("0", "1", "auto"):
        raise ValueError(
            f"HOROVOD_FLASH_ATTENTION must be 0/1/auto, got {v!r}")
    return v


def flash_wanted() -> bool:
    """The knob+backend half of the engagement predicate — what the
    train-step builders consult to decide check_vma (the Pallas
    kernel cannot declare vma types, so the replication checker must
    be off wherever flash could trace)."""
    return _flash_mode() in ("1", "auto") and \
        jax.default_backend() == "tpu"


def flash_possible_cfg(head_dim: int, seq: int,
                       sp_live: bool = False) -> bool:
    """Static-config half of the predicate, for builders that know
    the model config but not the runtime tensors: same shape rules as
    _flash_supported. GQA needs no condition — callers repeat KV
    heads to full width before attention(), so the kernel always
    sees k.shape == q.shape. With a live sequence-parallel axis the
    ring path runs instead and flash never traces. Builders keep
    check_vma ON when this is False — flash can never engage, so the
    checker loses nothing."""
    return (flash_wanted() and head_dim in (64, 128, 256)
            and seq % 128 == 0 and not sp_live)


def _flash_supported(q, k) -> bool:
    B, L, H, D = q.shape
    return (jax.default_backend() == "tpu"
            and k.shape == q.shape
            and L % 128 == 0 and D in (64, 128, 256))


def flash_attention_path(q, k, v, causal: bool, scale: float):
    """(B, L, H, D) in/out wrapper over the Pallas TPU flash kernel
    (jax.experimental.pallas.ops.tpu.flash_attention — fused online-
    softmax, custom VJP for the backward kernels)."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as _fa)
    qt = jnp.swapaxes(q, 1, 2)          # (B, H, L, D)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o = _fa(qt, kt, vt, causal=causal, sm_scale=scale)
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = True,
              scale: Optional[float] = None) -> jax.Array:
    """Single-device reference attention with the same (B, L, H, D)
    layout — the correctness oracle for ring_attention tests and the
    path used when the mesh has no live seq axis."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    mode = _flash_mode()
    if mode == "1" or (mode == "auto" and _flash_supported(q, k)):
        return flash_attention_path(q, k, v, causal, float(scale))
    scores = _blockwise_scores(q.astype(jnp.float32),
                               k.astype(jnp.float32), float(scale))
    if causal:
        L, Lk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((L, Lk), bool))[None, None]
        scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
