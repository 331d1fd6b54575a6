"""Decoder whose period mixes two mixers that share nothing but the
shapes of their projections: block-sparse softmax attention that
selects its keys and linear attention with a fixed decay a head
(OpenBMB MiniCPM-SALA's `minicpm4` and `lightning-attn` layers), under
MiniCPM's residual and logit multipliers, as one chip's share of a job
that divides every layer over chips.

  * Both mixers: q / k / v and a gate g projected from the normed
    input; RMSNorm over each q and k head with one learned gain,
    before any rope; the core's output times sigmoid(g) through W_o.
  * The sparse mixer (`SPARSE`): grouped-query heads, no position
    encoding; the core is `parallel.sparse_attention.sparse_attention`
    (causal attention up to `sparse.dense_len` positions, beyond it
    each query's `topk` blocks of keys).
  * The linear mixer (`LINEAR`): every head its own k and v,
    rotate-half rope on q and k, the core
    `parallel.linear_attention.linear_attention` with the slopes of
    heads `linear_first` .. of `linear_heads_total`, then one RMSNorm
    over the held heads' outputs side by side, a learned gain a
    channel (Lightning Attention's norm over the concatenated heads).
    A norm a head would make position 0 ill-conditioned: there a
    head's output is one value vector times a signed score, its norm
    keeps only the sign, and where |score| < 1e-3 the norm's eps turns
    the score's gradient on at 1 / sqrt(eps).
  * Each sub-layer's output enters the residual times
    `residual_scale` (scale_depth / sqrt(published depth)); the
    embedding is scaled by `embed_scale`, the final norm's output by
    `logit_scale` before the untied head.
  * The share. The weights' shapes say what this chip holds: `n_heads`
    q and `n_kv_heads` kv heads of the sparse mixer, `linear_heads` of
    the linear one, `d_ff` columns of the FFN. What the layer's other
    chips would add after W_o and after the down-projection is left
    out and no exchange stands in for it; the linear mixer's output
    norm takes its mean square over the heads held (the other chips'
    part of it is one number a token).
  * The stack: one `lax.scan` over periods of `period_kinds`, the
    kinds static inside the body, each layer checkpointed under
    `remat` (recomputed whole in the backward pass, except that a
    sparse layer keeps its selection); a kind's layers are stacked on
    the first axis of `params[kind]`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.linear_attention import decay_slopes, linear_attention
from ..parallel.sparse_attention import (SELECTION, SparseSpec,
                                         sparse_attention)
from ..tracing import device_scope
from . import latent_moe
from .transformer import _rope, embed_lookup, rmsnorm

_F32 = jnp.float32
SPARSE, LINEAR = "sparse", "linear"
KEEP_SELECTION = jax.checkpoint_policies.save_only_these_names(SELECTION)


@dataclasses.dataclass(frozen=True)
class SparseLinearConfig:
    vocab: int = 1024
    d_model: int = 64
    # every layer's kind: whole periods of `period_kinds`
    layer_kinds: Tuple[str, ...] = (SPARSE, LINEAR, LINEAR, LINEAR)
    period: int = 4
    head_dim: int = 16
    # what this chip holds of a layer (module docstring)
    n_heads: int = 4
    n_kv_heads: int = 1
    linear_heads: int = 4
    linear_first: int = 0
    linear_heads_total: int = 4
    d_ff: int = 128
    sparse: SparseSpec = SparseSpec(kernel_size=4, kernel_stride=2, block=8,
                                    topk=4, init_blocks=1, window=16,
                                    dense_len=32)
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    embed_scale: float = 12.0
    residual_scale: float = 1.4 / 32 ** 0.5
    logit_scale: float = 1.0 / 16
    dtype: Any = jnp.bfloat16
    remat: bool = False
    # embed_lookup / vocab_parallel_xent slice the vocabulary over this
    # axis when it is live
    tp_axis: Optional[str] = None

    def __post_init__(self):
        kinds = self.layer_kinds
        if set(kinds) - {SPARSE, LINEAR} or not kinds \
                or len(kinds) % self.period \
                or kinds != kinds[:self.period] * (len(kinds) // self.period):
            raise ValueError(
                f"layer_kinds {kinds} must be {SPARSE!r} / {LINEAR!r} in "
                f"whole periods of {self.period} that repeat")

    @property
    def period_kinds(self) -> Tuple[str, ...]:
        return self.layer_kinds[:self.period]


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(cfg: SparseLinearConfig, key: jax.Array) -> Dict[str, Any]:
    """Matrices normal(0.02) in `cfg.dtype`, norm gains one. `sparse`
    and `linear` stack their kind's layers on the first axis, in the
    order of `layer_kinds`."""
    D, dh, F = cfg.d_model, cfg.head_dim, cfg.d_ff
    keys = iter(jax.random.split(key, 32))

    def matrix(*shape):
        return (jax.random.normal(next(keys), shape, _F32) * 0.02
                ).astype(cfg.dtype)

    def layer(n, heads, kv_heads, output_norm):
        q_cols, kv_cols = heads * dh, kv_heads * dh
        return {
            "attn_norm": jnp.ones((n, D), _F32),
            "mlp_norm": jnp.ones((n, D), _F32),
            "q_norm": jnp.ones((n, dh), _F32),
            "k_norm": jnp.ones((n, dh), _F32),
            **({"o_norm": jnp.ones((n, q_cols), _F32)} if output_norm
               else {}),
            "wq": matrix(n, D, q_cols), "wg": matrix(n, D, q_cols),
            "wk": matrix(n, D, kv_cols), "wv": matrix(n, D, kv_cols),
            "wo": matrix(n, q_cols, D),
            "w_gate": matrix(n, D, F), "w_up": matrix(n, D, F),
            "w_down": matrix(n, F, D)}

    return {
        "embed": matrix(cfg.vocab, D),
        "head": matrix(D, cfg.vocab),
        "final_norm": jnp.ones((D,), _F32),
        SPARSE: layer(cfg.layer_kinds.count(SPARSE), cfg.n_heads,
                      cfg.n_kv_heads, False),
        LINEAR: layer(cfg.layer_kinds.count(LINEAR), cfg.linear_heads,
                      cfg.linear_heads, True),
    }


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def mixer_sum(cfg: SparseLinearConfig, p, x: jax.Array, kind: str
              ) -> jax.Array:
    """x: (B, L, D) -> (core * sigmoid(gate)) W_o, the held heads'
    part of the sum after W_o. `kind` is static."""
    B, L, _ = x.shape
    dh, eps = cfg.head_dim, cfg.norm_eps
    with device_scope("hvd.attn.proj"):
        u = rmsnorm(x, p["attn_norm"], eps)
        q = rmsnorm((u @ p["wq"]).reshape(B, L, -1, dh), p["q_norm"], eps)
        k = rmsnorm((u @ p["wk"]).reshape(B, L, -1, dh), p["k_norm"], eps)
        v = (u @ p["wv"]).reshape(B, L, -1, dh)
        gate = jax.nn.sigmoid((u @ p["wg"]).astype(_F32))
        if kind == LINEAR:
            positions = jnp.arange(L)
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
    if kind == LINEAR:
        with device_scope("hvd.attn.linear"):
            o = linear_attention(q, k, v, decay_slopes(
                cfg.linear_heads_total, cfg.linear_first, cfg.linear_heads))
    else:
        o = sparse_attention(q, k, v, cfg.sparse)   # scopes of its own
    with device_scope("hvd.attn.proj"):
        o = o.reshape(B, L, -1)
        if kind == LINEAR:
            o = rmsnorm(o, p["o_norm"], eps)
        o = (o.astype(_F32) * gate).astype(x.dtype)
        return o @ p["wo"]


def ffn_sum(cfg: SparseLinearConfig, p, x: jax.Array) -> jax.Array:
    """The held columns' part of the SwiGLU's sum after the
    down-projection, of the normed input."""
    return latent_moe.dense_ffn(cfg, p, x)


def block(cfg: SparseLinearConfig, p, x: jax.Array, kind: str) -> jax.Array:
    def add(x, y, scope):
        with device_scope(scope):
            return x + (y.astype(_F32) * cfg.residual_scale).astype(x.dtype)
    x = add(x, mixer_sum(cfg, p, x, kind), "hvd.attn.proj")
    return add(x, ffn_sum(cfg, p, x), "hvd.ffn")


# ---------------------------------------------------------------------------
# Forward + loss
# ---------------------------------------------------------------------------

def forward(cfg: SparseLinearConfig, params, tokens: jax.Array) -> jax.Array:
    """tokens (B, L) -> the hidden state after the last layer, before
    the final norm: (B, L, D)."""
    def layer(kind):
        fn = functools.partial(block, cfg, kind=kind)      # (p, x) -> x
        # recomputed whole but for the sparse layer's selection, which
        # is indices: 16 MB at 32k positions against selecting twice
        return jax.checkpoint(fn, policy=KEEP_SELECTION) if cfg.remat \
            else fn

    kinds = cfg.period_kinds
    layers = [layer(kind) for kind in kinds]
    # the i-th layer of a period is the `nth[i]`-th of its kind there
    nth = [kinds[:i].count(kind) for i, kind in enumerate(kinds)]

    with device_scope("hvd.embed"):
        x = embed_lookup(cfg, params["embed"], tokens)
        x = (x.astype(_F32) * cfg.embed_scale).astype(cfg.dtype)

    def one_period(x, p):
        for fn, kind, i in zip(layers, kinds, nth):
            x = fn(jax.tree.map(lambda a: a[i], p[kind]), x)
        return x, None

    periods = {kind: jax.tree.map(
        lambda a: a.reshape(-1, kinds.count(kind), *a.shape[1:]),
        params[kind]) for kind in set(kinds)}
    x, _ = lax.scan(one_period, x, periods)
    return x


def loss_fn(cfg: SparseLinearConfig, params, batch) -> jax.Array:
    """Next-token cross-entropy over the positions that have a target.
    batch: dict(tokens (B, L)). The logit multiplier rides on the
    final norm's gain (a power of two at the published sizes: exact)."""
    tokens = batch["tokens"]
    z = forward(cfg, params, tokens)
    head = {"head": params["head"],
            "final_norm": params["final_norm"] * cfg.logit_scale}
    return latent_moe.head_loss(cfg, head, z, jnp.roll(tokens, -1, axis=1), 1)
