"""Decoder whose layers differ in kind inside one period: sliding-window
attention with rotary positions in most layers, full causal attention
with no position encoding in every `period`-th, gated grouped-query
heads with per-head q / k norms, a norm before and after each
sub-layer, and sparse experts with a shared one (Arcee Trinity's
`afmoe` layer), as one chip's share of a job that divides every layer
over chips.

  * Attention: q / k / v and a gate g projected from the normed input;
    RMSNorm over each q and k head with a learned gain, then rope on
    the windowed layers only; the core is
    `parallel.ring_attention.attention` with `window` or without; the
    output times sigmoid(g) goes through W_o and the post-norm.
  * FFN: the leading layers dense SwiGLU, the others
    `latent_moe.expert_ffn` (sigmoid scores, bias-corrected top-k,
    normalised and scaled gates, a shared expert; the routed part is
    `parallel.moe.expert_share_ffn`), each followed by its post-norm.
  * The share. The weights' shapes say what this chip holds: `n_heads`
    q and `n_kv_heads` kv heads (columns of W_q / W_g / W_k / W_v,
    rows of W_o), `d_ff_dense` columns of the dense FFN, `d_ff_shared`
    of the shared expert, `experts_held` experts from `experts_first`
    on under a router of `n_experts`. What the layer's other chips
    would add to the two sums, after W_o and after the FFN's
    down-projections, is left out and no exchange stands in for it:
    the post-norms normalise what is there.
  * The stack: the leading dense layers unrolled, then one `lax.scan`
    over periods of `period` expert layers whose kinds are static
    inside the scan's body (the first scan over one period of a layer
    pattern, ROADMAP C8); the embedding is scaled by `embed_scale`,
    the head untied.

The blocks (`attention_sum`, `gated_attention`, `dense_block`,
`expert_block`) are functions of (config, one layer's weights,
activations).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.ring_attention import attention
from ..tracing import device_scope
from . import latent_moe
from .transformer import _rope, embed_lookup, rmsnorm

_F32 = jnp.float32
WINDOW, FULL = "window", "full"


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig:
    vocab: int = 1024
    d_model: int = 64
    # every layer's kind, the leading dense layers first; the expert
    # layers' kinds repeat with `period`
    layer_kinds: Tuple[str, ...] = (WINDOW, WINDOW, WINDOW, FULL, WINDOW)
    n_dense_layers: int = 1
    period: int = 4
    window: int = 32
    # what this chip holds of a layer (module docstring)
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    d_ff_dense: int = 96
    d_ff_expert: int = 32
    d_ff_shared: int = 32
    n_experts: int = 8
    experts_first: int = 0
    experts_held: int = 8
    top_k: int = 2
    routed_scale: float = 1.0
    # rows of a tile of the experts' dispatch buffer
    # (`parallel.moe.expert_share_ffn`; None: the kernels' own)
    dispatch_tile: Optional[int] = None
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    embed_scale: float = 8.0
    dtype: Any = jnp.bfloat16
    remat: bool = False
    # embed_lookup / vocab_parallel_xent slice the vocabulary over this
    # axis when it is live
    tp_axis: Optional[str] = None

    def __post_init__(self):
        kinds = self.layer_kinds[self.n_dense_layers:]
        if set(self.layer_kinds) - {WINDOW, FULL} \
                or not kinds or len(kinds) % self.period \
                or kinds != kinds[:self.period] * (len(kinds) // self.period):
            raise ValueError(
                f"layer_kinds {self.layer_kinds} must be {WINDOW!r} / "
                f"{FULL!r}, and after the {self.n_dense_layers} leading "
                f"dense layers whole periods of {self.period} that repeat")

    @property
    def period_kinds(self) -> Tuple[str, ...]:
        first = self.n_dense_layers
        return self.layer_kinds[first:first + self.period]


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(cfg: WindowMoEConfig, key: jax.Array) -> Dict[str, Any]:
    """Matrices normal(0.02) in `cfg.dtype`, norm gains one, the router
    and its bias float32, the bias small and non-zero. `dense` and
    `layers` stack their layers on the first axis."""
    D, dh = cfg.d_model, cfg.head_dim
    keys = iter(jax.random.split(key, 64))

    def matrix(*shape, dtype=cfg.dtype, std=0.02):
        return (jax.random.normal(next(keys), shape, _F32) * std
                ).astype(dtype)

    def attention_weights(*lead):
        q_cols, kv_cols = cfg.n_heads * dh, cfg.n_kv_heads * dh
        return {
            "attn_norm": jnp.ones((*lead, D), _F32),
            "attn_post_norm": jnp.ones((*lead, D), _F32),
            "mlp_norm": jnp.ones((*lead, D), _F32),
            "mlp_post_norm": jnp.ones((*lead, D), _F32),
            "q_norm": jnp.ones((*lead, dh), _F32),
            "k_norm": jnp.ones((*lead, dh), _F32),
            "wq": matrix(*lead, D, q_cols), "wg": matrix(*lead, D, q_cols),
            "wk": matrix(*lead, D, kv_cols),
            "wv": matrix(*lead, D, kv_cols),
            "wo": matrix(*lead, q_cols, D),
        }

    def dense_layer(*lead):
        F = cfg.d_ff_dense
        return {**attention_weights(*lead),
                "w_gate": matrix(*lead, D, F), "w_up": matrix(*lead, D, F),
                "w_down": matrix(*lead, F, D)}

    def expert_layer(*lead):
        F, S, E = cfg.d_ff_expert, cfg.d_ff_shared, cfg.experts_held
        return {**attention_weights(*lead),
                "router": matrix(*lead, D, cfg.n_experts, dtype=_F32),
                "router_bias": matrix(*lead, cfg.n_experts, dtype=_F32,
                                      std=0.01),
                "w_gate": matrix(*lead, E, D, F),
                "w_up": matrix(*lead, E, D, F),
                "w_down": matrix(*lead, E, F, D),
                "s_gate": matrix(*lead, D, S), "s_up": matrix(*lead, D, S),
                "s_down": matrix(*lead, S, D)}

    return {
        "embed": matrix(cfg.vocab, D),
        "head": matrix(D, cfg.vocab),
        "final_norm": jnp.ones((D,), _F32),
        "dense": dense_layer(cfg.n_dense_layers),
        "layers": expert_layer(len(cfg.layer_kinds) - cfg.n_dense_layers),
    }


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def attention_sum(cfg: WindowMoEConfig, p, x: jax.Array, kind: str
                  ) -> jax.Array:
    """x: (B, L, D) -> (attention * sigmoid(gate)) W_o, the held
    heads' part of the sum after W_o, before its post-norm. `kind` is
    static: a windowed layer ropes q and k and sees `cfg.window` keys,
    a full layer has no position encoding and sees every key before
    it."""
    B, L, _ = x.shape
    dh, eps = cfg.head_dim, cfg.norm_eps
    with device_scope("hvd.attn.proj"):
        u = rmsnorm(x, p["attn_norm"], eps)
        q = rmsnorm((u @ p["wq"]).reshape(B, L, -1, dh), p["q_norm"], eps)
        k = rmsnorm((u @ p["wk"]).reshape(B, L, -1, dh), p["k_norm"], eps)
        v = (u @ p["wv"]).reshape(B, L, -1, dh)
        gate = jax.nn.sigmoid((u @ p["wg"]).astype(_F32))
        if kind == WINDOW:
            positions = jnp.arange(L)
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
    if kind == WINDOW:
        with device_scope("hvd.attn.window"):
            o = attention(q, k, v, causal=True, window=cfg.window)
    else:
        with device_scope("hvd.attn.core"):
            o = attention(q, k, v, causal=True)
    with device_scope("hvd.attn.proj"):
        o = (o.reshape(B, L, -1).astype(_F32) * gate).astype(x.dtype)
        return o @ p["wo"]


def gated_attention(cfg: WindowMoEConfig, p, x: jax.Array, kind: str
                    ) -> jax.Array:
    """The attention sub-layer: norm_post(`attention_sum`)."""
    y = attention_sum(cfg, p, x, kind)
    with device_scope("hvd.attn.proj"):
        return rmsnorm(y, p["attn_post_norm"], cfg.norm_eps)


def dense_block(cfg: WindowMoEConfig, p, x: jax.Array, kind: str
                ) -> jax.Array:
    x = x + gated_attention(cfg, p, x, kind)
    f = latent_moe.dense_ffn(cfg, p, x)
    with device_scope("hvd.ffn"):
        return x + rmsnorm(f, p["mlp_post_norm"], cfg.norm_eps)


def expert_block(cfg: WindowMoEConfig, p, x: jax.Array, kind: str
                 ) -> jax.Array:
    x = x + gated_attention(cfg, p, x, kind)
    f = latent_moe.expert_ffn(cfg, p, x, tile_m=cfg.dispatch_tile)
    with device_scope("hvd.moe.route"):
        return x + rmsnorm(f, p["mlp_post_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Forward + loss
# ---------------------------------------------------------------------------

def forward(cfg: WindowMoEConfig, params, tokens: jax.Array) -> jax.Array:
    """tokens (B, L) -> the hidden state after the last layer, before
    the final norm: (B, L, D)."""
    def layer(block, kind):
        fn = functools.partial(block, cfg, kind=kind)      # (p, x) -> x
        return jax.checkpoint(fn) if cfg.remat else fn

    def one(tree, i):
        return jax.tree.map(lambda a: a[i], tree)

    with device_scope("hvd.embed"):
        x = embed_lookup(cfg, params["embed"], tokens)
        x = (x.astype(_F32) * cfg.embed_scale).astype(cfg.dtype)
    for i in range(cfg.n_dense_layers):
        x = layer(dense_block, cfg.layer_kinds[i])(one(params["dense"], i), x)

    layers = [layer(expert_block, kind) for kind in cfg.period_kinds]

    def one_period(x, p):
        for i, fn in enumerate(layers):
            x = fn(one(p, i), x)
        return x, None

    periods = jax.tree.map(
        lambda a: a.reshape(-1, cfg.period, *a.shape[1:]), params["layers"])
    x, _ = lax.scan(one_period, x, periods)
    return x


def loss_fn(cfg: WindowMoEConfig, params, batch) -> jax.Array:
    """Next-token cross-entropy over the positions that have a target.
    batch: dict(tokens (B, L))."""
    tokens = batch["tokens"]
    z = forward(cfg, params, tokens)
    return latent_moe.head_loss(cfg, params, z, jnp.roll(tokens, -1, axis=1),
                                1)
