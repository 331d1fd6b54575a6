"""Decoder whose layers differ in kind inside one period: sliding-window
attention with rotary positions in most layers, full causal attention
in every `period`-th, grouped-query heads with per-head q / k norms,
and sparse experts, as one chip's share of a job that divides every
layer over chips. The defaults are Arcee Trinity's `afmoe` layer: no
position encoding on the full layers, an output gate, a norm before
and after each sub-layer, sigmoid-routed experts with a shared one,
leading dense layers. Each of those is a field of the configuration;
JetBrains Mellum 2 turns them all (YaRN on the full layers, no gate,
norms before the sub-layers only, a softmax router, no shared expert,
no dense layer).

  * Attention: q / k / v, and a gate g where `output_gate`, projected
    from the normed input; RMSNorm over each q and k head with a
    learned gain, then a plain rope at `rope_theta` on the windowed
    layers and `full_rope` (none, plain or YaRN) on the full ones; the
    core is `parallel.ring_attention.attention` with `window` or
    without; the output, times sigmoid(g) where there is a gate, goes
    through W_o and, where `post_norms`, the post-norm.
  * FFN: the leading layers dense SwiGLU, the others
    `latent_moe.expert_ffn` with the configuration's `router`
    (sigmoid scores, bias-corrected top-k, normalised and scaled
    gates; or a softmax top-k renormalised) and a shared expert where
    `d_ff_shared` is not 0; the routed part is
    `parallel.moe.expert_share_ffn`. Each is followed by its post-norm
    where `post_norms`.
  * The share. The weights' shapes say what this chip holds: `n_heads`
    q and `n_kv_heads` kv heads (columns of W_q / W_g / W_k / W_v,
    rows of W_o), `d_ff_dense` columns of the dense FFN, `d_ff_shared`
    of the shared expert, `experts_held` experts from `experts_first`
    on under a router of `n_experts`. What the layer's other chips
    would add to the two sums, after W_o and after the FFN's
    down-projections, is left out and no exchange stands in for it:
    the post-norms normalise what is there; without them the partial
    sums join the residual as they are.
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
class Rope:
    """The full layers' rotary positions, halves rotated: plain at
    `theta`, or with `factor` above 1 YaRN's (arXiv:2309.00071):
    `latent_moe.yarn_inv_freq` over `original_len` positions between
    `beta_fast` and `beta_slow`, and cos and sin times
    `attention_factor`, as HF's `attention_scaling` does. The scores
    are then multiplied by the factor squared, what a softmax scale of
    factor^2 / sqrt(head_dim) would give; `attention()` keeps its
    1 / sqrt(head_dim)."""
    theta: float
    factor: float = 1.0
    original_len: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


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
    d_ff_shared: int = 32          # 0: no shared expert
    n_experts: int = 8
    experts_first: int = 0
    experts_held: int = 8
    top_k: int = 2
    routed_scale: float = 1.0
    # "sigmoid" (with a router bias and `routed_scale`) or "softmax"
    # (`latent_moe.expert_ffn`)
    router: str = "sigmoid"
    # rows of a tile of the experts' dispatch buffer
    # (`parallel.moe.expert_share_ffn`; None: the kernels' own)
    dispatch_tile: Optional[int] = None
    norm_eps: float = 1e-5
    # the windowed layers' plain rope, and the full layers' (None: no
    # position encoding)
    rope_theta: float = 10000.0
    full_rope: Optional[Rope] = None
    # the output gate, and a norm after each sub-layer
    output_gate: bool = True
    post_norms: bool = True
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
    and its bias float32, the bias small and non-zero; only the gate,
    post-norms, router bias, shared expert and dense layers the
    configuration has. `dense` and `layers` stack their layers on the
    first axis."""
    D, dh = cfg.d_model, cfg.head_dim
    keys = iter(jax.random.split(key, 64))

    def matrix(*shape, dtype=cfg.dtype, std=0.02):
        return (jax.random.normal(next(keys), shape, _F32) * std
                ).astype(dtype)

    def attention_weights(*lead):
        q_cols, kv_cols = cfg.n_heads * dh, cfg.n_kv_heads * dh
        norms = ("attn_norm", "mlp_norm") + (
            ("attn_post_norm", "mlp_post_norm") if cfg.post_norms else ())
        p = {name: jnp.ones((*lead, D), _F32) for name in norms}
        p["q_norm"] = jnp.ones((*lead, dh), _F32)
        p["k_norm"] = jnp.ones((*lead, dh), _F32)
        p["wq"] = matrix(*lead, D, q_cols)
        if cfg.output_gate:
            p["wg"] = matrix(*lead, D, q_cols)
        p["wk"] = matrix(*lead, D, kv_cols)
        p["wv"] = matrix(*lead, D, kv_cols)
        p["wo"] = matrix(*lead, q_cols, D)
        return p

    def dense_layer(*lead):
        F = cfg.d_ff_dense
        return {**attention_weights(*lead),
                "w_gate": matrix(*lead, D, F), "w_up": matrix(*lead, D, F),
                "w_down": matrix(*lead, F, D)}

    def expert_layer(*lead):
        F, S, E = cfg.d_ff_expert, cfg.d_ff_shared, cfg.experts_held
        p = {**attention_weights(*lead),
             "router": matrix(*lead, D, cfg.n_experts, dtype=_F32)}
        if cfg.router == "sigmoid":
            p["router_bias"] = matrix(*lead, cfg.n_experts, dtype=_F32,
                                      std=0.01)
        p["w_gate"] = matrix(*lead, E, D, F)
        p["w_up"] = matrix(*lead, E, D, F)
        p["w_down"] = matrix(*lead, E, F, D)
        if S:
            p["s_gate"] = matrix(*lead, D, S)
            p["s_up"] = matrix(*lead, D, S)
            p["s_down"] = matrix(*lead, S, D)
        return p

    params = {"embed": matrix(cfg.vocab, D), "head": matrix(D, cfg.vocab),
              "final_norm": jnp.ones((D,), _F32)}
    if cfg.n_dense_layers:
        params["dense"] = dense_layer(cfg.n_dense_layers)
    params["layers"] = expert_layer(len(cfg.layer_kinds) - cfg.n_dense_layers)
    return params


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _full_roped(q: jax.Array, k: jax.Array, rope: Rope):
    """q and k (B, L, heads, dh) with a full layer's rotary positions."""
    if rope.factor == 1.0:
        positions = jnp.arange(q.shape[1])
        return (_rope(q, positions, rope.theta),
                _rope(k, positions, rope.theta))
    inv_freq = latent_moe.yarn_inv_freq(
        q.shape[-1], rope.theta, rope.factor, rope.original_len,
        rope.beta_fast, rope.beta_slow)
    return tuple(latent_moe._rotated(x, inv_freq, rope.attention_factor)
                 for x in (q, k))


def _post_norm(cfg: WindowMoEConfig, p, name: str, y: jax.Array
               ) -> jax.Array:
    """y through the post-norm `name`, or as it is without `post_norms`."""
    return rmsnorm(y, p[name], cfg.norm_eps) if cfg.post_norms else y


def attention_sum(cfg: WindowMoEConfig, p, x: jax.Array, kind: str
                  ) -> jax.Array:
    """x: (B, L, D) -> (attention * sigmoid(gate)) W_o, the held
    heads' part of the sum after W_o, before its post-norm; without
    `output_gate` the attention goes through W_o as it is. `kind` is
    static: a windowed layer ropes q and k at `cfg.rope_theta` and sees
    `cfg.window` keys, a full layer ropes them by `cfg.full_rope`, if
    at all, and sees every key before it."""
    B, L, _ = x.shape
    dh, eps = cfg.head_dim, cfg.norm_eps
    with device_scope("hvd.attn.proj"):
        u = rmsnorm(x, p["attn_norm"], eps)
        q = rmsnorm((u @ p["wq"]).reshape(B, L, -1, dh), p["q_norm"], eps)
        k = rmsnorm((u @ p["wk"]).reshape(B, L, -1, dh), p["k_norm"], eps)
        v = (u @ p["wv"]).reshape(B, L, -1, dh)
        if cfg.output_gate:
            gate = jax.nn.sigmoid((u @ p["wg"]).astype(_F32))
        if kind == WINDOW:
            positions = jnp.arange(L)
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        elif cfg.full_rope is not None:
            q, k = _full_roped(q, k, cfg.full_rope)
    if kind == WINDOW:
        with device_scope("hvd.attn.window"):
            o = attention(q, k, v, causal=True, window=cfg.window)
    else:
        with device_scope("hvd.attn.core"):
            o = attention(q, k, v, causal=True)
    with device_scope("hvd.attn.proj"):
        o = o.reshape(B, L, -1)
        if cfg.output_gate:
            o = (o.astype(_F32) * gate).astype(x.dtype)
        return o @ p["wo"]


def gated_attention(cfg: WindowMoEConfig, p, x: jax.Array, kind: str
                    ) -> jax.Array:
    """The attention sub-layer: norm_post(`attention_sum`)."""
    y = attention_sum(cfg, p, x, kind)
    with device_scope("hvd.attn.proj"):
        return _post_norm(cfg, p, "attn_post_norm", y)


def dense_block(cfg: WindowMoEConfig, p, x: jax.Array, kind: str
                ) -> jax.Array:
    x = x + gated_attention(cfg, p, x, kind)
    f = latent_moe.dense_ffn(cfg, p, x)
    with device_scope("hvd.ffn"):
        return x + _post_norm(cfg, p, "mlp_post_norm", f)


def expert_block(cfg: WindowMoEConfig, p, x: jax.Array, kind: str
                 ) -> jax.Array:
    x = x + gated_attention(cfg, p, x, kind)
    f = latent_moe.expert_ffn(cfg, p, x, shared=cfg.d_ff_shared > 0,
                              tile_m=cfg.dispatch_tile, router=cfg.router)
    with device_scope("hvd.moe.route"):
        return x + _post_norm(cfg, p, "mlp_post_norm", f)


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
