"""Decoder of Mamba-1 mixers with an attention mixer among them (AI21
Jamba: `JambaMambaMixer`, `JambaAttention`, `JambaMLP` of Hugging Face's
`modeling_jamba.py`), as one chip's share of a job that divides every
layer over chips.

  * Every layer: h = x + mixer(rmsnorm(x)), out = h + swiglu(rmsnorm(h)).
  * The Mamba mixer (`MAMBA`): [x, z] = u W_in; x = silu(causal
    depthwise conv of width `d_conv` over x, plus its bias);
    [dt, B, C] = x W_x (`dt_rank`, `d_state`, `d_state` columns), each
    RMS-normed with a gain of its own (Jamba's norms on the SSM's
    inputs); delta = softplus(dt W_dt + b_dt) and A = -exp(A_log) in
    float32; the core is `parallel.selective_scan.selective_scan`
    (the recurrence, the D skip and the silu(z) gate); then W_out.
  * The attention mixer (`ATTN`): grouped-query heads, no position
    encoding, causal softmax over `attention()`, then W_o.
  * The embedding is tied: the head is its transpose.
  * The share. The weights' shapes say what this chip holds: `channels`
    of the Mamba mixer's inner channels (the columns of W_in's x and z
    halves, the conv, W_dt's columns, A_log's and D's rows, W_x's and
    W_out's rows), `n_heads` q heads on `n_kv_heads` kv heads, `d_ff`
    columns of the FFN. What the layer's other chips would add is left
    out and no exchange stands in for it: after W_x (so dt, B and C are
    the held channels' partial sum, and their norms normalise what is
    there), after W_out, after W_o and after the down-projection.
  * The stack: one `lax.scan` over periods of `period_kinds`, the kinds
    static inside the body, and inside it one `lax.scan` over each run
    of layers of one kind (Jamba's period is 7 Mamba layers, the
    attention layer, 6 Mamba layers: a layer's program is compiled
    once a run, not once a layer); each layer checkpointed under
    `remat` (recomputed whole in the backward pass); a kind's layers
    are stacked on the first axis of `params[kind]`.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.ring_attention import attention
from ..parallel.selective_scan import selective_scan
from ..tracing import device_scope
from . import latent_moe
from .transformer import embed_lookup, rmsnorm

_F32 = jnp.float32
MAMBA, ATTN = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab: int = 512
    d_model: int = 64
    # every layer's kind: whole periods of `period_kinds`
    layer_kinds: Tuple[str, ...] = (MAMBA, ATTN, MAMBA, MAMBA)
    period: int = 4
    head_dim: int = 16
    # what this chip holds of a layer (module docstring)
    n_heads: int = 4
    n_kv_heads: int = 1
    d_ff: int = 128
    channels: int = 128
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 8
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = False
    # embed_lookup / vocab_parallel_xent slice the vocabulary over this
    # axis when it is live
    tp_axis: Optional[str] = None

    def __post_init__(self):
        kinds = self.layer_kinds
        if set(kinds) - {MAMBA, ATTN} or not kinds \
                or len(kinds) % self.period \
                or kinds != kinds[:self.period] * (len(kinds) // self.period):
            raise ValueError(
                f"layer_kinds {kinds} must be {MAMBA!r} / {ATTN!r} in "
                f"whole periods of {self.period} that repeat")

    @property
    def period_kinds(self) -> Tuple[str, ...]:
        return self.layer_kinds[:self.period]


def layer_kinds(n_layers: int, period: int, offset: int
                ) -> Tuple[str, ...]:
    """Layer i is attention where i % period == offset, else Mamba
    (`JambaConfig.layers_block_type`)."""
    return tuple(ATTN if i % period == offset else MAMBA
                 for i in range(n_layers))


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(cfg: JambaConfig, key: jax.Array) -> Dict[str, Any]:
    """Matrices and the conv's weights normal(0.02) in `cfg.dtype`,
    norm gains one; in float32 the conv's bias 0, A_log = log(1..N) a
    channel, D = 1 and b_dt = softplus^-1(dt) with dt log-uniform in
    [1e-3, 1e-1] (mamba_ssm's initialisers). `mamba` and `attention`
    stack their kind's layers on the first axis, in the order of
    `layer_kinds`."""
    D, dh, F, Ch = cfg.d_model, cfg.head_dim, cfg.d_ff, cfg.channels
    N, R = cfg.d_state, cfg.dt_rank
    keys = iter(jax.random.split(key, 32))

    def matrix(*shape):
        return (jax.random.normal(next(keys), shape, _F32) * 0.02
                ).astype(cfg.dtype)

    def ffn(n):
        return {"input_norm": jnp.ones((n, D), _F32),
                "mlp_norm": jnp.ones((n, D), _F32),
                "w_gate": matrix(n, D, F), "w_up": matrix(n, D, F),
                "w_down": matrix(n, F, D)}

    def mamba(n):
        dt = jnp.exp(jax.random.uniform(next(keys), (n, Ch), _F32,
                                        math.log(1e-3), math.log(1e-1)))
        return {**ffn(n),
                "in_proj": matrix(n, D, 2 * Ch),
                "conv_w": matrix(n, cfg.d_conv, Ch),
                "conv_b": jnp.zeros((n, Ch), _F32),
                "x_proj": matrix(n, Ch, R + 2 * N),
                "dt_norm": jnp.ones((n, R), _F32),
                "b_norm": jnp.ones((n, N), _F32),
                "c_norm": jnp.ones((n, N), _F32),
                "dt_proj": matrix(n, R, Ch),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, N + 1, dtype=_F32)), (n, Ch, N)),
                "D": jnp.ones((n, Ch), _F32),
                "out_proj": matrix(n, Ch, D)}

    def attention_layer(n):
        q_cols, kv_cols = cfg.n_heads * dh, cfg.n_kv_heads * dh
        return {**ffn(n),
                "wq": matrix(n, D, q_cols), "wk": matrix(n, D, kv_cols),
                "wv": matrix(n, D, kv_cols), "wo": matrix(n, q_cols, D)}

    return {
        "embed": matrix(cfg.vocab, D),
        "final_norm": jnp.ones((D,), _F32),
        MAMBA: mamba(cfg.layer_kinds.count(MAMBA)),
        ATTN: attention_layer(cfg.layer_kinds.count(ATTN)),
    }


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv in float32: out_t = sum_k w[k] x_{t-K+1+k}
    + b, x (B, L, C), w (K, C), as shifted multiply-adds."""
    K, L = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(_F32), ((0, 0), (K - 1, 0), (0, 0)))
    w = w.astype(_F32)
    return sum(xp[:, k:k + L] * w[k] for k in range(K)) + b


def ssm_inputs(cfg: JambaConfig, p, x: jax.Array):
    """The Mamba mixer up to W_x: (x after the conv, z, x W_x), where
    x W_x is the held channels' part of a sum over all channels (the
    one exchange inside the mixer in a job whose tensor axis is
    live)."""
    Ch = cfg.channels
    with device_scope("hvd.ssm.proj"):
        u = rmsnorm(x, p["input_norm"], cfg.norm_eps)
        xz = u @ p["in_proj"]
        xs = jax.nn.silu(causal_conv(xz[..., :Ch], p["conv_w"], p["conv_b"])
                         ).astype(x.dtype)
        return xs, xz[..., Ch:], xs @ p["x_proj"]


def ssm_outputs(cfg: JambaConfig, p, xs, z, dbc) -> jax.Array:
    """The Mamba mixer from x W_x on: the dt / B / C norms, delta, the
    scan, the gate and W_out."""
    N, R, eps = cfg.d_state, cfg.dt_rank, cfg.norm_eps
    with device_scope("hvd.ssm.proj"):
        dt = rmsnorm(dbc[..., :R], p["dt_norm"], eps)
        b = rmsnorm(dbc[..., R:R + N].astype(_F32), p["b_norm"], eps)
        c = rmsnorm(dbc[..., R + N:].astype(_F32), p["c_norm"], eps)
        delta = jax.nn.softplus(jnp.dot(dt, p["dt_proj"],
                                        preferred_element_type=_F32)
                                + p["dt_bias"])
        A = -jnp.exp(p["A_log"])
    with device_scope("hvd.ssm.scan"):
        y = selective_scan(xs, delta, A, b, c, p["D"], z)
    with device_scope("hvd.ssm.proj"):
        return y @ p["out_proj"]


def mamba_sum(cfg: JambaConfig, p, x: jax.Array) -> jax.Array:
    """x: (B, L, D) -> the held channels' part of the sum after W_out."""
    return ssm_outputs(cfg, p, *ssm_inputs(cfg, p, x))


def attention_sum(cfg: JambaConfig, p, x: jax.Array) -> jax.Array:
    """x: (B, L, D) -> the held heads' part of the sum after W_o."""
    B, L, _ = x.shape
    dh = cfg.head_dim
    with device_scope("hvd.attn.proj"):
        u = rmsnorm(x, p["input_norm"], cfg.norm_eps)
        q = (u @ p["wq"]).reshape(B, L, -1, dh)
        k = (u @ p["wk"]).reshape(B, L, -1, dh)
        v = (u @ p["wv"]).reshape(B, L, -1, dh)
    with device_scope("hvd.attn.core"):
        o = attention(q, k, v, causal=True)
    with device_scope("hvd.attn.proj"):
        return o.reshape(B, L, -1) @ p["wo"]


def block(cfg: JambaConfig, p, x: jax.Array, kind: str) -> jax.Array:
    if kind == MAMBA:
        y, scope = mamba_sum(cfg, p, x), "hvd.ssm.proj"
    else:
        y, scope = attention_sum(cfg, p, x), "hvd.attn.proj"
    with device_scope(scope):
        x = x + y
    f = latent_moe.dense_ffn(cfg, p, x)
    with device_scope("hvd.ffn"):
        return x + f


# ---------------------------------------------------------------------------
# Forward + loss
# ---------------------------------------------------------------------------

def forward(cfg: JambaConfig, params, tokens: jax.Array) -> jax.Array:
    """tokens (B, L) -> the hidden state after the last layer, before
    the final norm: (B, L, D)."""
    def layer(kind):
        fn = functools.partial(block, cfg, kind=kind)      # (p, x) -> x
        return jax.checkpoint(fn) if cfg.remat else fn

    kinds = cfg.period_kinds
    # kinds in the period's order, not a set's: the order of the ops,
    # and with it the compile cache's key, must not follow the
    # interpreter's hash seed
    layers = {kind: layer(kind) for kind in dict.fromkeys(kinds)}
    # a period as runs of one kind: (kind, first of the kind in the
    # period, layers), each run one scan, so that a layer's program is
    # compiled once a run and not once a layer
    runs, seen = [], {}
    for kind, run in itertools.groupby(kinds):
        n = len(list(run))
        runs.append((kind, seen.get(kind, 0), n))
        seen[kind] = seen.get(kind, 0) + n

    with device_scope("hvd.embed"):
        x = embed_lookup(cfg, params["embed"], tokens)

    def one_period(x, p):
        for kind, first, n in runs:
            x, _ = lax.scan(lambda x, w: (layers[kind](w, x), None), x,
                            jax.tree.map(lambda a: a[first:first + n],
                                         p[kind]))
        return x, None

    periods = {kind: jax.tree.map(
        lambda a: a.reshape(-1, kinds.count(kind), *a.shape[1:]),
        params[kind]) for kind in dict.fromkeys(kinds)}
    x, _ = lax.scan(one_period, x, periods)
    return x


def loss_fn(cfg: JambaConfig, params, batch) -> jax.Array:
    """Next-token cross-entropy over the positions that have a target,
    the head tied to the embedding. batch: dict(tokens (B, L))."""
    tokens = batch["tokens"]
    z = forward(cfg, params, tokens)
    with device_scope("hvd.head_loss"):
        head = {"head": params["embed"].T,
                "final_norm": params["final_norm"]}
    return latent_moe.head_loss(cfg, head, z, jnp.roll(tokens, -1, axis=1), 1)
