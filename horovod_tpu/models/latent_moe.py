"""Decoder with latent attention, sparse experts and several residual
streams: the block of today's fine-grained MoE models (DeepSeek-V3's
layer with manifold-constrained hyper-connections around it), as one
chip's share of an expert-parallel job.

  * Residual streams (mHC, arXiv:2512.24880): a token's state is n
    streams; every sub-layer reads a sigmoid-weighted sum of them and
    writes back through a Sinkhorn-normalised n x n mixing matrix
    (`mixed`). The mixer's arithmetic is float32.
  * Latent attention (MLA, arXiv:2405.04434): low-rank q and kv
    projections, per-head no-rope + shared-rope keys (YaRN
    frequencies, arXiv:2309.00071), values narrower than the keys;
    the core is `parallel.ring_attention.attention`.
  * Expert FFN (arXiv:2412.19437): sigmoid scores, bias-corrected
    top-k, normalised and scaled gates (or, as the caller says, a
    softmax top-k renormalised), a shared expert; the routed
    part is `parallel.moe.expert_share_ffn`, told which experts this
    chip holds (`experts_first`, and as many as the weights stack)
    while the router keeps its full width. No pair is dropped.
  * Leading dense layers unrolled, the expert layers under one
    `lax.scan` over stacked weights with the streams as carry, one
    optional multi-token-prediction module after the stack, untied
    head.

The blocks (`mixed`, `latent_attention`, `expert_ffn`, `dense_ffn`)
are functions of (config, one layer's weights, activations), so that a
layer table (ROADMAP C8) can call them. The streams are a tuple of n
(B, L, D) arrays, through the scan's carry too: as one (B, L, n, D)
array the TPU's tiling of the last two dimensions would pad every
buffer fourfold, and as one (n, B, L, D) array every read of a stream
is a slice whose transpose pads a cotangent back to all n (on the v5e
those pads were a quarter of the mixers' backward; PERF.md, PR 31).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..parallel.moe import (expert_share_ffn, topk_sigmoid_route,
                             topk_softmax_route)
from ..parallel.ring_attention import attention
from ..tracing import device_scope
from .transformer import embed_lookup, rmsnorm, vocab_parallel_xent

_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    vocab: int = 1024
    d_model: int = 64
    n_dense_layers: int = 1
    n_expert_layers: int = 2
    n_heads: int = 4
    qk_nope_dim: int = 16
    qk_rope_dim: int = 8
    v_head_dim: int = 16
    q_rank: int = 32
    kv_rank: int = 24
    d_ff_dense: int = 160
    d_ff_expert: int = 32
    # The router's width, the experts this chip holds of it
    # (`experts_first` .. + `experts_held`) and the experts a token
    # chooses.
    n_experts: int = 8
    experts_first: int = 0
    experts_held: int = 8
    top_k: int = 2
    routed_scale: float = 1.0
    # residual streams
    hc_mult: int = 4
    hc_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    norm_eps: float = 1e-6
    # rotary positions on the rope dims, YaRN-blended
    rope_theta: float = 10000.0
    rope_factor: float = 1.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_original_len: int = 4096
    rope_mscale_all_dim: float = 0.0
    # multi-token prediction: one module of depth 1, weighted `mtp_lambda`
    mtp: bool = False
    mtp_lambda: float = 0.3
    dtype: Any = jnp.bfloat16
    remat: bool = False
    # embed_lookup / vocab_parallel_xent slice the vocabulary over this
    # axis when it is live; the share of the vocabulary a chip holds
    # without one is simply a smaller vocabulary.
    tp_axis: Optional[str] = None


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(cfg: LatentMoEConfig, key: jax.Array) -> Dict[str, Any]:
    """Matrices normal(0.02) in `cfg.dtype`, norm gains one, the
    mixers' scalars 0.01 and their biases seeded (normal(0.5), the
    mixing matrix's normal(1): no H is the identity and the Sinkhorn
    iteration has work to do), the router and its bias float32, the
    bias small and non-zero."""
    D, n, H = cfg.d_model, cfg.hc_mult, cfg.n_heads
    keys = iter(jax.random.split(key, 256))

    def matrix(*shape, dtype=cfg.dtype):
        return (jax.random.normal(next(keys), shape, _F32) * 0.02
                ).astype(dtype)

    def seeded(std, *shape):
        return jax.random.normal(next(keys), shape, _F32) * std

    def mixer(*lead):
        return {
            "p_pre": matrix(*lead, n * D, n),
            "p_post": matrix(*lead, n * D, n),
            "p_res": matrix(*lead, n * D, n * n),
            "a_pre": jnp.full(lead, 0.01, _F32),
            "a_post": jnp.full(lead, 0.01, _F32),
            "a_res": jnp.full(lead, 0.01, _F32),
            "b_pre": seeded(0.5, *lead, n), "b_post": seeded(0.5, *lead, n),
            "b_res": seeded(1.0, *lead, n, n),
        }

    def attention_weights(*lead):
        return {
            "hc_attn": mixer(*lead), "hc_ffn": mixer(*lead),
            "attn_norm": jnp.ones((*lead, D), _F32),
            "q_norm": jnp.ones((*lead, cfg.q_rank), _F32),
            "kv_norm": jnp.ones((*lead, cfg.kv_rank), _F32),
            "mlp_norm": jnp.ones((*lead, D), _F32),
            "w_qa": matrix(*lead, D, cfg.q_rank),
            "w_qb": matrix(*lead, cfg.q_rank,
                           H * (cfg.qk_nope_dim + cfg.qk_rope_dim)),
            "w_kva": matrix(*lead, D, cfg.kv_rank + cfg.qk_rope_dim),
            "w_kvb": matrix(*lead, cfg.kv_rank,
                            H * (cfg.qk_nope_dim + cfg.v_head_dim)),
            "w_o": matrix(*lead, H * cfg.v_head_dim, D),
        }

    def dense_layer(*lead):
        F = cfg.d_ff_dense
        return {**attention_weights(*lead),
                "w_gate": matrix(*lead, D, F), "w_up": matrix(*lead, D, F),
                "w_down": matrix(*lead, F, D)}

    def expert_layer(*lead):
        F, E = cfg.d_ff_expert, cfg.experts_held
        return {**attention_weights(*lead),
                "router": matrix(*lead, D, cfg.n_experts, dtype=_F32),
                "router_bias": seeded(0.01, *lead, cfg.n_experts),
                "w_gate": matrix(*lead, E, D, F),
                "w_up": matrix(*lead, E, D, F),
                "w_down": matrix(*lead, E, F, D),
                "s_gate": matrix(*lead, D, F), "s_up": matrix(*lead, D, F),
                "s_down": matrix(*lead, F, D)}

    params = {
        "embed": matrix(cfg.vocab, D),
        "head": matrix(D, cfg.vocab),
        "final_norm": jnp.ones((D,), _F32),
        "dense": dense_layer(cfg.n_dense_layers),
        "layers": expert_layer(cfg.n_expert_layers),
    }
    if cfg.mtp:
        params["mtp"] = {"h_norm": jnp.ones((D,), _F32),
                         "e_norm": jnp.ones((D,), _F32),
                         "w_eh": matrix(2 * D, D),
                         "block": expert_layer()}
    return params


# ---------------------------------------------------------------------------
# Residual streams
# ---------------------------------------------------------------------------

def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """m: (n, n, ...) positive, rows first. `iters` times: every
    column divided by its sum, then every row by its sum."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def mixer_coefficients(cfg: LatentMoEConfig, p, streams):
    """streams: n arrays (B, L, D). Returns H_pre (n, B, L), H_post
    (n, B, L) and H_res (n, n, B, L), float32, the token on the last
    axes. x' P = (x P) / rms(x): the projection reads the streams as
    they lie and the normalisation is one scalar a token."""
    n = len(streams)
    B, L, D = streams[0].shape
    proj = jnp.concatenate([p["p_pre"], p["p_post"], p["p_res"]],
                           axis=-1).reshape(n, D, -1)
    raw = jnp.moveaxis(
        sum(jnp.einsum("bld,dc->blc", streams[j], proj[j],
                       preferred_element_type=_F32) for j in range(n)),
        -1, 0)
    squares = sum(jnp.sum(jnp.square(x.astype(_F32)), axis=-1)
                  for x in streams)
    raw = raw * lax.rsqrt(squares / (n * D) + cfg.hc_eps)
    pre = jax.nn.sigmoid(p["a_pre"] * raw[:n] + p["b_pre"][:, None, None])
    post = 2.0 * jax.nn.sigmoid(p["a_post"] * raw[n:2 * n]
                                + p["b_post"][:, None, None])
    res = p["a_res"] * raw[2 * n:].reshape(n, n, B, L) \
        + p["b_res"][:, :, None, None]
    res = sinkhorn(jnp.exp(jnp.clip(res, *cfg.hc_clamp)), cfg.hc_iters,
                   cfg.hc_eps)
    return pre, post, res


def mixed(cfg: LatentMoEConfig, p, streams,
          sublayer: Callable[[jax.Array], jax.Array]):
    """One sub-layer F between the streams: u = H_pre X, y = F(u),
    X' = H_res X + H_post^T y. Returns X', a tuple like `streams`."""
    n, dtype = len(streams), streams[0].dtype
    with device_scope("hvd.hc"):
        pre, post, res = mixer_coefficients(cfg, p, streams)
        u = sum(pre[j][..., None] * streams[j].astype(_F32)
                for j in range(n)).astype(dtype)
    y = sublayer(u)
    with device_scope("hvd.hc"):
        yf = y.astype(_F32)
        return tuple(
            (sum(res[i, j][..., None] * streams[j].astype(_F32)
                 for j in range(n)) + post[i][..., None] * yf).astype(dtype)
            for i in range(n))


def into_streams(cfg: LatentMoEConfig, x: jax.Array):
    return (x,) * cfg.hc_mult


def out_of_streams(streams) -> jax.Array:
    with device_scope("hvd.hc"):
        return sum(x.astype(_F32) for x in streams).astype(streams[0].dtype)


# ---------------------------------------------------------------------------
# Latent attention
# ---------------------------------------------------------------------------

def yarn_inv_freq(dim: int, base: float, factor: float, original_len: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """The frequencies of `dim` rotary dims under YaRN (arXiv:2309.00071):
    the plain ones at `base` where a dim turns more than `beta_fast`
    times over `original_len` positions, those divided by `factor` where
    it turns fewer than `beta_slow` times, a linear ramp between."""
    def correction_dim(rotations):
        return dim * math.log(original_len / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    high = high + 0.001 if low == high else high
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def softmax_scale(cfg: LatentMoEConfig) -> float:
    """(no-rope + rope width)^-0.5 times YaRN's attention factor
    squared, m = 0.1 * mscale_all_dim * ln(factor) + 1."""
    m = 0.1 * cfg.rope_mscale_all_dim * math.log(cfg.rope_factor) + 1.0
    return (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5 * m * m


def _rotated(x: jax.Array, inv_freq: np.ndarray, scale: float = 1.0
             ) -> jax.Array:
    """x: (B, L, H, rope dims), halves rotated, float32 arithmetic; cos
    and sin times `scale` where it is not 1 (YaRN's attention factor,
    as HF's `attention_scaling` applies it)."""
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[1], dtype=_F32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half].astype(_F32), x[..., half:].astype(_F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def latent_attention(cfg: LatentMoEConfig, p, u: jax.Array) -> jax.Array:
    """u: (B, L, D) -> (B, L, D)."""
    B, L, _ = u.shape
    H, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    eps = cfg.norm_eps
    with device_scope("hvd.attn.proj"):
        inv_freq = yarn_inv_freq(dr, cfg.rope_theta, cfg.rope_factor,
                                 cfg.rope_original_len, cfg.rope_beta_fast,
                                 cfg.rope_beta_slow)
        h = rmsnorm(u, p["attn_norm"], eps)
        q = (rmsnorm(h @ p["w_qa"], p["q_norm"], eps) @ p["w_qb"]
             ).reshape(B, L, H, dn + dr)
        q = jnp.concatenate([q[..., :dn], _rotated(q[..., dn:], inv_freq)],
                            axis=-1)
        kv_a = h @ p["w_kva"]
        k_rope = _rotated(kv_a[:, :, None, cfg.kv_rank:], inv_freq)
        kv = (rmsnorm(kv_a[..., :cfg.kv_rank], p["kv_norm"], eps)
              @ p["w_kvb"]).reshape(B, L, H, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (B, L, H, dr))],
            axis=-1)
        v = kv[..., dn:]
    with device_scope("hvd.attn.core"):
        o = attention(q, k, v, causal=True, scale=softmax_scale(cfg))
    with device_scope("hvd.attn.proj"):
        return (o.reshape(B, L, H * dv) @ p["w_o"]).astype(u.dtype)


# ---------------------------------------------------------------------------
# Feed-forward blocks
# ---------------------------------------------------------------------------

def _swiglu(h, w_gate, w_up, w_down):
    gate = jax.nn.silu((h @ w_gate).astype(_F32))
    up = (h @ w_up).astype(_F32)
    return (gate * up).astype(h.dtype) @ w_down


def dense_ffn(cfg: LatentMoEConfig, p, u: jax.Array) -> jax.Array:
    with device_scope("hvd.ffn"):
        h = rmsnorm(u, p["mlp_norm"], cfg.norm_eps)
        return _swiglu(h, p["w_gate"], p["w_up"], p["w_down"])


def expert_ffn(cfg: LatentMoEConfig, p, u: jax.Array, shared: bool = True,
               tile_m: Optional[int] = None, router: str = "sigmoid"
               ) -> jax.Array:
    """The held experts' part of the routed sum plus the shared expert
    (`shared=False` leaves it out: another chip of the layer counts
    it, or the layer has none). `tile_m`: `expert_share_ffn`'s.
    `router`: "sigmoid" (`topk_sigmoid_route` with the layer's
    `router_bias` and `cfg.routed_scale`) or "softmax"
    (`topk_softmax_route`). u: (B, L, D) -> (B, L, D)."""
    B, L, D = u.shape
    with device_scope("hvd.moe.route"):
        h = rmsnorm(u, p["mlp_norm"], cfg.norm_eps)
        tokens = h.reshape(B * L, D)
        logits = jnp.dot(tokens.astype(_F32), p["router"].astype(_F32),
                         precision=lax.Precision.HIGHEST)
        if router == "softmax":
            experts, gates = topk_softmax_route(logits, cfg.top_k)
        else:
            experts, gates = topk_sigmoid_route(
                logits, p["router_bias"], cfg.top_k, cfg.routed_scale)
    y = expert_share_ffn(
        tokens, experts, gates, p["w_gate"], p["w_up"], p["w_down"],
        first=cfg.experts_first, tile_m=tile_m).reshape(B, L, D)
    if shared:
        with device_scope("hvd.moe.shared"):
            y = y + _swiglu(h, p["s_gate"], p["s_up"],
                            p["s_down"]).astype(_F32)
    return y.astype(u.dtype)


def block(cfg: LatentMoEConfig, p, streams, ffn: Callable):
    """Mixer, latent attention, mixer, FFN."""
    streams = mixed(cfg, p["hc_attn"], streams,
                    lambda u: latent_attention(cfg, p, u))
    return mixed(cfg, p["hc_ffn"], streams, lambda u: ffn(cfg, p, u))


# ---------------------------------------------------------------------------
# Forward + loss
# ---------------------------------------------------------------------------

def _checkpointed(cfg: LatentMoEConfig, fn):
    return jax.checkpoint(fn) if cfg.remat else fn


def forward(cfg: LatentMoEConfig, params, tokens: jax.Array) -> jax.Array:
    """tokens (B, L) -> the streams summed after the last layer,
    before the final norm: (B, L, D)."""
    with device_scope("hvd.embed"):
        streams = into_streams(
            cfg, embed_lookup(cfg, params["embed"], tokens))
    dense = _checkpointed(cfg, lambda p, x: block(cfg, p, x, dense_ffn))
    for i in range(cfg.n_dense_layers):
        streams = dense(jax.tree.map(lambda a: a[i], params["dense"]),
                        streams)
    expert = _checkpointed(cfg, lambda p, x: block(cfg, p, x, expert_ffn))
    streams, _ = lax.scan(lambda x, p: (expert(p, x), None), streams,
                          params["layers"])
    return out_of_streams(streams)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _head_logits(hidden, head, carried_back: float):
    """hidden (B, L, D) @ head (D, V) -> float32 logits. Backward, the
    cotangent goes into both matmuls as it is and `carried_back`
    multiplies their float32 results: see `head_loss`."""
    return jnp.einsum("bld,dv->blv", hidden, head,
                      preferred_element_type=_F32)


def _head_logits_fwd(hidden, head, carried_back):
    return _head_logits(hidden, head, carried_back), (hidden, head)


def _head_logits_bwd(carried_back, residuals, ct):
    hidden, head = residuals
    # the barrier keeps the compiler from folding `carried_back` into
    # an operand again
    ct = lax.optimization_barrier(ct.astype(hidden.dtype))
    d_hidden = jnp.einsum("blv,dv->bld", ct, head,
                          preferred_element_type=_F32) * carried_back
    d_head = jnp.einsum("bld,blv->dv", hidden, ct,
                        preferred_element_type=_F32) * carried_back
    return d_hidden.astype(hidden.dtype), d_head.astype(head.dtype)


_head_logits.defvjp(_head_logits_fwd, _head_logits_bwd)


def head_loss(cfg: LatentMoEConfig, params, z, targets, ahead: int,
               weight: float = 1.0):
    """Final norm, the untied head over this chip's vocabulary, and
    `weight` times the mean cross-entropy over the positions that have
    a target `ahead` tokens on (all but the last `ahead`).

    The mean's weight / N reaches the head's backward matmuls inside
    their float32 cotangent, which the MXU takes as bf16: at the
    target class every position holds the same -weight / N, so its
    rounding is one error shared by all positions, a scale on every
    gradient of the model (on the v5e +0.26 % at weight 0.3, -0.17 %
    at N = 255, nothing at powers of two; PERF.md section 6, PR 31).
    So the cotangent carries the power of two below weight / N, which
    bf16 holds exactly, and the factor left over, between 1 and 2,
    multiplies the matmuls' float32 results."""
    B, L = targets.shape
    mean = weight / (B * (L - ahead))
    power = 2.0 ** math.floor(math.log2(mean))
    with device_scope("hvd.head_loss"):
        hidden = rmsnorm(z, params["final_norm"], cfg.norm_eps)
        head = params["head"].astype(cfg.dtype)
        missing = tuple(jax.typeof(hidden).vma - jax.typeof(head).vma)
        if missing:     # its gradient is summed by the cast's transpose
            head = lax.pcast(head, missing, to="varying")
        logits = _head_logits(hidden.astype(cfg.dtype), head, mean / power)
        nll = vocab_parallel_xent(cfg, logits, targets)
        valid = (jnp.arange(L, dtype=jnp.int32) < L - ahead).astype(_F32)
        scaled = jnp.sum(nll * valid) * power
        # the value is weight x the mean; the gradient is `scaled`'s,
        # which `_head_logits` completes
        return scaled + lax.stop_gradient(scaled * (mean / power - 1.0))


def mtp_hidden(cfg: LatentMoEConfig, params, z, next_tokens) -> jax.Array:
    """The multi-token module: [norm(z_t) | norm(Emb(x_{t+1}))] W_eh,
    copied into the streams, one expert block of its own, streams
    summed."""
    m = params["mtp"]
    with device_scope("hvd.mtp"):
        emb = embed_lookup(cfg, params["embed"], next_tokens)
        joined = jnp.concatenate(
            [rmsnorm(z, m["h_norm"], cfg.norm_eps),
             rmsnorm(emb, m["e_norm"], cfg.norm_eps)], axis=-1)
        streams = into_streams(cfg, (joined @ m["w_eh"]).astype(cfg.dtype))
    streams = _checkpointed(
        cfg, lambda p, x: block(cfg, p, x, expert_ffn))(m["block"], streams)
    return out_of_streams(streams)


def loss_fn(cfg: LatentMoEConfig, params, batch) -> jax.Array:
    """Next-token cross-entropy, plus `mtp_lambda` times the multi-token
    module's second-next-token cross-entropy, each over the positions
    that have a target. batch: dict(tokens (B, L))."""
    tokens = batch["tokens"]
    z = forward(cfg, params, tokens)
    next_tokens = jnp.roll(tokens, -1, axis=1)
    loss = head_loss(cfg, params, z, next_tokens, 1)
    if cfg.mtp and cfg.mtp_lambda > 0:
        z2 = mtp_hidden(cfg, params, z, next_tokens)
        loss = loss + head_loss(
            cfg, params, z2, jnp.roll(tokens, -2, axis=1), 2,
            weight=cfg.mtp_lambda)
    return loss
