"""Plain reference of the `afmoe` decoder (Arcee Trinity): float32
`jax.numpy`, every matrix multiplication at precision "highest", no
scan over layers, no kernel, no sorted dispatch (every expert held
multiplies every token, and the gates are the mask). Written from the
published `config.json` and the layer equations of the configuration
file (`perfbench/configs/trinity-large-ep32tp4.json`, whose `assumed`
says what the config leaves open), and independent of
`horovod_tpu/models/`; it reads only the layout of the weights and the
configuration file's keys.

  * attention: q, k, v and a gate projected from the normed input;
    RMSNorm with a learned gain over each q and k head; rotate-half
    rope on `sliding_attention` layers only (`full_attention` layers
    have no position encoding); query i sees keys j <= i, and in a
    sliding layer only those with i - j < `sliding_window`; the output
    times sigmoid(gate) goes through W_o, and a norm follows;
  * FFN: dense SwiGLU in the `num_dense_layers` leading layers; else a
    shared expert plus the top `num_experts_per_tok` of sigmoid scores
    + bias, the chosen scores normalised to sum 1 (`route_norm`) and
    scaled by `route_scale`; a norm follows;
  * the embedding scaled by sqrt(hidden) (`mup_enabled`), a final
    norm, an untied head, next-token cross-entropy.

The share: the weights' shapes are this chip's (12 of 48 q heads and 2
of 8 kv heads, 3,072 of the dense FFN's 12,288 columns, 768 of the
shared expert's 3,072, `num_experts` experts from `experts_first` on
under a router `published.num_experts` wide, a slice of the
vocabulary). The router scores all experts; the terms of experts,
heads and columns held elsewhere are left out, here as in the program,
and the norms after W_o and after the FFN normalise what is there.

So that a sample longer than the window fits beside 4.8 GB of float32
weights and as much of gradients, each layer is recomputed in the
backward pass (`jax.checkpoint`), attention walks the queries in
blocks of `QUERY_BLOCK` against all keys, and the experts and the head
walk the tokens in blocks of `TOKEN_BLOCK`. Neither changes the
arithmetic: a block's rows are computed as they would be whole, and
every sum over tokens is the same sum. A layer's q / k / v / gate
projections are one matrix multiplication, and a block's held experts
one batched einsum a matrix: a multiplication at "highest" is about
1 MB of TPU code, and the machine's compile cache holds 192 MiB.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
TOKEN_BLOCK = 1024

# Relative tolerances between the system (bf16 weights and matmuls,
# f32 accumulation, norms, router scores, gate, softmax and loss) and
# this reference on the same bf16 weights, calibrated on the chip at
# the published widths and the sample's 1 x 8192
# (`python3 -m perfbench.tests.chip_tolerance_afmoe`, my chip runs,
# PR 35, calls a and b; PERF.md section 6 has every reading).
# The system as it is, 23 seeds: loss off by 3.5e-6 to 7.1e-5 (median
# 1.4e-5), gradient norm by 6.4e-5 to 2.2e-3 (the next 1.3e-3), either
# sign: top-4 of 256 choices that bf16 flips move the norm, a mean
# over 8,191 positions hardly the loss. So here the loss is the sharp
# number and the norm the blunt one, the other way round from
# `xing4`. Every matrix rounded to 3 bits of mantissa, three seeds:
# loss 2.2e-4, 3.2e-4, 3.4e-4 (norm 2.8e-4 to 4.2e-3: it cannot
# tell). The windowed layers causal without their window: loss
# 4.0e-4, norm 2.2e-2; the output gate dropped: 5.1e-4, 3.8e-2; the
# expert layers' FFN post-norm dropped: 1.6e-4, 0.13. The loss's limit
# is 1.7 times the largest error seen and 0.55 of the smallest
# rounded reading; the norm's is 2.7 times the largest seen and a
# quarter of the smallest of the three dropped terms'.
# What these two numbers cannot see, measured: rope applied on the
# full layers too reads like the system as it is (loss 6.8e-5, 3.7e-5,
# 9.1e-6 and norm 7.3e-4, 4.5e-4, 1.7e-3 at seeds 7, 8, 9): at seeded
# weights q and k are isotropic, a rotation by position leaves the
# distribution of every score as it was, and a mean loss and a global
# gradient norm are statistics of that distribution.
# `tests/test_window_moe.py` holds both layer kinds to 2e-5
# elementwise against this file, which ropes the sliding layers only.
TOLERANCE = {"loss": 1.2e-4, "grad_norm": 6e-3}


def _rmsnorm(x, gain, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x: (batch, seq, heads, head_dim), halves rotated
    (`rotate_half`), no scaling."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _in_blocks(fn, block, *xs):
    """fn over (batch, seq, ...) arrays, `block` positions at a time,
    each block recomputed in the backward pass; fn's results are
    (batch, block, ...) and come back joined along seq."""
    seq = xs[0].shape[1]
    block = min(block, seq)
    assert seq % block == 0, (seq, block)

    def split(a):
        return jnp.moveaxis(
            a.reshape(a.shape[0], seq // block, block, *a.shape[2:]), 1, 0)

    def join(a):
        return jnp.moveaxis(a, 0, 1).reshape(
            a.shape[1], seq, *a.shape[3:])
    out = jax.lax.map(lambda blocks: jax.checkpoint(fn)(*blocks),
                      tuple(split(a) for a in xs))
    return jax.tree.map(join, out)


def attention_sum(config, w, x, kind):
    """(softmax(q k^T / sqrt(d) + mask) v * sigmoid(g)) W_o: the held
    heads' part of the sum after W_o, before the post-norm."""
    dh, eps = config["head_dim"], config["rms_norm_eps"]
    b, s, _ = x.shape
    kv = w["wk"].shape[-1] // dh
    group = w["wq"].shape[-1] // dh // kv
    sliding = kind == "sliding_attention"
    u = _rmsnorm(x, w["attn_norm"], eps)
    joined = u @ jnp.concatenate(
        [w["wq"], w["wg"], w["wk"], w["wv"]], axis=-1)
    q, g, k, v = jnp.split(
        joined, [kv * group * dh, 2 * kv * group * dh,
                 (2 * group + 1) * kv * dh], axis=-1)
    q = _rmsnorm(q.reshape(b, s, kv * group, dh), w["q_norm"], eps)
    k = _rmsnorm(k.reshape(b, s, kv, dh), w["k_norm"], eps)
    v = v.reshape(b, s, kv, dh)
    if sliding:
        theta = float(config["rope_theta"])
        q, k = _rope(q, theta), _rope(k, theta)
    # query head j reads key / value head j // group
    q = q.reshape(b, s, kv, group, dh)
    key_position = jnp.arange(s)

    def block(q_block, position):
        scores = jnp.einsum("bqngd,bknd->bngqk", q_block, k) * dh ** -0.5
        behind = position[0][:, None] - key_position[None, :]
        seen = behind >= 0
        if sliding:
            seen = seen & (behind < config["sliding_window"])
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bngqk,bknd->bqngd",
                          jax.nn.softmax(scores, axis=-1), v)
    out = _in_blocks(block, QUERY_BLOCK, q,
                     jnp.broadcast_to(key_position, (b, s)))
    return (out.reshape(b, s, kv * group * dh) * jax.nn.sigmoid(g)) @ w["wo"]


def attention(config, w, x, kind):
    return _rmsnorm(attention_sum(config, w, x, kind), w["attn_post_norm"],
                    config["rms_norm_eps"])


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(config, w, h):
    """(gates (b, s, E), nonzero at the chosen experts): sigmoid
    scores, the top k of scores + bias, the chosen scores normalised
    to sum 1 and scaled. `n_group` = `topk_group` = 1: no group
    limit."""
    scores = jax.nn.sigmoid(h @ w["router"])
    _, chosen = jax.lax.top_k(scores + w["router_bias"],
                              config["num_experts_per_tok"])
    picked = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1],
                                    dtype=scores.dtype), axis=-2)
    kept = scores * picked
    if config["route_norm"]:
        kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
    return kept * config["route_scale"]


def expert_ffn(config, w, m, shared=True):
    """The held experts' part of the routed sum plus the held columns
    of the shared expert, before the post-norm. Expert e of the router
    is `w_gate[e - experts_first]`."""
    first = config.get("experts_first", 0)
    held = w["w_gate"].shape[0]

    def block(h):
        gates = route(config, w, h)[..., first:first + held]
        hidden = jax.nn.silu(jnp.einsum("bsd,edf->bsef", h, w["w_gate"])) \
            * jnp.einsum("bsd,edf->bsef", h, w["w_up"])
        each = jnp.einsum("bsef,efd->bsed", hidden, w["w_down"])
        out = jnp.sum(gates[..., None] * each, axis=-2)
        if shared:
            out = out + _swiglu(h, w["s_gate"], w["s_up"], w["s_down"])
        return out
    return _in_blocks(block, TOKEN_BLOCK, m)


def layer(config, kind, dense, w, x):
    eps = config["rms_norm_eps"]
    x = x + attention(config, w, x, kind)
    m = _rmsnorm(x, w["mlp_norm"], eps)
    f = _swiglu(m, w["w_gate"], w["w_up"], w["w_down"]) if dense \
        else expert_ffn(config, w, m)
    return x + _rmsnorm(f, w["mlp_post_norm"], eps)


def hidden_states(config, p, tokens):
    """The state after the last layer, before the final norm."""
    x = p["embed"][tokens]
    if config["mup_enabled"]:
        x = x * math.sqrt(config["hidden_size"])
    n_dense = config["num_dense_layers"]
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    for i, kind in enumerate(kinds):
        dense = i < n_dense
        w = jax.tree.map(lambda a: a[i if dense else i - n_dense],
                         p["dense" if dense else "layers"])
        x = jax.checkpoint(
            functools.partial(layer, config, kind, dense))(w, x)
    return x


def loss(config, params, batch, carry=None):
    """Mean next-token cross-entropy over the positions that have a
    target (all but the last)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        tokens = batch["tokens"]
        b, s = tokens.shape
        z = _rmsnorm(hidden_states(config, p, tokens), p["final_norm"],
                     config["rms_norm_eps"])

        def block(z_block, target):
            logp = jax.nn.log_softmax(z_block @ p["head"], axis=-1)
            return jnp.take_along_axis(logp, target[..., None],
                                       axis=-1)[..., 0]
        picked = _in_blocks(block, TOKEN_BLOCK, z,
                            jnp.roll(tokens, -1, axis=1))
        return -jnp.sum(picked[:, :s - 1]) / (b * (s - 1))
