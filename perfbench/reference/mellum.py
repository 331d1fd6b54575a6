"""Plain reference of the `mellum` decoder (JetBrains Mellum 2): float32
`jax.numpy`, every matrix multiplication at precision "highest", no
scan over layers, no kernel, no sorted dispatch (every expert held
multiplies every token, and the gates are the mask). Written from the
published `config.json` and the layer equations of transformers'
Qwen3-MoE / YaRN code as the configuration file
(`perfbench/configs/mellum2-12b-ep4.json`, whose `assumed` says what
the config leaves open) describes them, and independent of
`horovod_tpu/models/`; it reads only the layout of the weights and the
configuration file's keys.

  * attention: RMSNorm, q, k, v projected; RMSNorm with a learned gain
    over each q and k head; rotate-half rope of the layer's kind
    (`rope_parameters`): `default` plain at its theta, `yarn` with the
    ramped frequencies and cos and sin multiplied by `attention_factor`;
    query i sees keys j <= i, and in a sliding layer only those with
    i - j < `sliding_window`; scores scaled by head_dim^-0.5; W_o; the
    result added to the residual;
  * experts: RMSNorm, a softmax over the router's `published.num_experts`
    logits, the top `num_experts_per_tok`, the chosen probabilities
    divided by their sum (`norm_topk_prob`); SwiGLU experts; the sum
    added to the residual;
  * a final norm, an untied head, next-token cross-entropy.

The share: the weights' shapes are this chip's (`num_experts` experts
from `experts_first` on, a slice of the vocabulary). The router scores
all experts; the terms of experts held elsewhere are left out, here as
in the program.

So that a sample of 8,192 positions fits beside 2.4 GB of float32
weights and as much of gradients, each layer is recomputed in the
backward pass (`jax.checkpoint`), attention walks the queries in
blocks of `QUERY_BLOCK` against all keys, and the experts and the head
walk the tokens in blocks of `TOKEN_BLOCK`. Neither changes the
arithmetic: a block's rows are computed as they would be whole, and
every sum over tokens is the same sum.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
TOKEN_BLOCK = 1024

# Relative tolerances between the system (bf16 weights and matmuls,
# f32 accumulation, norms, rope, router, softmax and loss) and this
# reference on the same bf16 weights, calibrated on the chip at the
# published widths and the sample's 1 x 8192
# (`python3 -m perfbench.tests.chip_tolerance_mellum`; PERF.md
# section 6 has every reading), on the configuration's seeded weights
# (`assumed.initialiser`: the embedding normal(1)). The system as it
# is, 10 seeds: loss off by 9e-8 to 2.3e-5, gradient norm by 4.2e-6 to
# 1.3e-4. Every matrix rounded to 3 bits of mantissa: loss 2.6e-4,
# norm 1.4e-3. The five faults, loss | norm: windowed layers causal
# 1.6e-4 | 3.0e-2, YaRN's attention factor dropped 7.0e-5 | 1.6e-2, a
# plain rope on the full layers 1.1e-4 | 1.6e-2, gates not
# renormalised 4.8e-6 | 1.6e-2, a sigmoid router 4.5e-5 | 6.7e-3. The
# norm's limit is 3.9 times the largest error seen and a third of the
# rounded weights' reading, which the loss's also catches (4.3 times
# its limit); the loss's is 2.6 times the largest seen and catches the
# window and the plain rope besides; every fault is outside the norm's
# by 13 times or more.
TOLERANCE = {"loss": 6e-5, "grad_norm": 5e-4}


def _rmsnorm(x, gain, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def inv_freq(params, head_dim):
    """transformers' `_compute_default_rope_parameters` /
    `_compute_yarn_parameters` for one layer kind: (inverse
    frequencies (head_dim / 2,), the factor cos and sin are multiplied
    by)."""
    base = float(params["rope_theta"])
    dims = np.arange(0, head_dim, 2, dtype=np.float64) / head_dim
    plain = 1.0 / base ** dims
    if params["rope_type"] == "default":
        return plain.astype(np.float32), 1.0
    factor = float(params["factor"])
    original = params["original_max_position_embeddings"]

    def correction_dim(rotations):
        return head_dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(params["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(params["beta_slow"])),
               head_dim - 1)
    if low == high:
        high += 0.001
    extrapolation = 1.0 - np.clip(
        (np.arange(head_dim // 2) - low) / (high - low), 0.0, 1.0)
    frequencies = (plain / factor) * (1 - extrapolation) \
        + plain * extrapolation
    return frequencies.astype(np.float32), float(params["attention_factor"])


def _rope(x, params):
    """x: (batch, seq, heads, head_dim): x cos + rotate_half(x) sin,
    cos and sin times the kind's attention factor."""
    frequencies, scale = inv_freq(params, x.shape[-1])
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(frequencies)
    angle = jnp.concatenate([angle, angle], axis=-1)
    cos = (jnp.cos(angle) * scale)[:, None, :]
    sin = (jnp.sin(angle) * scale)[:, None, :]
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


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


def attention(config, w, x, kind):
    """softmax(q k^T / sqrt(d) + mask) v W_o of the normed input: the
    sub-layer's output, before the residual."""
    dh, eps = config["head_dim"], config["rms_norm_eps"]
    b, s, _ = x.shape
    kv = w["wk"].shape[-1] // dh
    group = w["wq"].shape[-1] // dh // kv
    sliding = kind == "sliding_attention"
    u = _rmsnorm(x, w["attn_norm"], eps)
    joined = u @ jnp.concatenate([w["wq"], w["wk"], w["wv"]], axis=-1)
    q, k, v = jnp.split(joined, [kv * group * dh, (group + 1) * kv * dh],
                        axis=-1)
    q = _rmsnorm(q.reshape(b, s, kv * group, dh), w["q_norm"], eps)
    k = _rmsnorm(k.reshape(b, s, kv, dh), w["k_norm"], eps)
    v = v.reshape(b, s, kv, dh)
    params = config["rope_parameters"][kind]
    q, k = _rope(q, params), _rope(k, params)
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
    return out.reshape(b, s, kv * group * dh) @ w["wo"]


def route(config, w, h):
    """(gates (b, s, E), nonzero at the chosen experts): a softmax over
    the router, the top k, the chosen probabilities renormalised."""
    probs = jax.nn.softmax(h @ w["router"], axis=-1)
    _, chosen = jax.lax.top_k(probs, config["num_experts_per_tok"])
    picked = jnp.sum(jax.nn.one_hot(chosen, probs.shape[-1],
                                    dtype=probs.dtype), axis=-2)
    kept = probs * picked
    if config["norm_topk_prob"]:
        kept = kept / jnp.sum(kept, axis=-1, keepdims=True)
    return kept


def expert_ffn(config, w, m):
    """The held experts' part of the routed sum. Expert e of the router
    is `w_gate[e - experts_first]`."""
    first = config.get("experts_first", 0)
    held = w["w_gate"].shape[0]

    def block(h):
        gates = route(config, w, h)[..., first:first + held]
        hidden = jax.nn.silu(jnp.einsum("bsd,edf->bsef", h, w["w_gate"])) \
            * jnp.einsum("bsd,edf->bsef", h, w["w_up"])
        each = jnp.einsum("bsef,efd->bsed", hidden, w["w_down"])
        return jnp.sum(gates[..., None] * each, axis=-2)
    return _in_blocks(block, TOKEN_BLOCK, m)


def layer(config, kind, w, x):
    x = x + attention(config, w, x, kind)
    m = _rmsnorm(x, w["mlp_norm"], config["rms_norm_eps"])
    return x + expert_ffn(config, w, m)


def hidden_states(config, p, tokens):
    """The state after the last layer, before the final norm."""
    x = p["embed"][tokens]
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    for i, kind in enumerate(kinds):
        w = jax.tree.map(lambda a: a[i], p["layers"])
        x = jax.checkpoint(functools.partial(layer, config, kind))(w, x)
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
