"""Plain reference of the latent-attention, sparse-expert,
multi-stream decoder (`Xing4.0-29B-A4B`): float32 `jax.numpy`, every
matrix multiplication at precision "highest", no scan, no remat, no
kernel, no sorted dispatch (every expert held multiplies every token,
and the gates are the mask). Written from the published `config.json` and the papers its
keys come from, and independent of `horovod_tpu/models/`; it reads
only the layout of the weights and the configuration file's keys.

  * residual streams: manifold-constrained hyper-connections
    (arXiv:2512.24880, after arXiv:2409.19606): `hc_mult` streams,
    sigmoid pre / post coefficients, a Sinkhorn-normalised mixing
    matrix (`hc_sinkhorn_iters` times columns, then rows);
  * attention: multi-head latent attention (arXiv:2405.04434) with
    YaRN's blended rotary frequencies (arXiv:2309.00071) on the rope
    dims, one rotary key head shared by all heads;
  * FFN: sigmoid scores, bias-corrected top-k, normalised and scaled
    gates, one shared expert (arXiv:2412.19437); the leading layers
    dense SwiGLU;
  * one multi-token-prediction module (arXiv:2412.19437, section 2.2).

The share: the configuration says which experts this chip holds
(`experts_first`, `n_routed_experts` of `published.n_routed_experts`).
The router scores all of them; the terms of experts held elsewhere
are left out, here as in the program, and the shared expert is whole.
The attention and the head are computed whole: the seeded sample
(`per_chip` 1 x `seq` 256) makes them 8 and 17 MB, so nothing needs
blocks to fit beside the 4.7 GB of float32 weights.

What `config.json` does not say is in the configuration file's
`assumed`, and this file follows it.

A matrix multiplication at "highest" compiles to about 1 MB of TPU
code, and the driver's jitted gradient of `loss` has to fit the
machine's compile cache beside the step (192 MiB there; PERF.md
section 6, PR 31). So what can be one multiplication is one: the
experts held go through one batched einsum a matrix, a mixer's three
projections through one, and the mixing over the `hc_mult` streams is
written as products and sums, which are no matrix multiplications.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Relative tolerances between the system (bf16 weights and matmuls,
# f32 accumulation, f32 mixer, router, softmax and loss) and this
# reference on the same bf16 weights, calibrated on the chip at the
# published widths (`python3 -m perfbench.tests.chip_tolerance_xing4`,
# my chip runs, PR 31; PERF.md section 6 has every reading).
# The system as it is, ten seeds: loss off by 3.2e-5 to 3.4e-4,
# gradient norm by 2.1e-5 to 7.8e-4, either sign (top-k choices that
# bf16 flips are most of it: the routed experts' own gradients differ
# by 15 %). With every matrix rounded to fp8's 3 bits of mantissa:
# loss 4.3e-4, norm 4.1e-3. With the shared expert dropped: loss
# 5.2e-3, norm 0.21. The norm's limit is 2.6 times the largest error
# seen and half the fp8 reading; the loss cannot tell fp8 from bf16
# here (4.3e-4 against 3.4e-4), so its limit only keeps 3 times of
# room above the largest error seen and fp8 fails by the norm.
# What these two numbers cannot see, measured: H_res replaced by the
# identity (loss 1.0e-4, norm 8.5e-4) and 2 Sinkhorn iterations for
# 20 (5.0e-5, 6.8e-4) read like the system as it is: the streams are
# nearly collinear and the mixing rows sum to 1, so at seeded weights
# neither moves a loss or a global gradient norm
# (`tests/test_latent_moe.py` holds both to 2e-5 elementwise).
TOLERANCE = {"loss": 1e-3, "grad_norm": 2e-3}


def _rmsnorm(x, gain, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def yarn_inv_freq(config):
    """Rotary frequencies of the rope dims, blended as YaRN does
    between the published ones (fast dims, kept) and those divided by
    `factor` (slow dims, interpolated), with a linear ramp between the
    dims that turn `beta_fast` and `beta_slow` times over the original
    length."""
    dim, base = config["qk_rope_head_dim"], float(config["rope_theta"])
    scaling = config["rope_scaling"]
    length = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(length / (rotations * 2 * math.pi)) / \
            (2 * math.log(base))
    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    plain = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) /
                    (high - low), 0.0, 1.0)
    return plain / scaling["factor"] * ramp + plain * (1.0 - ramp)


def softmax_scale(config):
    """(nope + rope width)^-0.5 times YaRN's attention factor squared,
    m = 0.1 * mscale_all_dim * ln(factor) + 1."""
    scaling = config["rope_scaling"]
    m = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1.0
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return width ** -0.5 * m * m


def _rope(x, inv_freq):
    """x: (batch, seq, heads, rope dims), halves rotated
    (`rotate_half`). mscale / mscale_all_dim = 1 leaves the amplitude
    alone."""
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def sinkhorn(logits, iterations, eps, clamp):
    """(..., n, n) -> the same, nearly doubly stochastic: exp of the
    clamped logits, then `iterations` times every column divided by
    its sum, then every row by its sum."""
    m = jnp.exp(jnp.clip(logits, clamp[0], clamp[1]))
    for _ in range(iterations):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def mixer_coefficients(config, w, streams, iterations=None):
    """streams: (batch, seq, n, D) -> H_pre (b, s, n), H_post (b, s, n),
    H_res (b, s, n, n)."""
    n = config["hc_mult"]
    b, s = streams.shape[:2]
    x = streams.reshape(b, s, -1)
    x = x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + config["hc_eps"])
    raw = x @ jnp.concatenate([w["p_pre"], w["p_post"], w["p_res"]], -1)
    pre = jax.nn.sigmoid(w["a_pre"] * raw[..., :n] + w["b_pre"])
    post = 2.0 * jax.nn.sigmoid(
        w["a_post"] * raw[..., n:2 * n] + w["b_post"])
    res = w["a_res"] * raw[..., 2 * n:].reshape(b, s, n, n) + w["b_res"]
    res = sinkhorn(
        res, config["hc_sinkhorn_iters"] if iterations is None
        else iterations, config["hc_eps"],
        (config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"]))
    return pre, post, res


def mixed(config, w, streams, sublayer):
    """One sub-layer between the streams: X' = H_res X + H_post^T F(H_pre X)."""
    pre, post, res = mixer_coefficients(config, w, streams)
    y = sublayer(jnp.sum(pre[..., None] * streams, axis=2))
    kept = jnp.sum(res[..., None] * streams[:, :, None, :, :], axis=3)
    return kept + post[..., None] * y[:, :, None, :]


def latent_attention(config, w, u):
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, eps = config["v_head_dim"], config["rms_norm_eps"]
    rank = config["kv_lora_rank"]
    b, s, _ = u.shape
    inv_freq = yarn_inv_freq(config)
    h = _rmsnorm(u, w["attn_norm"], eps)
    q = (_rmsnorm(h @ w["w_qa"], w["q_norm"], eps) @ w["w_qb"]).reshape(
        b, s, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv_freq)], -1)
    kv_a = h @ w["w_kva"]
    k_rope = _rope(kv_a[..., None, rank:], inv_freq)      # one head
    kv = (_rmsnorm(kv_a[..., :rank], w["kv_norm"], eps) @
          w["w_kvb"]).reshape(b, s, heads, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope, (b, s, heads, rope))], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * softmax_scale(config)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                     kv[..., nope:])
    return out.reshape(b, s, heads * dv) @ w["w_o"]


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(config, w, h):
    """(gates (b, s, E), nonzero at the chosen experts): sigmoid
    scores, the top k of scores + bias, the chosen scores normalised
    to sum 1 and scaled. `n_group` = `topk_group` = 1: no group
    limit."""
    k = config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(h @ w["router"])
    _, chosen = jax.lax.top_k(scores + w["router_bias"], k)
    picked = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1],
                                    dtype=scores.dtype), axis=-2)
    kept = scores * picked
    gates = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
    return gates * config["routed_scaling_factor"]


def expert_ffn(config, w, u, shared=True):
    """The held experts' part of the routed sum, plus the shared
    expert. Expert e of the router is `w_gate[e - experts_first]`."""
    h = _rmsnorm(u, w["mlp_norm"], config["rms_norm_eps"])
    gates = route(config, w, h)
    first = config.get("experts_first", 0)
    out = _swiglu(h, w["s_gate"], w["s_up"], w["s_down"]) if shared \
        else jnp.zeros_like(u)
    held = w["w_gate"].shape[0]
    hidden = jax.nn.silu(jnp.einsum("bsd,edf->bsef", h, w["w_gate"])) \
        * jnp.einsum("bsd,edf->bsef", h, w["w_up"])
    each = jnp.einsum("bsef,efd->bsed", hidden, w["w_down"])
    return out + jnp.sum(
        gates[..., first:first + held, None] * each, axis=-2)


def dense_ffn(config, w, u):
    h = _rmsnorm(u, w["mlp_norm"], config["rms_norm_eps"])
    return _swiglu(h, w["w_gate"], w["w_up"], w["w_down"])


def block(config, w, streams, ffn):
    streams = mixed(config, w["hc_attn"], streams,
                    lambda u: latent_attention(config, w, u))
    return mixed(config, w["hc_ffn"], streams, lambda u: ffn(config, w, u))


def _into_streams(config, x):
    return jnp.broadcast_to(x[:, :, None, :],
                            (*x.shape[:2], config["hc_mult"], x.shape[-1]))


def _masked_xent(logits, targets, valid):
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.mean(picked, axis=0) * valid) / jnp.sum(valid)


def loss(config, params, batch, carry=None):
    """Next-token cross-entropy plus `mtp_lambda` times the
    second-next-token cross-entropy of the multi-token module, each
    over the positions that have a target."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        eps = config["rms_norm_eps"]
        tokens = batch["tokens"]
        s = tokens.shape[1]
        position = jnp.arange(s)
        streams = _into_streams(config, p["embed"][tokens])
        for i in range(config["first_k_dense_replace"]):
            w = jax.tree.map(lambda a: a[i], p["dense"])
            streams = block(config, w, streams, dense_ffn)
        for i in range(config["num_hidden_layers"] -
                       config["first_k_dense_replace"]):
            w = jax.tree.map(lambda a: a[i], p["layers"])
            streams = block(config, w, streams, expert_ffn)
        z = jnp.sum(streams, axis=2)
        logits = _rmsnorm(z, p["final_norm"], eps) @ p["head"]
        next_token = jnp.roll(tokens, -1, axis=1)
        total = _masked_xent(logits, next_token,
                             (position < s - 1).astype(jnp.float32))
        if config["num_nextn_predict_layers"]:
            m = p["mtp"]
            joined = jnp.concatenate([
                _rmsnorm(z, m["h_norm"], eps),
                _rmsnorm(p["embed"][next_token], m["e_norm"], eps)], -1)
            streams = block(config, m["block"],
                            _into_streams(config, joined @ m["w_eh"]),
                            expert_ffn)
            logits = _rmsnorm(jnp.sum(streams, axis=2), p["final_norm"],
                              eps) @ p["head"]
            total = total + config["mtp_lambda"] * _masked_xent(
                logits, jnp.roll(tokens, -2, axis=1),
                             (position < s - 2).astype(jnp.float32))
        return total
