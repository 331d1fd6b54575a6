"""Plain reference of the dense decoder: float32 `jax.numpy`, every
matrix multiplication at precision "highest", no scan, no remat, no
kernel. Written from the published description (Mistral 7B,
arXiv:2310.06825: pre-norm RMSNorm, rotate-half rotary embedding,
grouped-query attention, causal softmax, SwiGLU) and independent of
`horovod_tpu/models/`; it reads only the layout of the weights.

Departures from the published model, both forced by the library's
model file and stated in the configuration: the output head is tied to
the embedding, and RMSNorm's epsilon is 1e-6. The sliding window
(4096) is wider than any sequence the cells run, so it masks nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Relative tolerances between the system (bf16 weights and matmuls,
# f32 accumulation, f32 softmax and loss) and this reference on the
# same bf16 weights, calibrated on the chip at the published widths
# (`python3 -m perfbench.tests.chip_tolerance`, my chip runs, PR 26).
# The system as it is, five seeds: loss off by 2.0e-5 to 1.7e-4,
# gradient norm by 6.7e-5 to 1.7e-4. With every weight matrix rounded
# to fp8's 3 bits of mantissa (activations still bf16, so less than a
# real fp8 path would lose): loss 7.5e-4, norm 1.5e-3. With every norm
# gain doubled (a dropped term): loss 0.19, norm 3.2. The tolerances
# are 3 and 5 times the largest bf16 error seen, and below fp8's.
TOLERANCE = {"loss": 5e-4, "grad_norm": 8e-4}


def _rmsnorm(x, gain, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x: (batch, seq, heads, head_dim), halves rotated as in the
    published code (`rotate_half`)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss(config, params, batch, carry=None):
    """Mean next-token cross-entropy of `batch` under `params`."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        heads, kv = (config["num_attention_heads"],
                     config["num_key_value_heads"])
        dh, theta = config["head_dim"], float(config["rope_theta"])
        eps = config["rms_norm_eps"]
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = p["embed"][tokens]
        causal = jnp.tril(jnp.ones((s, s), bool))
        for i in range(config["num_hidden_layers"]):
            w = jax.tree.map(lambda a: a[i], p["layers"])
            h = _rmsnorm(x, w["attn_norm"], eps)
            q = _rope((h @ w["wq"]).reshape(b, s, heads, dh), theta)
            k = _rope((h @ w["wk"]).reshape(b, s, kv, dh), theta)
            v = (h @ w["wv"]).reshape(b, s, kv, dh)
            # query head j reads key/value head j // (heads // kv)
            q = q.reshape(b, s, kv, heads // kv, dh)
            scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) * dh ** -0.5
            scores = jnp.where(causal, scores, -jnp.inf)
            out = jnp.einsum("bgrqk,bkgd->bqgrd",
                             jax.nn.softmax(scores, axis=-1), v)
            x = x + out.reshape(b, s, heads * dh) @ w["wo"]
            h = _rmsnorm(x, w["mlp_norm"], eps)
            x = x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) \
                @ w["w_down"]
        logits = _rmsnorm(x, p["final_norm"], eps) @ p["embed"].T
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            logp, batch["targets"][..., None], axis=-1)
        return -jnp.mean(picked)
