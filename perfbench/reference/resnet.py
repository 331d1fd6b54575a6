"""Plain reference of ResNet v1.5 (arXiv:1512.03385 Table 1 with the
stride on the 3x3 convolution): float32, every convolution and matrix
multiplication at precision "highest", BatchNorm on the statistics of
the batch it is given. Independent of `horovod_tpu/models/`; it reads
only the layout of the weights (flax's names).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Relative tolerances between the system (float32 weights, bf16
# convolutions and activations, f32 BatchNorm statistics) and this
# reference, calibrated on the chip at 224 px on 8 images
# (`python3 -m perfbench.tests.chip_tolerance`, my chip runs, PR 26).
# The system as it is, three seeds: loss off by 4.8e-7 to 1.0e-4,
# gradient norm by 4.9e-4 to 1.0e-3. With every norm gain doubled (a
# dropped term): loss 2.3e-2, norm 1.0. The tolerances are 10 times the
# largest bf16 error seen. What this sample does NOT catch: weights
# rounded to fp8's 3 bits of mantissa moved the loss by 6.7e-5 and the
# norm by 1.8e-4, inside bf16's own error, because every convolution is
# followed by a normalisation over the batch (PERF.md, open questions).
TOLERANCE = {"loss": 1e-3, "grad_norm": 1e-2}


def _conv(x, kernel, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def loss(config, params, batch, carry=None):
    """Mean softmax cross-entropy of `batch` under `params`; `carry`
    (the running statistics) is not read in training mode."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = batch["images"].astype(jnp.float32)
        eps = config["bn_epsilon"]
        x = _conv(x, p["conv_init"]["kernel"], 2, [(3, 3), (3, 3)])
        x = jax.nn.relu(_bn(x, p["bn_init"], eps))
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), "SAME")
        n = 0
        for i, blocks in enumerate(config["stage_sizes"]):
            for j in range(blocks):
                w = p[f"BottleneckBlock_{n}"]
                n += 1
                stride = 2 if i > 0 and j == 0 else 1
                y = jax.nn.relu(_bn(_conv(x, w["Conv_0"]["kernel"]),
                                    w["BatchNorm_0"], eps))
                y = jax.nn.relu(_bn(_conv(y, w["Conv_1"]["kernel"], stride),
                                    w["BatchNorm_1"], eps))
                y = _bn(_conv(y, w["Conv_2"]["kernel"]), w["BatchNorm_2"], eps)
                if "conv_proj" in w:
                    x = _bn(_conv(x, w["conv_proj"]["kernel"], stride),
                            w["norm_proj"], eps)
                x = jax.nn.relu(x + y)
        x = jnp.mean(x, axis=(1, 2))
        logits = x @ p["Dense_0"]["kernel"] + p["Dense_0"]["bias"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            logp, batch["labels"][:, None], axis=-1)
        return -jnp.mean(picked)
