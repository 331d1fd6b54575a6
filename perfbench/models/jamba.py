"""`jamba`-shaped configurations (AI21 Jamba: Mamba-1 selective-scan
mixers with an attention layer among them, a SwiGLU after each mixer,
a tied head) through `horovod_tpu.models.jamba`, as one chip's share of
a job that divides every layer over chips: the configuration file says
how many inner channels, heads, FFN columns and vocabulary rows are
held here and carries the published `config.json` keys.
"""

from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Any, Dict

BYTES = 2                # bf16
F32 = 4
# Operations of the recurrence a (channel, state) and token, forward:
# delta A, its exp, the decay times the state, (delta x) B, the sum,
# the readout's multiply and add.
RECURRENCE_FORWARD = 7
# and backward: the adjoint's multiply-add, d(delta x) B's, dB's, dC's,
# a_t h_{t-1} decay (2), d delta's s A (2), dA's s delta (2), the
# adjoint handed on (1), the decay rebuilt (2).
RECURRENCE_BACKWARD = 17


def layer_kinds(config: Dict[str, Any]):
    """Each layer's kind, attention where i % period == offset."""
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    return ["attention" if i % period == offset else "mamba"
            for i in range(config["num_hidden_layers"])]


def head_dim(config: Dict[str, Any]) -> int:
    return config["hidden_size"] // config["published"]["num_attention_heads"]


def matmul_weights_a_token(config: Dict[str, Any]) -> float:
    """Weights one token meets in matrix multiplications and the conv on
    this chip: the held channels, heads, columns and vocabulary rows;
    the embedding lookup is a gather."""
    d, ch = config["hidden_size"], config["mamba_channels_held"]
    n, r = config["mamba_d_state"], config["mamba_dt_rank"]
    ffn = 3 * d * config["ffn_columns_held"]
    mamba = d * 2 * ch + config["mamba_d_conv"] * ch + ch * (r + 2 * n) \
        + r * ch + ch * d + ffn
    q_cols = config["num_attention_heads"] * head_dim(config)
    kv_cols = config["num_key_value_heads"] * head_dim(config)
    attention = d * (q_cols + 2 * kv_cols) + q_cols * d + ffn
    kinds = layer_kinds(config)
    return kinds.count("mamba") * mamba + kinds.count("attention") \
        * attention + d * config["vocab_size"]


def core_flops_a_token(config: Dict[str, Any], seq: int) -> float:
    """Forward operations of the cores a token, summed over the layers:
    attention's QK^T and PV (2 x 128 each) over the causal pairs, a q
    head; the recurrence's own operations a held channel and state."""
    kinds = layer_kinds(config)
    attention = 4 * head_dim(config) * config["num_attention_heads"] \
        * (seq + 1) / 2
    scan = RECURRENCE_FORWARD * config["mamba_channels_held"] \
        * config["mamba_d_state"]
    return kinds.count("attention") * attention + kinds.count("mamba") * scan


def flops_per_unit(config: Dict[str, Any], spec: Dict[str, Any]) -> float:
    """Operations the forward and backward passes require for one
    token: 2 a weight met forward plus the cores' own, times 3 for
    forward + backward. Recompute under remat is not counted."""
    return 3.0 * (2 * matmul_weights_a_token(config)
                  + core_flops_a_token(config, spec["seq"]))


def _scan_layers(config, spec):
    tokens = spec["batch_per_chip"] * spec["seq"]
    return layer_kinds(config).count("mamba"), tokens, \
        config["mamba_channels_held"], config["mamba_d_state"]


def selective_scan_flops(config: Dict[str, Any],
                         spec: Dict[str, Any]) -> float:
    """Operations a training step's `hvd_selective_scan_*` calls must
    do, every layer checkpointed: the forward kernel twice and the
    backward once, by the recurrence's own count a (token, channel,
    state). The backward kernel's rebuild of a chunk's states is how
    it computes them, not required work."""
    layers, tokens, ch, n = _scan_layers(config, spec)
    return float(layers * tokens * ch * n
                 * (2 * RECURRENCE_FORWARD + RECURRENCE_BACKWARD))


def selective_scan_bytes(config: Dict[str, Any],
                         spec: Dict[str, Any]) -> float:
    """Bytes those calls must move, every operand read and every result
    written once: the forward reads x (bf16), delta, B and C (float32)
    and writes y (float32), twice; the backward reads x, delta, B, C
    and dy and writes dx, d delta, dB, dC and dA. The saved entry
    states and the backward's partial sums a lane are how the kernels
    hand work on, not required bytes."""
    layers, tokens, ch, n = _scan_layers(config, spec)
    rows = tokens * ch
    bc = 2 * tokens * n * F32
    forward = rows * (BYTES + F32 + F32) + bc
    backward = rows * (BYTES + F32 + F32 + BYTES + F32) + 2 * bc \
        + ch * n * F32
    return float(layers * (2 * forward + backward))


def library_config(config: Dict[str, Any]):
    import jax.numpy as jnp
    from horovod_tpu.models.jamba import JambaConfig, layer_kinds as kinds
    assert config["hidden_act"] == "silu" and config["tie_word_embeddings"]
    assert config["num_experts"] == config["num_experts_per_tok"] == 1
    assert config["mamba_conv_bias"] and not config["mamba_proj_bias"]
    assert config["sliding_window"] is None
    assert config["mamba_channels_held"] <= \
        config["mamba_expand"] * config["hidden_size"]
    return JambaConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        layer_kinds=kinds(config["num_hidden_layers"],
                          config["attn_layer_period"],
                          config["attn_layer_offset"]),
        period=config["attn_layer_period"], head_dim=head_dim(config),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["ffn_columns_held"],
        channels=config["mamba_channels_held"],
        d_state=config["mamba_d_state"], d_conv=config["mamba_d_conv"],
        dt_rank=config["mamba_dt_rank"], norm_eps=config["rms_norm_eps"],
        # the cells train in bfloat16 (the configuration's `training`);
        # a CPU rehearsal at toy widths names float32
        dtype=jnp.dtype(config.get("dtype", "bfloat16")), remat=True)


def build(config: Dict[str, Any], spec: Dict[str, Any], n_chips: int):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.models import jamba

    seq, vocab = spec["seq"], config["vocab_size"]
    cfg = library_config(config)

    def init(key):
        """The library's parameter tree, filled from `key` as the
        configuration's `assumed.c_initialiser` says, in the types the
        library serves."""
        shapes = jax.eval_shape(lambda k: jamba.init_params(cfg, k), key)
        paths, tree = jax.tree.flatten_with_path(shapes)
        keys = jax.random.split(key, len(paths))
        n = config["mamba_d_state"]

        def made(k, path, s):
            name = jax.tree_util.keystr(path[-1:])[2:-2]
            if name.endswith("norm") or name == "D":
                return jnp.ones(s.shape, s.dtype)
            if name == "conv_b":
                return jnp.zeros(s.shape, s.dtype)
            if name == "A_log":
                return jnp.broadcast_to(jnp.log(jnp.arange(
                    1, n + 1, dtype=s.dtype)), s.shape)
            if name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, s.shape, s.dtype, math.log(1e-3), math.log(1e-1)))
                return dt + jnp.log(-jnp.expm1(-dt))
            return (jax.random.normal(k, s.shape, jnp.float32)
                    * config["initializer_range"]).astype(s.dtype)
        return jax.tree.unflatten(
            tree, [made(k, path, s) for k, (path, s) in zip(keys, paths)]
        ), None

    def loss_fn(params, batch):
        return jamba.loss_fn(cfg, params, batch)

    def tokens_batch(key, n, length):
        return {"tokens": jax.random.randint(key, (n, length), 0, vocab,
                                             jnp.int32)}

    return SimpleNamespace(
        init=init, loss_fn=loss_fn, has_aux=False, carry_key=None,
        optimizer=optax.adamw(1e-4),
        batch_spec={"tokens": P("data")},
        make_batch=lambda key, n: tokens_batch(key, n, seq),
        sample_batch=lambda key, n: tokens_batch(
            key, n * spec["sample"]["per_chip"], spec["sample"]["seq"]),
        units_per_sample=seq,
        flops_per_unit=flops_per_unit(config, spec),
        step_kwargs={})
