"""`afmoe`-shaped configurations (Arcee Trinity: sliding-window and
full attention mixed, gated grouped-query heads with q / k norms, a
norm before and after each sub-layer, sigmoid top-k experts with a
shared one) through `horovod_tpu.models.window_moe`, as one chip's
share of a job that divides every layer over chips: the configuration
file says how many heads, FFN columns, experts and vocabulary rows are
held here and carries the published `config.json` keys.

No (token, expert) pair is dropped: the library's dispatch buffer
takes tokens x min(k, experts held) pairs, more than a batch can send.
"""

from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Any, Dict

ROUTER_BIAS_STD = 0.01   # `assumed.router_bias` of the configuration
SLIDING = "sliding_attention"
BYTES = 2                # bf16


def layer_kinds(config: Dict[str, Any]):
    """`layer_types` of the layers that are run: the first
    `num_hidden_layers` of the published list."""
    return config["layer_types"][:config["num_hidden_layers"]]


def matmul_weights_a_token(config: Dict[str, Any]) -> float:
    """Weights one token meets in matrix multiplications on this chip:
    the held heads, columns and vocabulary rows, the routed experts at
    their expectation here (`num_experts_per_tok` x held / router
    width of an expert a token); the embedding lookup is a gather."""
    d, dh = config["hidden_size"], config["head_dim"]
    q_cols = config["num_attention_heads"] * dh
    kv_cols = config["num_key_value_heads"] * dh
    attention = d * (2 * q_cols + 2 * kv_cols) + q_cols * d
    expert = 3 * d * config["moe_intermediate_size"]
    routed = config["num_experts_per_tok"] * config["num_experts"] \
        / config["published"]["num_experts"]
    expert_layer = attention + d * config["published"]["num_experts"] \
        + 3 * d * config["shared_columns_held"] + routed * expert
    dense_layer = attention + 3 * d * config["dense_columns_held"]
    n_dense = config["num_dense_layers"]
    return n_dense * dense_layer \
        + (config["num_hidden_layers"] - n_dense) * expert_layer \
        + d * config["vocab_size"]


def visible_pairs(seq: int, window=None) -> int:
    """(query, key) pairs of one head that the mask leaves: key <=
    query, and with a window query - key < window."""
    reach = seq if window is None else min(window, seq)
    return reach * (reach + 1) // 2 + (seq - reach) * reach


def pairs_a_step(config: Dict[str, Any], spec: Dict[str, Any]) -> int:
    """Visible pairs of one held q head, summed over the layers that
    are run: the sliding layers under their window, the full ones
    under the causal mask."""
    seq = spec["seq"]
    return spec["batch_per_chip"] * sum(
        visible_pairs(seq, config["sliding_window"]
                      if kind == SLIDING else None)
        for kind in layer_kinds(config))


def flops_per_unit(config: Dict[str, Any], spec: Dict[str, Any]) -> float:
    """Operations the forward and backward passes require for one
    token: 2 a weight met forward, plus QK^T and PV (2 x 128 each)
    over exactly the (query, key) pairs each layer kind's mask leaves,
    window and causal mask both (the older adapters count the whole
    seq x seq square, masked half included; this one does not, so its
    `mfu.tok` is the stricter reading), times 3 for forward +
    backward. Recompute under remat is not counted."""
    tokens = spec["batch_per_chip"] * spec["seq"]
    core = 4 * config["head_dim"] * config["num_attention_heads"] \
        * pairs_a_step(config, spec) / tokens
    return 3.0 * (2 * matmul_weights_a_token(config) + core)


def attention_flops(config: Dict[str, Any], spec: Dict[str, Any]) -> float:
    """Operations a training step's `hvd_fused_attention_*` calls must
    do for the visible pairs, every layer checkpointed: the forward
    kernel twice (QK^T, PV), dQ once (QK^T, dO V^T, dS K) and dK/dV
    once (QK^T, P^T dO, V dO^T, dS^T Q): 11 products of 2 x 128
    operations a pair and q head. Blocks the mask cuts are computed
    whole by the kernels; that is not required work."""
    return 11 * 2.0 * config["head_dim"] * config["num_attention_heads"] \
        * pairs_a_step(config, spec)


def attention_bytes(config: Dict[str, Any], spec: Dict[str, Any]) -> float:
    """Bytes those calls must move: each reads q, k, v (the backward
    kernels dO and the two float32 rows a q head, log-sum-exp and
    delta, too) and writes its outputs (o and the log-sum-exp; dQ; dK
    and dV) once, bf16."""
    tokens = spec["batch_per_chip"] * spec["seq"]
    dh = config["head_dim"]
    q = tokens * config["num_attention_heads"] * dh * BYTES
    kv = tokens * config["num_key_value_heads"] * dh * BYTES
    row = tokens * config["num_attention_heads"] * 4
    forward = q + 2 * kv + q + row
    d_q = 2 * q + 2 * kv + 2 * row + q
    d_kv = 2 * q + 2 * kv + 2 * row + 2 * kv
    return config["num_hidden_layers"] * (2 * forward + d_q + d_kv)


def library_config(config: Dict[str, Any]):
    import jax.numpy as jnp
    from horovod_tpu.models.window_moe import FULL, WINDOW, WindowMoEConfig
    assert config["num_shared_experts"] == 1
    assert config["n_group"] == config["topk_group"] == 1
    assert config["score_func"] == "sigmoid" and config["route_norm"]
    assert config["rope_scaling"] is None
    assert not config["tie_word_embeddings"]
    return WindowMoEConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        layer_kinds=tuple(WINDOW if kind == SLIDING else FULL
                          for kind in layer_kinds(config)),
        n_dense_layers=config["num_dense_layers"],
        period=config["global_attn_every_n_layers"],
        window=config["sliding_window"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_ff_dense=config["dense_columns_held"],
        d_ff_expert=config["moe_intermediate_size"],
        d_ff_shared=config["shared_columns_held"],
        n_experts=config["published"]["num_experts"],
        experts_first=config["experts_first"],
        experts_held=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        routed_scale=float(config["route_scale"]),
        dispatch_tile=config.get("dispatch_tile_rows"),
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        embed_scale=math.sqrt(config["hidden_size"])
        if config["mup_enabled"] else 1.0,
        # the cells train in bfloat16 (the configuration's `training`);
        # a CPU rehearsal at toy widths names float32, where bfloat16's
        # noise is as large as a dropped term
        dtype=jnp.dtype(config.get("dtype", "bfloat16")), remat=True)


def build(config: Dict[str, Any], spec: Dict[str, Any], n_chips: int):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.models import window_moe as wm

    seq, vocab = spec["seq"], config["vocab_size"]
    cfg = library_config(config)

    def init(key):
        """The library's parameter tree, filled from `key` as the
        configuration's `assumed.initialiser` says, in the types the
        library serves."""
        shapes = jax.eval_shape(lambda k: wm.init_params(cfg, k), key)
        paths, tree = jax.tree.flatten_with_path(shapes)
        keys = jax.random.split(key, len(paths))

        def made(k, path, s):
            name = jax.tree_util.keystr(path[-1:])
            if "norm" in name:
                return jnp.ones(s.shape, s.dtype)
            std = ROUTER_BIAS_STD if "router_bias" in name \
                else config["initializer_range"]
            return (jax.random.normal(k, s.shape, jnp.float32) * std
                    ).astype(s.dtype)
        return jax.tree.unflatten(
            tree, [made(k, path, s) for k, (path, s) in zip(keys, paths)]
        ), None

    def loss_fn(params, batch):
        return wm.loss_fn(cfg, params, batch)

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
