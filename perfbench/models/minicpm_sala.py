"""`minicpm_sala`-shaped configurations (OpenBMB MiniCPM-SALA:
block-sparse attention that selects its keys and lightning linear
attention mixed in one stack, gated heads with q / k norms, MiniCPM's
residual and logit multipliers) through
`horovod_tpu.models.sparse_linear`, as one chip's share of a job that
divides every layer over chips: the configuration file says how many
heads, FFN columns and vocabulary rows are held here and carries the
published `config.json` keys.
"""

from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Any, Dict

SPARSE, LINEAR = "minicpm4", "lightning-attn"
BYTES = 2                # bf16


def layer_kinds(config: Dict[str, Any]):
    """`mixer_types` of the layers that are run: the first
    `num_hidden_layers` of the published list."""
    return config["mixer_types"][:config["num_hidden_layers"]]


def matmul_weights_a_token(config: Dict[str, Any]) -> float:
    """Weights one token meets in matrix multiplications on this chip:
    the held heads, columns and vocabulary rows; the embedding lookup
    is a gather."""
    d = config["hidden_size"]
    ffn = 3 * d * config["ffn_columns_held"]
    q_cols = config["num_attention_heads"] * config["head_dim"]
    kv_cols = config["num_key_value_heads"] * config["head_dim"]
    sparse = d * (2 * q_cols + 2 * kv_cols) + q_cols * d + ffn
    lin_cols = config["lightning_nh"] * config["lightning_head_dim"]
    linear = 5 * d * lin_cols + ffn
    kinds = layer_kinds(config)
    return kinds.count(SPARSE) * sparse + kinds.count(LINEAR) * linear \
        + d * config["vocab_size"]


def selected_pairs(config: Dict[str, Any], seq: int) -> int:
    """(query, key) pairs of one q head that a sparse layer's
    equations keep over a sequence: every key at or before the query
    up to `dense_len` positions; beyond, the keys at or before the
    query in its `topk` blocks, of which its own is one (all its
    blocks where it has no more than `topk`)."""
    sparse = config["sparse_config"]
    block, topk = sparse["block_size"], sparse["topk"]
    if seq <= sparse["dense_len"]:
        return seq * (seq + 1) // 2
    return sum(t + 1 if t // block < topk
               else (topk - 1) * block + t % block + 1
               for t in range(seq))


def core_flops_a_token(config: Dict[str, Any], seq: int) -> float:
    """Forward operations of the two cores a token, summed over the
    layers that are run. Sparse: QK^T and PV (2 x 128 each) over the
    pairs the selection keeps, a q head. Linear: the state's update
    k^T v and its reading q S (2 x 128 x 128 each) a head, the
    recurrence's own count, which no chunking goes under."""
    kinds = layer_kinds(config)
    dh = config["head_dim"]
    sparse = 4 * dh * config["num_attention_heads"] \
        * selected_pairs(config, seq) / seq
    ld = config["lightning_head_dim"]
    linear = 4 * ld * ld * config["lightning_nh"]
    return kinds.count(SPARSE) * sparse + kinds.count(LINEAR) * linear


def flops_per_unit(config: Dict[str, Any], spec: Dict[str, Any]) -> float:
    """Operations the forward and backward passes require for one
    token: 2 a weight met forward plus the two cores' required
    products over the pairs the equations keep, times 3 for forward +
    backward. The selection (pooled scores, top-k) is not counted: it
    is how the pairs are found, not what the model computes over
    them. Recompute under remat is not counted."""
    return 3.0 * (2 * matmul_weights_a_token(config)
                  + core_flops_a_token(config, spec["seq"]))


def sparse_attention_flops(config: Dict[str, Any],
                           spec: Dict[str, Any]) -> float:
    """Operations a training step's `hvd_sparse_attention_*` calls must
    do for the selected pairs, every layer checkpointed: the forward
    kernel twice (QK^T, PV), dQ once (QK^T, dO V^T, dS K) and dK/dV
    once (QK^T, P^T dO, V dO^T, dS^T Q): 11 products of 2 x 128
    operations a pair and q head. The kernels compute every kernel
    block that some query of a query block selected, whole; that is
    not required work."""
    return 11 * 2.0 * config["head_dim"] * config["num_attention_heads"] \
        * layer_kinds(config).count(SPARSE) * spec["batch_per_chip"] \
        * selected_pairs(config, spec["seq"])


def sparse_attention_bytes(config: Dict[str, Any],
                           spec: Dict[str, Any]) -> float:
    """Bytes those calls must move: each reads q, k, v (the backward
    kernels dO and the two float32 rows a q head, log-sum-exp and
    delta, too) and writes its outputs (o and the log-sum-exp; dQ; dK
    and dV) once, bf16; the selection's words are not counted."""
    tokens = spec["batch_per_chip"] * spec["seq"]
    dh = config["head_dim"]
    q = tokens * config["num_attention_heads"] * dh * BYTES
    kv = tokens * config["num_key_value_heads"] * dh * BYTES
    row = tokens * config["num_attention_heads"] * 4
    forward = q + 2 * kv + q + row
    d_q = 2 * q + 2 * kv + 2 * row + q
    d_kv = 2 * q + 2 * kv + 2 * row + 2 * kv
    return layer_kinds(config).count(SPARSE) * (2 * forward + d_q + d_kv)


def linear_attention_flops(config: Dict[str, Any],
                           spec: Dict[str, Any]) -> float:
    """Operations a training step's `hvd_linear_attention_*` calls must
    do, every layer checkpointed, by the recurrence's own count
    (2 x 128 x 128 a product, token and head, which no chunking goes
    under): the forward kernel twice (k^T v, q S), dQ once (k^T v
    again for the state it rebuilds, dO S^T) and dK/dV once (q^T dO,
    v dS^T, k dS): 9 products. The masked products inside a chunk are
    how the kernels compute them, not required work."""
    ld = config["lightning_head_dim"]
    return 9 * 2.0 * ld * ld * config["lightning_nh"] \
        * layer_kinds(config).count(LINEAR) \
        * spec["batch_per_chip"] * spec["seq"]


def linear_attention_bytes(config: Dict[str, Any],
                           spec: Dict[str, Any]) -> float:
    """Bytes those calls must move, bf16: the forward kernel reads q,
    k, v and writes o (twice); dQ reads k, v, dO and writes dQ; dK/dV
    reads q, k, v, dO and writes dK, dV: 18 arrays of tokens x heads x
    128 a layer. The decay tables (a few MB a call) are not counted."""
    one = spec["batch_per_chip"] * spec["seq"] * config["lightning_nh"] \
        * config["lightning_head_dim"] * BYTES
    return layer_kinds(config).count(LINEAR) * (2 * 4 + 4 + 6) * one


def library_config(config: Dict[str, Any]):
    import jax.numpy as jnp
    from horovod_tpu.models.sparse_linear import (
        LINEAR as LIN, SPARSE as SP, SparseLinearConfig)
    from horovod_tpu.parallel.sparse_attention import SparseSpec
    assert config["qk_norm"] and not config["attention_bias"]
    assert not config["attn_use_rope"] and config["lightning_use_rope"]
    assert config["use_output_norm"] and config["use_output_gate"] \
        and config["attn_use_output_gate"]
    assert config["lightning_nkv"] == config["lightning_nh"]
    assert config["lightning_head_dim"] == config["head_dim"]
    assert config["lightning_scale"] == "1/sqrt(d)"
    assert not config["tie_word_embeddings"]
    kinds = tuple(SP if kind == SPARSE else LIN
                  for kind in layer_kinds(config))
    sparse = config["sparse_config"]
    return SparseLinearConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        layer_kinds=kinds,
        # the shortest stretch of the kinds that repeats
        period=next(p for p in range(1, len(kinds) + 1)
                    if kinds == kinds[:p] * (len(kinds) // p)),
        head_dim=config["head_dim"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        linear_heads=config["lightning_nh"],
        linear_first=config["lightning_heads_first"],
        linear_heads_total=config["published"]["lightning_nh"],
        d_ff=config["ffn_columns_held"],
        sparse=SparseSpec(
            kernel_size=sparse["kernel_size"],
            kernel_stride=sparse["kernel_stride"],
            block=sparse["block_size"], topk=sparse["topk"],
            init_blocks=sparse["init_blocks"],
            window=sparse["window_size"], dense_len=sparse["dense_len"]),
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        embed_scale=float(config["scale_emb"]),
        residual_scale=config["scale_depth"] / math.sqrt(
            config["published"]["num_hidden_layers"]),
        logit_scale=config["dim_model_base"] / config["hidden_size"],
        # the cells train in bfloat16 (the configuration's `training`);
        # a CPU rehearsal at toy widths names float32
        dtype=jnp.dtype(config.get("dtype", "bfloat16")), remat=True)


def build(config: Dict[str, Any], spec: Dict[str, Any], n_chips: int):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.models import sparse_linear as sl

    seq, vocab = spec["seq"], config["vocab_size"]
    cfg = library_config(config)

    def init(key):
        """The library's parameter tree, filled from `key` as the
        configuration's `assumed.g_initialiser` says, in the types the
        library serves."""
        shapes = jax.eval_shape(lambda k: sl.init_params(cfg, k), key)
        paths, tree = jax.tree.flatten_with_path(shapes)
        keys = jax.random.split(key, len(paths))

        def made(k, path, s):
            if "norm" in jax.tree_util.keystr(path[-1:]):
                return jnp.ones(s.shape, s.dtype)
            return (jax.random.normal(k, s.shape, jnp.float32)
                    * config["initializer_range"]).astype(s.dtype)
        return jax.tree.unflatten(
            tree, [made(k, path, s) for k, (path, s) in zip(keys, paths)]
        ), None

    def loss_fn(params, batch):
        return sl.loss_fn(cfg, params, batch)

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
