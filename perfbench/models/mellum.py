"""`mellum`-shaped configurations (JetBrains Mellum 2: sliding-window and
full attention mixed, grouped-query heads with q / k norms, plain rope
on the windowed layers and YaRN on the full ones, a softmax top-k
router over fine-grained experts, no shared expert) through
`horovod_tpu.models.window_moe`, as one chip's share of an
expert-parallel group: the configuration file says how many experts
and vocabulary rows are held here and carries the published
`config.json` keys.

No (token, expert) pair is dropped: the library's dispatch buffer
takes tokens x min(k, experts held) pairs, more than a batch can send.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Dict

SLIDING = "sliding_attention"
BYTES = 2                # bf16


def layer_kinds(config: Dict[str, Any]):
    """`layer_types` of the layers that are run: the first
    `num_hidden_layers` of the published list."""
    return config["layer_types"][:config["num_hidden_layers"]]


def period(kinds) -> int:
    """The shortest run of layer kinds that the list repeats."""
    n = len(kinds)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and kinds == kinds[:p] * (n // p))


def routed_rows_a_token(config: Dict[str, Any]) -> float:
    """Experts a token meets on this chip at the router's expectation:
    `num_experts_per_tok` x held / router width."""
    return config["num_experts_per_tok"] * config["num_experts"] \
        / config["published"]["num_experts"]


def matmul_weights_a_token(config: Dict[str, Any]) -> float:
    """Weights one token meets in matrix multiplications on this chip:
    q / k / v / o, the router, the held experts at their expectation
    here, the head over the held vocabulary; the embedding lookup is a
    gather."""
    d, dh = config["hidden_size"], config["head_dim"]
    q_cols = config["num_attention_heads"] * dh
    kv_cols = config["num_key_value_heads"] * dh
    attention = d * (q_cols + 2 * kv_cols) + q_cols * d
    expert = 3 * d * config["moe_intermediate_size"]
    layer = attention + d * config["published"]["num_experts"] \
        + routed_rows_a_token(config) * expert
    return config["num_hidden_layers"] * layer + d * config["vocab_size"]


def visible_pairs(seq: int, window=None) -> int:
    """(query, key) pairs of one head that the mask leaves: key <=
    query, and with a window query - key < window."""
    reach = seq if window is None else min(window, seq)
    return reach * (reach + 1) // 2 + (seq - reach) * reach


def pairs_a_step(config: Dict[str, Any], spec: Dict[str, Any]) -> int:
    """Visible pairs of one q head, summed over the layers that are
    run: the sliding layers under their window, the full ones under
    the causal mask."""
    seq = spec["seq"]
    return spec["batch_per_chip"] * sum(
        visible_pairs(seq, config["sliding_window"]
                      if kind == SLIDING else None)
        for kind in layer_kinds(config))


def flops_per_unit(config: Dict[str, Any], spec: Dict[str, Any]) -> float:
    """Operations the forward and backward passes require for one
    token: 2 a weight met forward (the held experts at their expected
    rows), plus QK^T and PV (2 x 128 each) over exactly the (query,
    key) pairs each layer kind's mask leaves, times 3 for forward +
    backward. Recompute under remat is not counted."""
    tokens = spec["batch_per_chip"] * spec["seq"]
    core = 4 * config["head_dim"] * config["num_attention_heads"] \
        * pairs_a_step(config, spec) / tokens
    return 3.0 * (2 * matmul_weights_a_token(config) + core)


def grouped_matmul_calls(config: Dict[str, Any], spec: Dict[str, Any]):
    """(calls a step, rows a call) of the held experts' grouped
    matmuls as a training step with every layer checkpointed runs
    them: three matrices a layer, each once forward, once again in the
    backward pass's recompute, once for the rows' gradient and once
    for the weights'. Rows are the routing's expectation on this chip:
    tokens x experts a token x held / router width; padding to tiles
    is not required work."""
    rows = spec["batch_per_chip"] * spec["seq"] * routed_rows_a_token(config)
    return 3 * 4 * config["num_hidden_layers"], rows


def grouped_matmul_flops(config: Dict[str, Any],
                         spec: Dict[str, Any]) -> float:
    """Operations a step of all `hvd_grouped_matmul_*` calls: every
    call, of whichever kind, is 2 x rows x hidden x expert width."""
    calls, rows = grouped_matmul_calls(config, spec)
    return calls * 2.0 * rows * config["hidden_size"] \
        * config["moe_intermediate_size"]


def grouped_matmul_bytes(config: Dict[str, Any],
                         spec: Dict[str, Any]) -> float:
    """Bytes a step those calls must move, bf16: a call reads or
    writes its rows at both widths once and the held experts' matrix
    once."""
    calls, rows = grouped_matmul_calls(config, spec)
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    return calls * BYTES * (rows * (d + f) + config["num_experts"] * d * f)


def full_rope(params: Dict[str, Any]):
    """The full layers' `rope_parameters` as the library's `Rope`:
    "default" is a plain rope at its theta; "yarn" adds the ramp of
    frequencies and the attention factor."""
    from horovod_tpu.models.window_moe import Rope
    if params["rope_type"] == "default":
        return Rope(float(params["rope_theta"]))
    assert params["rope_type"] == "yarn", params
    return Rope(theta=float(params["rope_theta"]),
                factor=float(params["factor"]),
                original_len=params["original_max_position_embeddings"],
                beta_fast=float(params["beta_fast"]),
                beta_slow=float(params["beta_slow"]),
                attention_factor=float(params["attention_factor"]))


def library_config(config: Dict[str, Any]):
    import jax.numpy as jnp
    from horovod_tpu.models.window_moe import FULL, WINDOW, WindowMoEConfig
    ropes = config["rope_parameters"]
    full, sliding = ropes["full_attention"], ropes["sliding_attention"]
    assert sliding["rope_type"] == "default", sliding
    # transformers' YaRN takes its factor as max_position_embeddings
    # over the original length where both are given: they agree here
    assert full.get("rope_type") != "yarn" or full["factor"] == \
        config["max_position_embeddings"] \
        / full["original_max_position_embeddings"]
    assert config["norm_topk_prob"] and not config["attention_bias"]
    assert not config["tie_word_embeddings"]
    assert set(config["mlp_layer_types"]) == {"sparse"}
    kinds = tuple(WINDOW if kind == SLIDING else FULL
                  for kind in layer_kinds(config))
    return WindowMoEConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        layer_kinds=kinds, n_dense_layers=0, period=period(kinds),
        window=config["sliding_window"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff_dense=0,
        d_ff_expert=config["moe_intermediate_size"], d_ff_shared=0,
        n_experts=config["published"]["num_experts"],
        experts_first=config["experts_first"],
        experts_held=config["num_experts"],
        top_k=config["num_experts_per_tok"], router="softmax",
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(sliding["rope_theta"]), full_rope=full_rope(full),
        output_gate=False, post_norms=False, embed_scale=1.0,
        # the cell trains in bfloat16 (the configuration's `training`);
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
            name = path[-1].key
            if "norm" in name:
                return jnp.ones(s.shape, s.dtype)
            std = config["embedding_initializer_range"] \
                if name == "embed" else config["initializer_range"]
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
