"""`Xing4.0-29B-A4B`-shaped configurations (latent attention, sigmoid
top-k experts with a shared expert, `hc_mult` residual streams, one
multi-token module) through `horovod_tpu.models.latent_moe`, as one
chip's share of an expert-parallel job: the configuration file says
how many experts and vocabulary rows are held here and carries the
published `config.json` keys.

No (token, expert) pair is dropped: the library's dispatch buffer
takes tokens x min(k, experts held) pairs, more than a batch can send.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Dict

# `assumed.initialiser` of the configuration: its `initializer_range`
# for the matrices, and
MIXER_SCALE = 0.01       # a_pre, a_post, a_res
MIXER_BIAS_STD = 0.5     # b_pre, b_post
MIXER_MIX_STD = 1.0      # b_res: Sinkhorn needs its 20 iterations
ROUTER_BIAS_STD = 0.01


def layer_counts(config: Dict[str, Any]):
    """(leading dense layers, expert layers of the stack, expert
    layers of the multi-token module)."""
    dense = config["first_k_dense_replace"]
    return (dense, config["num_hidden_layers"] - dense,
            config["num_nextn_predict_layers"])


def matmul_weights_a_token(config: Dict[str, Any]) -> float:
    """Weights one token meets in matrix multiplications: the routed
    experts at their expectation here (`num_experts_per_tok` x held /
    router width of an expert a token), the head once for the main
    loss and once for the multi-token module; embedding lookups are
    gathers."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, rq, rkv = (config["v_head_dim"], config["q_lora_rank"],
                   config["kv_lora_rank"])
    n = config["hc_mult"]
    attention = d * rq + rq * heads * (nope + rope) + d * (rkv + rope) \
        + rkv * heads * (nope + dv) + heads * dv * d
    mixers = 2 * n * d * (2 * n + n * n)
    expert = 3 * d * config["moe_intermediate_size"]
    routed = config["num_experts_per_tok"] * config["n_routed_experts"] \
        / config["published"]["n_routed_experts"]
    router = d * config["published"]["n_routed_experts"]
    expert_layer = attention + mixers + router \
        + (config["n_shared_experts"] + routed) * expert
    dense_layer = attention + mixers + 3 * d * config["intermediate_size"]
    n_dense, n_expert, n_mtp = layer_counts(config)
    head = d * config["vocab_size"]
    return n_dense * dense_layer + (n_expert + n_mtp) * expert_layer \
        + n_mtp * 2 * d * d + (1 + n_mtp) * head


def flops_per_unit(config: Dict[str, Any], spec: Dict[str, Any]) -> float:
    """Operations the forward and backward passes require for one
    token: 2 a weight met forward, plus QK^T over the published 192
    and PV over the published 128 columns of every head over the whole
    sequence (not halved for the causal mask, as the transformer
    adapter counts it; the zero padding of the fused core is not
    required work), times 3 for forward + backward. Recompute under
    remat is not counted."""
    layers = sum(layer_counts(config))
    core = 2 * layers * spec["seq"] * config["num_attention_heads"] * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        + config["v_head_dim"])
    return 3.0 * (2 * matmul_weights_a_token(config) + core)


def grouped_matmul_calls(config: Dict[str, Any], spec: Dict[str, Any]):
    """(calls a step, rows a call) of the routed experts' grouped
    matmuls as a training step with every layer checkpointed runs
    them: three matrices an expert layer, each once
    forward, once again in the backward pass's recompute, once for the
    rows' gradient and once for the weights'. Rows are the published
    routing's expectation on this chip: tokens x experts a token x
    held / router width; padding to tiles is not required work."""
    _, n_expert, n_mtp = layer_counts(config)
    rows = spec["batch_per_chip"] * spec["seq"] \
        * config["num_experts_per_tok"] * config["n_routed_experts"] \
        / config["published"]["n_routed_experts"]
    return 3 * 4 * (n_expert + n_mtp), rows


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
    return calls * 2.0 * (rows * (d + f)
                          + config["n_routed_experts"] * d * f)


def library_config(config: Dict[str, Any]):
    import jax.numpy as jnp
    from horovod_tpu.models.latent_moe import LatentMoEConfig
    scaling = config["rope_scaling"]
    n_dense, n_expert, n_mtp = layer_counts(config)
    assert config["n_shared_experts"] == 1 and n_mtp in (0, 1)
    assert config["n_group"] == config["topk_group"] == 1
    return LatentMoEConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_dense_layers=n_dense, n_expert_layers=n_expert,
        n_heads=config["num_attention_heads"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], q_rank=config["q_lora_rank"],
        kv_rank=config["kv_lora_rank"],
        d_ff_dense=config["intermediate_size"],
        d_ff_expert=config["moe_intermediate_size"],
        n_experts=config["published"]["n_routed_experts"],
        experts_first=config["experts_first"],
        experts_held=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        routed_scale=float(config["routed_scaling_factor"]),
        hc_mult=config["hc_mult"], hc_iters=config["hc_sinkhorn_iters"],
        hc_eps=config["hc_eps"],
        hc_clamp=(float(config["mhc_h_res_clamp_min"]),
                  float(config["mhc_h_res_clamp_max"])),
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        rope_factor=float(scaling["factor"]),
        rope_beta_fast=float(scaling["beta_fast"]),
        rope_beta_slow=float(scaling["beta_slow"]),
        rope_original_len=scaling["original_max_position_embeddings"],
        rope_mscale_all_dim=float(scaling["mscale_all_dim"]),
        mtp=bool(n_mtp), mtp_lambda=config["mtp_lambda"],
        dtype=jnp.bfloat16, remat=True)


def build(config: Dict[str, Any], spec: Dict[str, Any], n_chips: int):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.models import latent_moe as lm

    seq, vocab = spec["seq"], config["vocab_size"]
    cfg = library_config(config)

    def init(key):
        """The library's parameter tree, filled from `key` as the
        configuration's `assumed.initialiser` says, in the types the
        library serves."""
        shapes = jax.eval_shape(lambda k: lm.init_params(cfg, k), key)
        paths, tree = jax.tree.flatten_with_path(shapes)
        keys = jax.random.split(key, len(paths))

        def made(k, path, s):
            name = jax.tree_util.keystr(path[-1:])
            if "norm" in name:
                return jnp.ones(s.shape, s.dtype)
            if "'a_" in name:
                return jnp.full(s.shape, MIXER_SCALE, s.dtype)
            std = MIXER_MIX_STD if "b_res" in name else \
                MIXER_BIAS_STD if "'b_" in name else \
                ROUTER_BIAS_STD if "router_bias" in name else \
                config["initializer_range"]
            return (jax.random.normal(k, s.shape, jnp.float32) * std
                    ).astype(s.dtype)
        return jax.tree.unflatten(
            tree, [made(k, path, s) for k, (path, s) in zip(keys, paths)]
        ), None

    def loss_fn(params, batch):
        return lm.loss_fn(cfg, params, batch)

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
