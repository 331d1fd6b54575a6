"""Dense decoder configurations through `horovod_tpu.models.transformer`.

An adapter gives a driver what it needs to train one model family
through the library: seeded weights and batches made on the device,
the loss function, the optimizer, how the batch is sharded, and the
operations one unit of work (here a token) requires. The configuration
file carries the published `config.json` keys.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Dict

# config.json `initializer_range` of the Mistral family. The library's
# own init gives the tied embedding unit variance, which starts the
# loss at a few hundred (PERF.md, PR 24); the benchmark makes its own
# weights so that its losses do not move when that init is repaired.
INIT_STD = 0.02


def matmul_params(config: Dict[str, Any]) -> int:
    """Weights that sit in a matrix multiplication, the tied head
    counted once (the embedding lookup is a gather, not a matmul)."""
    d, h, kv = (config["hidden_size"], config["num_attention_heads"],
                config["num_key_value_heads"])
    dh, f = config["head_dim"], config["intermediate_size"]
    per_layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f
    return config["num_hidden_layers"] * per_layer + \
        config["vocab_size"] * d


def flops_per_unit(config: Dict[str, Any], spec: Dict[str, Any]) -> float:
    """Operations the forward and backward passes require for one
    token: 2 per weight forward, plus QK^T and PV over the whole
    sequence (4 * seq * heads * head_dim a layer, not halved for the
    causal mask), times 3 for forward + backward. Recompute under
    remat is not required work and is not counted."""
    attn = 4 * config["num_hidden_layers"] * spec["seq"] * \
        config["num_attention_heads"] * config["head_dim"]
    return 3.0 * (2 * matmul_params(config) + attn)


def build(config: Dict[str, Any], spec: Dict[str, Any], n_chips: int):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel.ring_attention import flash_possible_cfg

    seq, vocab = spec["seq"], config["vocab_size"]
    cfg = tfm.TransformerConfig(
        vocab=vocab, d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        max_seq=seq, moe=False, rope_theta=float(config["rope_theta"]),
        dtype=jnp.bfloat16, remat=True, remat_mode="full",
        tp_axis=None, sp_axis=None, ep_axis=None)

    def init(key):
        """The library's parameter tree, filled from `key`: norm gains
        one, every matrix normal with INIT_STD, in the served type."""
        shapes = jax.eval_shape(lambda k: tfm.init_params(cfg, k), key)
        paths, tree = jax.tree.flatten_with_path(shapes)
        keys = jax.random.split(key, len(paths))
        made = [jnp.ones(s.shape, s.dtype)
                if "norm" in jax.tree_util.keystr(path) else
                (jax.random.normal(k, s.shape, jnp.float32) *
                 INIT_STD).astype(s.dtype)
                for k, (path, s) in zip(keys, paths)]
        return jax.tree.unflatten(tree, made), None

    def tokens_batch(key, n, length):
        tokens = jax.random.randint(key, (n, length), 0, vocab,
                                    jnp.int32)
        return {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}

    return SimpleNamespace(
        init=init,
        loss_fn=lambda p, b: tfm.loss_fn(cfg, p, b),
        has_aux=False, carry_key=None,
        optimizer=optax.adamw(1e-4),
        batch_spec={"tokens": P("data"), "targets": P("data")},
        make_batch=lambda key, n: tokens_batch(key, n, seq),
        sample_batch=lambda key, n: tokens_batch(
            key, n * spec["sample"]["per_chip"], spec["sample"]["seq"]),
        units_per_sample=seq,
        flops_per_unit=flops_per_unit(config, spec),
        step_kwargs=dict(
            check_vma=not flash_possible_cfg(cfg.head_dim, seq)))
