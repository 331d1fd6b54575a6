"""The `afmoe` family (sliding-window and full attention mixed, gated
grouped-query heads with q / k norms, sandwich norms, sparse experts
with a shared one) at a tiny size on the CPU: the system agrees with
its plain reference through the driver's own sample check, whole and
in blocks; the tolerances catch a lower precision and each of four
dropped or misplaced terms; the FLOP functions equal a hand count at
the published widths; the configuration keeps every published key."""

import os
from unittest import mock

import pytest

from perfbench import run
from perfbench.tests.test_harness import ROOT
from perfbench.tests.test_xing4 import check, rounded_weights

KINDS = ["sliding_attention"] * 3 + ["full_attention"]
TINY = {
    "config": {
        "global_attn_every_n_layers": 4, "head_dim": 8, "hidden_size": 32,
        "intermediate_size": 192, "layer_types": KINDS * 2,
        "moe_intermediate_size": 16, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 4, "num_dense_layers": 1, "num_experts": 8,
        "num_experts_per_tok": 4, "num_hidden_layers": 5,
        "num_key_value_heads": 2, "num_shared_experts": 1,
        "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2.448, "score_func": "sigmoid",
        "sliding_window": 16, "tie_word_embeddings": False,
        "topk_group": 1, "vocab_size": 128, "dense_columns_held": 48,
        "shared_columns_held": 8, "experts_first": 0,
        "initializer_range": 0.15, "published": {"num_experts": 32},
        # at these widths bfloat16's own noise in the gradient norm
        # (1e-2, top-k choices flipped) is as large as a dropped
        # term's; the equations are what this rehearsal holds, the
        # chip holds the precision (reference/afmoe.py TOLERANCE)
        "dtype": "float32"},
    "cell": {"batch_per_chip": 2, "seq": 32,
             "rate_metric": "tokens_per_s_chip",
             "sample": {"per_chip": 2, "seq": 64}}}


def _patched(name, replacement):
    """A fault that traces the system with `window_moe.<name>`
    replaced by `replacement(original)`."""
    def fault(loss_fn):
        def faulty(params, batch):
            from horovod_tpu.models import window_moe as wm
            with mock.patch.object(
                    wm, name, replacement(getattr(wm, name))):
                return loss_fn(params, batch)
        return faulty
    fault.__name__ = name
    return fault


def _rope_everywhere(attention):
    """The full layers roped like the windowed ones."""
    def roped(q, k, v, causal=True, window=None):
        import jax.numpy as jnp
        from horovod_tpu.models.transformer import _rope
        if window is None:
            positions = jnp.arange(q.shape[1])
            q, k = _rope(q, positions, 1e4), _rope(k, positions, 1e4)
        return attention(q, k, v, causal=causal, window=window)
    return roped


def _no_ffn_post_norm(expert_block):
    """The expert layers' FFN added to the residual as it is."""
    def block(cfg, p, x, kind):
        from horovod_tpu.models import latent_moe, window_moe as wm
        x = x + wm.gated_attention(cfg, p, x, kind)
        return x + latent_moe.expert_ffn(cfg, p, x)
    return block


def no_gate(loss_fn):
    """The output gate dropped: W_g = 0 makes it the constant 1 / 2,
    which the post-norm after W_o takes out."""
    import jax
    return lambda p, b: loss_fn(jax.tree_util.tree_map_with_path(
        lambda path, a: a * 0 if "wg" in jax.tree_util.keystr(path) else a,
        p), b)


# (name, what is built differently, what the loss is wrapped in): the
# five faults ISSUE 35 names. The system without its window is the
# system built with a window no sequence reaches.
PROBES = (("as it is", {}, None),
          ("no_window", {"sliding_window": 1 << 30}, None),
          ("rope_on_full_layers", {}, _patched("attention",
                                               _rope_everywhere)),
          ("no_gate", {}, no_gate),
          ("no_ffn_post_norm", {}, _patched("expert_block",
                                            _no_ffn_post_norm)),
          ("rounded_weights", {}, rounded_weights))


@pytest.mark.parametrize("blocks", [None, (16, 32)],
                         ids=["whole", "in-blocks"])
@pytest.mark.parametrize("name,change,fault", PROBES,
                         ids=[p[0].replace(" ", "_") for p in PROBES])
def test_system_against_reference(monkeypatch, name, change, fault, blocks):
    import jax
    from horovod_tpu.parallel.mesh import data_parallel_mesh
    driver = run.load_module(ROOT, "drivers", "jit_train")
    model = run.load_module(ROOT, "models", "afmoe")
    reference = run.load_module(ROOT, "reference", "afmoe")
    if blocks:
        monkeypatch.setattr(reference, "QUERY_BLOCK", blocks[0])
        monkeypatch.setattr(reference, "TOKEN_BLOCK", blocks[1])
    mesh = data_parallel_mesh(jax.devices()[:2])
    assert check(driver, model, reference, TINY["config"], TINY["cell"],
                 mesh, 7, change, fault) is (name == "as it is")


def _published():
    config = run.read_json(
        os.path.join(ROOT, "configs", "trinity-large-ep32tp4.json"))
    spec = run.read_json(os.path.join(
        ROOT, "workloads", "trinity-large-ep32tp4.jit-dp1.json"))
    return run.load_module(ROOT, "models", "afmoe"), config, spec


def test_flops_equal_the_hand_count():
    model, config, spec = _published()
    # By hand, this chip's share at the published widths. Attention:
    #   W_q, W_g 3072 * 1536 each, W_k, W_v 3072 * 256 each, W_o
    #   1536 * 3072 = 3 * 4,718,592 + 2 * 786,432       = 15,728,640
    # dense layer: + 3 * 3072 * 3072 = 28,311,552        = 44,040,192
    # expert layer: router 3072 * 256 = 786,432, shared 3 * 3072 * 768
    #   = 7,077,888, routed 4 * 8 / 256 = 1 / 8 of an expert of
    #   3 * 3072 * 3072 = 28,311,552 a token             = 27,131,904
    # head 3072 * 25024 = 76,873,728
    attention = 3 * 4_718_592 + 2 * 786_432
    expert_layer = attention + 786_432 + 7_077_888 + 28_311_552 // 8
    assert (attention, expert_layer) == (15_728_640, 27_131_904)
    weights = 44_040_192 + 4 * expert_layer + 76_873_728
    assert weights == 229_441_536
    assert model.matmul_weights_a_token(config) == weights
    # the core, pairs a head: causal 16384 * 16385 / 2 = 134,225,920;
    # windowed 4096 * 4097 / 2 + 12288 * 4096 = 58,722,304 (43.7 %);
    # layers sliding, sliding, sliding, full, sliding
    assert model.visible_pairs(16384) == 134_225_920
    assert model.visible_pairs(16384, 4096) == 58_722_304
    pairs = 4 * 58_722_304 + 134_225_920
    assert model.pairs_a_step(config, spec) == pairs == 369_115_136
    core = 4 * 128 * 12 * pairs / 16384
    by_hand = 3 * (2 * weights + core)
    assert model.flops_per_unit(config, spec) == by_hand
    assert round(by_hand / 1e9, 3) == 1.792            # GFLOP a token
    # the kernels: 11 products of 2 * 128 a pair and head; 5.6 bytes
    # a FLOP-second short of mattering (63.3 ms against 3.3 ms)
    assert model.attention_flops(config, spec) == 22 * 128 * 12 * pairs
    assert round(model.attention_flops(config, spec) / 197e12 * 1e3, 1) \
        == 63.3
    q, kv, row = 16384 * 1536 * 2, 16384 * 256 * 2, 16384 * 12 * 4
    assert model.attention_bytes(config, spec) == 5 * (
        2 * (2 * q + 2 * kv + row) + (3 * q + 2 * kv + 2 * row)
        + (2 * q + 4 * kv + 2 * row))


def test_parameter_count_of_the_share():
    """1,198.2 M parameters, 9.59 GB at 8 bytes (bf16 weights,
    gradients, both AdamW moments)."""
    import jax
    from horovod_tpu.models import window_moe as wm
    model, config, _ = _published()
    cfg = model.library_config(config)
    shapes = jax.eval_shape(lambda k: wm.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    count = sum(s.size for s in jax.tree.leaves(shapes))
    assert round(count / 1e6, 1) == 1198.2
    assert cfg.period_kinds == ("window", "window", "full", "window")
    assert cfg.layer_kinds[0] == "window" and cfg.window == 4096


def test_configuration_keeps_every_published_key():
    """Every key of the catalog's `config` is in the file at its
    published value, except the reduced ones, whose originals are
    under `published`; `layer_types` is whole."""
    catalog = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 3072,
        "intermediate_size": 12288,
        "layer_types": (["sliding_attention"] * 3
                        + ["full_attention"]) * 15,
        "load_balance_coeff": 5e-05, "max_position_embeddings": 262144,
        "model_type": "afmoe", "moe_intermediate_size": 3072,
        "mup_enabled": True, "n_group": 1, "num_attention_heads": 48,
        "num_dense_layers": 6, "num_expert_groups": 1, "num_experts": 256,
        "num_experts_per_tok": 4, "num_hidden_layers": 60,
        "num_key_value_heads": 8, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.448, "score_func": "sigmoid",
        "sliding_window": 4096, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
    _, config, _ = _published()
    reduced = set(config["reduced"])
    own = {"dense_columns_held": "dense_columns",
           "shared_columns_held": "shared_columns"}
    assert reduced == set(own) | {
        "num_hidden_layers", "num_dense_layers", "num_attention_heads",
        "num_key_value_heads", "num_experts", "vocab_size"}
    for key, value in catalog.items():
        if key in reduced:
            assert config["published"][key] == value
            assert config[key] < value
        else:
            assert config[key] == value, key
    for key, published in own.items():
        assert config[key] * 4 == config["published"][published]
    assert config["published"]["dense_columns"] == 12288
    assert config["published"]["shared_columns"] == 3072
