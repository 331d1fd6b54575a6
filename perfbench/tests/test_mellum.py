"""The `mellum` family (sliding-window and full attention mixed, q / k
normed grouped-query heads, plain rope on the windowed layers and YaRN
on the full ones, a softmax top-k router, no shared expert) at a tiny
size on the CPU: the system agrees with its plain reference through
the driver's own sample check, whole and in blocks; the tolerances
catch a lower precision and each of five misplaced terms; the FLOP
functions equal a hand count at the published widths; the
configuration keeps every published key."""

import os
from unittest import mock

import pytest

from perfbench import run
from perfbench.tests.test_harness import ROOT
from perfbench.tests.test_xing4 import check, rounded_weights

KINDS = ["sliding_attention"] * 3 + ["full_attention"]
TINY = {
    "config": {
        "attention_bias": False, "head_dim": 8, "hidden_act": "silu",
        "hidden_size": 32, "intermediate_size": 64, "layer_types": KINDS * 3,
        "max_position_embeddings": 256, "mlp_layer_types": ["sparse"] * 12,
        "moe_intermediate_size": 16, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_experts": 16,
        "num_experts_per_tok": 8, "num_hidden_layers": 8,
        "num_key_value_heads": 1, "rms_norm_eps": 1e-6,
        # a small original length and theta, so that YaRN's ramp turns
        # most of the 4 frequencies within the sample's 64 positions
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 1000, "factor": 16,
                "original_max_position_embeddings": 16, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 1000}},
        "sliding_window": 16, "tie_word_embeddings": False,
        "vocab_size": 128, "experts_first": 16, "initializer_range": 0.15,
        "embedding_initializer_range": 1.0,
        "published": {"num_experts": 64},
        # at these widths bfloat16's own noise is as large as a
        # misplaced term's; the equations are what this rehearsal
        # holds, the chip holds the precision (reference/mellum.py
        # TOLERANCE)
        "dtype": "float32"},
    "cell": {"batch_per_chip": 2, "seq": 32,
             "rate_metric": "tokens_per_s_chip",
             "sample": {"per_chip": 2, "seq": 64}}}


def full_rope(**values):
    """A change of the full layers' `rope_parameters`: `values` over
    the published ones, or only `values` with `rope_type` given."""
    def change(config):
        ropes = config["rope_parameters"]
        full = values if "rope_type" in values else {
            **ropes["full_attention"], **values}
        if "rope_theta" not in full:
            full = {**full,
                    "rope_theta": ropes["full_attention"]["rope_theta"]}
        return {"rope_parameters": {**ropes, "full_attention": full}}
    return change


def router(replacement):
    """A fault that traces the system with the softmax router replaced
    by `replacement(logits, k)`."""
    def fault(loss_fn):
        def faulty(params, batch):
            from horovod_tpu.models import latent_moe
            with mock.patch.object(latent_moe, "topk_softmax_route",
                                   replacement):
                return loss_fn(params, batch)
        return faulty
    fault.__name__ = replacement.__name__
    return fault


def unnormalised(logits, k):
    """The softmax router with the chosen probabilities as they are,
    not renormalised."""
    import jax
    import jax.numpy as jnp
    gates, experts = jax.lax.top_k(
        jax.nn.softmax(logits.astype(jnp.float32), axis=-1), k)
    return experts.astype(jnp.int32), gates


def sigmoid(logits, k):
    """Sigmoid scores, top-k, normalised: the other family's router."""
    import jax.numpy as jnp
    from horovod_tpu.parallel import moe
    return moe.topk_sigmoid_route(logits, jnp.zeros(logits.shape[-1]), k,
                                  1.0)


# (name, what is built differently, what the loss is wrapped in): the
# six faults of the calibration. The system without its window is the
# system built with a window no sequence reaches.
PROBES = (("as it is", {}, None),
          ("no_window", {"sliding_window": 1 << 30}, None),
          ("no_attention_factor", full_rope(attention_factor=1.0), None),
          ("plain_rope_on_full_layers", full_rope(rope_type="default"),
           None),
          ("unnormalised_gates", {}, router(unnormalised)),
          ("sigmoid_router", {}, router(sigmoid)),
          ("rounded_weights", {}, rounded_weights))


def check_probe(driver, model, reference, config, spec, mesh, seed, change,
                fault):
    """`test_xing4.check` with a change that may be a function of the
    configuration."""
    if callable(change):
        change = change(config)
    return check(driver, model, reference, config, spec, mesh, seed, change,
                 fault)


@pytest.mark.parametrize("blocks", [None, (16, 32)],
                         ids=["whole", "in-blocks"])
@pytest.mark.parametrize("name,change,fault", PROBES,
                         ids=[p[0].replace(" ", "_") for p in PROBES])
def test_system_against_reference(monkeypatch, name, change, fault, blocks):
    import jax
    from horovod_tpu.parallel.mesh import data_parallel_mesh
    driver = run.load_module(ROOT, "drivers", "jit_train")
    model = run.load_module(ROOT, "models", "mellum")
    reference = run.load_module(ROOT, "reference", "mellum")
    if blocks:
        monkeypatch.setattr(reference, "QUERY_BLOCK", blocks[0])
        monkeypatch.setattr(reference, "TOKEN_BLOCK", blocks[1])
    mesh = data_parallel_mesh(jax.devices()[:2])
    assert check_probe(driver, model, reference, TINY["config"],
                       TINY["cell"], mesh, 7, change, fault) is (
        name == "as it is")


def _published():
    config = run.read_json(
        os.path.join(ROOT, "configs", "mellum2-12b-ep4.json"))
    spec = run.read_json(os.path.join(
        ROOT, "workloads", "mellum2-12b-ep4.jit-dp1.json"))
    return run.load_module(ROOT, "models", "mellum"), config, spec


def test_flops_equal_the_hand_count():
    model, config, spec = _published()
    # By hand, this chip's share at the published widths. Attention:
    #   W_q 2304 * 4096, W_k, W_v 2304 * 512 each, W_o 4096 * 2304
    #   = 2 * 9,437,184 + 2 * 1,179,648                  = 21,233,664
    # router 2304 * 64 = 147,456; the held experts at 8 * 16 / 64 = 2
    # experts a token of 3 * 2304 * 896 = 6,193,152     = 12,386,304
    # head 2304 * 24576 = 56,623,104
    attention = 2 * 9_437_184 + 2 * 1_179_648
    layer = attention + 147_456 + 2 * 6_193_152
    assert (attention, layer) == (21_233_664, 33_767_424)
    weights = 4 * layer + 56_623_104
    assert weights == 191_692_800
    assert model.matmul_weights_a_token(config) == weights
    # the core, pairs a head of one sequence: causal 8192 * 8193 / 2 =
    # 33,558,528; windowed 1024 * 1025 / 2 + 7168 * 1024 = 7,864,832
    # (23.4 %); 3 windowed layers and a full one, 2 sequences
    assert model.visible_pairs(8192) == 33_558_528
    assert model.visible_pairs(8192, 1024) == 7_864_832
    pairs = 2 * (3 * 7_864_832 + 33_558_528)
    assert model.pairs_a_step(config, spec) == pairs == 114_306_048
    core = 4 * 128 * 32 * pairs / 16384
    by_hand = 3 * (2 * weights + core)
    assert model.flops_per_unit(config, spec) == by_hand
    assert round(by_hand / 1e9, 3) == 1.493            # GFLOP a token
    # forward by layer kind, a token: projections 170 M, cores 114 M,
    # held experts 99 M, head 113 M
    assert round(2 * 4 * attention / 1e6) == 170
    assert round(core / 1e6) == 114
    assert round(2 * 4 * 2 * 6_193_152 / 1e6) == 99
    # the grouped matmuls: 3 matrices x 4 passes x 4 layers, 32,768
    # rows a call (16,384 tokens x 8 x 16 / 64), MXU-bound by 2 to 1
    calls, rows = model.grouped_matmul_calls(config, spec)
    assert (calls, rows) == (48, 32_768)
    flops = 48 * 2 * 32_768 * 2304 * 896
    assert model.grouped_matmul_flops(config, spec) == flops
    nbytes = 48 * 2 * (32_768 * (2304 + 896) + 16 * 2304 * 896)
    assert model.grouped_matmul_bytes(config, spec) == nbytes
    assert round(flops / 197e12 * 1e3, 1) == 33.0       # ms at the peak
    assert round(nbytes / 819e9 * 1e3, 1) == 16.2


def test_parameter_count_of_the_share():
    """595.15 M parameters, 4.76 GB at 8 bytes (bf16 weights,
    gradients, both AdamW moments): the arithmetic of the cut."""
    import jax
    from horovod_tpu.models import window_moe as wm
    model, config, _ = _published()
    cfg = model.library_config(config)
    shapes = jax.eval_shape(lambda k: wm.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    count = sum(s.size for s in jax.tree.leaves(shapes))
    assert round(count / 1e6, 2) == 595.15
    assert round(8 * count / 1e9, 2) == 4.76
    assert cfg.period_kinds == ("window",) * 3 + ("full",)
    assert cfg.n_dense_layers == 0 and len(cfg.layer_kinds) == 4
    assert (cfg.window, cfg.router, cfg.d_ff_shared) == (1024, "softmax", 0)
    assert not cfg.output_gate and not cfg.post_norms
    assert cfg.rope_theta == 5e5 and cfg.full_rope.factor == 16.0
    assert "dense" not in shapes and "wg" not in shapes["layers"]


def test_the_seeded_embedding_has_unit_deviation():
    """`assumed.initialiser`: matrices normal(initializer_range) but
    the embedding normal(embedding_initializer_range = 1), both keys of
    the configuration file, every norm gain 1, so that the tokens' own
    rows, not what attention averages over a window, decide the
    routing (PERF.md section 6)."""
    import jax
    import numpy as np
    model, config, _ = _published()
    assert config["initializer_range"] == 0.02
    assert config["embedding_initializer_range"] == 1.0
    m = model.build(TINY["config"], TINY["cell"], 1)
    params, _ = m.init(jax.random.PRNGKey(0))
    assert 0.9 < float(np.std(np.asarray(params["embed"]))) < 1.1
    assert 0.1 < float(np.std(np.asarray(params["head"]))) < 0.2  # 0.15
    for name in ("q_norm", "k_norm", "attn_norm", "mlp_norm"):
        np.testing.assert_array_equal(np.asarray(params["layers"][name]),
                                      1.0)


def test_configuration_keeps_every_published_key():
    """Every key of the catalog's `config` is in the file at its
    published value, except the reduced ones, whose originals are
    under `published`; `layer_types` is whole."""
    kinds = (["sliding_attention"] * 3 + ["full_attention"]) * 7
    catalog = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "layer_types": kinds, "max_position_embeddings": 131072,
        "max_window_layers": 0, "mlp_layer_types": ["sparse"] * 28,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        "sliding_window": 1024, "tie_word_embeddings": False,
        "vocab_size": 98304, "use_sliding_window": True}
    _, config, _ = _published()
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in catalog.items():
        if key in reduced:
            assert config["published"][key] == value
            assert config[key] < value
        else:
            assert config[key] == value, key
    assert config["num_experts"] * 4 == 64
    assert config["vocab_size"] * 4 == 98304
