"""The `minicpm_sala` family (block-sparse attention that selects its
keys and lightning linear attention 1 : 3, gated heads with q / k
norms, MiniCPM's multipliers) at a tiny size on the CPU: the system
agrees with its plain reference through the driver's own sample check,
whole and in blocks; the tolerances catch a lower precision and each
of the faults ISSUE 37 names; the FLOP functions equal a hand count at
the published widths; the configuration keeps every published key."""

import os
from unittest import mock

import pytest

from perfbench import run
from perfbench.tests.test_harness import ROOT
from perfbench.tests.test_xing4 import check, rounded_weights

M4, LA = "minicpm4", "lightning-attn"
SPARSE = {"kernel_size": 4, "kernel_stride": 2, "block_size": 8, "topk": 4,
          "init_blocks": 1, "window_size": 16, "dense_len": 32}
TINY = {
    "config": {
        "attention_bias": False, "attn_use_rope": False, "head_dim": 16,
        "hidden_size": 64, "intermediate_size": 256,
        "lightning_head_dim": 16, "lightning_nh": 4, "lightning_nkv": 4,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "mixer_types": [M4, LA, LA, LA] * 2, "num_attention_heads": 4,
        "num_hidden_layers": 4, "num_key_value_heads": 1, "qk_norm": True,
        "rms_norm_eps": 1e-6, "vocab_size": 128, "rope_theta": 10000,
        "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 16,
        "tie_word_embeddings": False, "use_output_gate": True,
        "use_output_norm": True, "attn_use_output_gate": True,
        "ffn_columns_held": 128, "lightning_heads_first": 0,
        "initializer_range": 0.15, "sparse_config": SPARSE,
        "published": {"num_hidden_layers": 32, "lightning_nh": 8},
        # the equations are what this rehearsal holds; the chip holds
        # the precision (reference/minicpm_sala.py TOLERANCE)
        "dtype": "float32"},
    "cell": {"batch_per_chip": 2, "seq": 64,
             "rate_metric": "tokens_per_s_chip",
             "sample": {"per_chip": 2, "seq": 128}}}


def _traced_with(patches):
    """A fault that traces the system with names of
    `models/sparse_linear.py` replaced: `patches(sl)` gives
    {name: replacement}."""
    def fault(loss_fn):
        def faulty(params, batch):
            from horovod_tpu.models import sparse_linear as sl
            with mock.patch.multiple(sl, **patches(sl)):
                return loss_fn(params, batch)
        return faulty
    return fault


def _no_decay(sl):
    import jax.numpy as jnp
    real = sl.decay_slopes
    return {"decay_slopes": lambda *a: jnp.zeros_like(real(*a))}


def _no_rope(sl):
    return {"_rope": lambda x, positions, theta: x}


def _rope_on_sparse(sl):
    real = sl.sparse_attention

    def roped(q, k, v, spec):
        import jax.numpy as jnp
        positions = jnp.arange(q.shape[1])
        return real(sl._rope(q, positions, 1e4),
                    sl._rope(k, positions, 1e4), v, spec)
    return {"sparse_attention": roped}


def _no_output_norm(sl):
    """The rmsnorm that follows a linear core hands its input back."""
    real_core, real_norm = sl.linear_attention, sl.rmsnorm
    after_core = []

    def core(*a, **kw):
        after_core.append(True)
        return real_core(*a, **kw)

    def norm(x, w, eps=1e-6):
        return x if after_core and after_core.pop() else real_norm(x, w, eps)
    return {"linear_attention": core, "rmsnorm": norm}


def no_gate(kind):
    """A mixer kind's gate left out: W_g = 0 makes it the constant
    1 / 2, which W_o x 2 takes out."""
    def fault(loss_fn):
        def faulty(params, batch):
            layers = params[kind]
            return loss_fn({**params, kind: {
                **layers, "wg": layers["wg"] * 0,
                "wo": layers["wo"] * 2}}, batch)
        return faulty
    return fault


def probes(config):
    """(name, what is built differently, what the loss is wrapped in):
    the faults ISSUE 37 names, for a configuration's sizes. The system
    with its sparse layer dense is the system built with a dense
    length no sequence reaches; with the forced blocks dropped, with
    no initial block and a window of the query's own block alone;
    without its logit multiplier, with `dim_model_base` the hidden
    size."""
    def sparse(**change):
        return {"sparse_config": {**config["sparse_config"], **change}}
    return (
        ("as it is", {}, None),
        ("sparse_layer_dense", sparse(dense_len=1 << 30), None),
        ("forced_blocks_dropped",
         sparse(init_blocks=0,
                window_size=config["sparse_config"]["block_size"]), None),
        ("no_decay", {}, _traced_with(_no_decay)),
        ("no_rope_on_linear", {}, _traced_with(_no_rope)),
        ("rope_on_sparse", {}, _traced_with(_rope_on_sparse)),
        ("no_gate_sparse", {}, no_gate("sparse")),
        ("no_gate_linear", {}, no_gate("linear")),
        ("no_output_norm", {}, _traced_with(_no_output_norm)),
        ("no_embedding_multiplier", {"scale_emb": 1}, None),
        ("no_depth_multiplier", {"scale_depth": config["published"][
            "num_hidden_layers"] ** 0.5}, None),
        ("no_logit_multiplier", {"dim_model_base": config["hidden_size"]},
         None),
        ("rounded_weights", {}, rounded_weights))


def load():
    return (run.load_module(ROOT, "drivers", "jit_train"),
            run.load_module(ROOT, "models", "minicpm_sala"),
            run.load_module(ROOT, "reference", "minicpm_sala"))


PROBES = probes(TINY["config"])
# The rehearsal runs the system in float32, like the reference: as it
# is they differ by 1e-7 | 0 (loss | gradient norm) and the mildest
# fault at this size (weights rounded to 3 bits of mantissa) by
# 7e-6 | 9e-5, where the chip's limits are bfloat16's. These are the
# float32 rehearsal's.
REHEARSAL_TOLERANCE = {"loss": 2e-6, "grad_norm": 2e-5}


@pytest.mark.parametrize("blocks", [None, (16, 32)],
                         ids=["whole", "in-blocks"])
@pytest.mark.parametrize("name,change,fault", PROBES,
                         ids=[p[0].replace(" ", "_") for p in PROBES])
def test_system_against_reference(monkeypatch, name, change, fault, blocks):
    import jax
    from horovod_tpu.parallel.mesh import data_parallel_mesh
    driver, model, reference = load()
    monkeypatch.setattr(reference, "TOLERANCE", REHEARSAL_TOLERANCE)
    if blocks:
        monkeypatch.setattr(reference, "QUERY_BLOCK", blocks[0])
        monkeypatch.setattr(reference, "TOKEN_BLOCK", blocks[1])
    mesh = data_parallel_mesh(jax.devices()[:2])
    assert check(driver, model, reference, TINY["config"], TINY["cell"],
                 mesh, 7, change, fault) is (name == "as it is")


def _published():
    config = run.read_json(
        os.path.join(ROOT, "configs", "minicpm-sala-tp2vp8.json"))
    spec = run.read_json(os.path.join(
        ROOT, "workloads", "minicpm-sala-tp2vp8.jit-dp1.json"))
    return run.load_module(ROOT, "models", "minicpm_sala"), config, spec


def test_flops_equal_the_hand_count():
    model, config, spec = _published()
    # By hand, this chip's share at the published widths. The FFN's
    # held half: 3 * 4096 * 8192                        = 100,663,296
    # sparse layer: W_q, W_g, W_o 4096 * 2048 each, W_k, W_v
    #   4096 * 128 each = 3 * 8,388,608 + 2 * 524,288 + the FFN
    #                                                   = 126,877,696
    # lightning layer: five of 4096 * 2048 + the FFN    = 142,606,336
    # head 4096 * 9216                                  =  37,748,736
    weights = 126_877_696 + 3 * 142_606_336 + 37_748_736
    assert weights == 592_445_440
    assert model.matmul_weights_a_token(config) == weights
    # the sparse core, pairs a head at 32,768: the first 4,096 queries
    # have no more than 64 blocks and keep every key at or before them,
    # 4096 * 4097 / 2 = 8,390,656; the other 28,672 keep 63 whole
    # blocks and their own up to themselves, 28,672 * 4,032 + 448 *
    # (64 * 65 / 2) = 116,536,... : 23.3 % of the causal pairs
    pairs = 4096 * 4097 // 2 + 28_672 * 63 * 64 + 448 * (64 * 65 // 2)
    assert model.selected_pairs(config, 32768) == pairs == 124_928_000
    assert model.selected_pairs(config, 8192) == 8192 * 8193 // 2
    assert round(pairs / (32768 * 32769 // 2), 4) == 0.2327
    # the linear core: k^T v and q S, 2 * 128 * 128 each, 16 heads
    core = 4 * 128 * 16 * pairs / 32768 + 3 * 4 * 128 * 128 * 16
    by_hand = 3 * (2 * weights + core)
    assert model.flops_per_unit(config, spec) == by_hand
    assert round(by_hand / 1e9, 3) == 3.658             # GFLOP a token
    # the kernels: 11 products of 2 * 128 a pair and head, 28.6 ms at
    # the MXU's peak against 1.6 ms of bytes
    assert model.sparse_attention_flops(config, spec) == 22 * 128 * 16 * pairs
    assert round(model.sparse_attention_flops(config, spec)
                 / 197e12 * 1e3, 1) == 28.6
    q, kv, row = 32768 * 2048 * 2, 32768 * 128 * 2, 32768 * 16 * 4
    assert model.sparse_attention_bytes(config, spec) == (
        2 * (2 * q + 2 * kv + row) + (3 * q + 2 * kv + 2 * row)
        + (2 * q + 4 * kv + 2 * row))


def test_linear_kernels_counts_equal_the_hand_count():
    model, config, spec = _published()
    # 9 products of 2 * 128 * 128 a token and head, 16 heads, 3 layers,
    # 32,768 tokens: 0.46 TFLOP, 2.4 ms at the MXU's peak; 18 arrays of
    # 32768 * 2048 * 2 bytes a layer: 7.25 GB, 8.8 ms at the HBM's
    assert model.linear_attention_flops(config, spec) \
        == 9 * 2 * 128 * 128 * 16 * 3 * 32768 == 463_856_467_968
    assert model.linear_attention_bytes(config, spec) \
        == 3 * 18 * 32768 * 2048 * 2 == 7_247_757_312
    assert round(7_247_757_312 / 819e9 * 1e3, 2) == 8.85


def test_parameter_count_of_the_share():
    """630.2 M parameters, 5.04 GB at 8 bytes (bf16 weights,
    gradients, both AdamW moments)."""
    import jax
    from horovod_tpu.models import sparse_linear as sl
    model, config, _ = _published()
    cfg = model.library_config(config)
    shapes = jax.eval_shape(lambda k: sl.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    count = sum(s.size for s in jax.tree.leaves(shapes))
    assert round(count / 1e6, 1) == 630.2
    assert round(count * 8 / 1e9, 2) == 5.04
    assert cfg.period_kinds == ("sparse", "linear", "linear", "linear")


def test_configuration_keeps_every_published_key():
    """Every key of the catalog's `config` is in the file at its
    published value, except the reduced ones, whose originals are
    under `published`; `mixer_types` is whole."""
    la6, la8, la4 = [LA] * 6, [LA] * 8, [LA] * 4
    catalog = {
        "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 16384, "lightning_head_dim": 128,
        "lightning_nh": 32, "lightning_nkv": 32,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "max_position_embeddings": 524288, "model_type": "minicpm_sala",
        "mixer_types": [M4, *la8, M4, *la6, M4, M4, *la4, M4, *la6, M4, M4,
                        M4],
        "num_attention_heads": 32, "num_hidden_layers": 32,
        "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
        "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
        "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
        "dim_model_base": 256, "tie_word_embeddings": False,
        "use_output_gate": True, "use_output_norm": True,
        "attn_use_output_gate": True}
    _, config, spec = _published()
    assert len(catalog["mixer_types"]) == 32
    assert catalog["mixer_types"].count(M4) == 8
    reduced = set(config["reduced"])
    assert reduced == {
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "lightning_nh", "lightning_nkv", "ffn_columns_held", "vocab_size"}
    for key, value in catalog.items():
        if key in reduced:
            assert config["published"][key] == value
            assert config[key] < value
        else:
            assert config[key] == value, key
    assert config["ffn_columns_held"] * 2 \
        == config["published"]["ffn_columns"] == config["intermediate_size"]
    assert config["mixer_types"][:4] == [M4, LA, LA, LA]
    assert sorted(config["assumed"])[:7] == [
        "a_sparse_config", "b_selection", "c_decay", "d_norms_and_gates",
        "e_rope", "f_multipliers", "g_initialiser"]
    # the traffic of ISSUE 37, and a sample the sparse layer selects in
    assert (spec["batch_per_chip"], spec["seq"], spec["ring"],
            spec["traced_steps"]) == (1, 32768, 4, 10)
    assert spec["sample"]["seq"] > config["sparse_config"]["dense_len"]
