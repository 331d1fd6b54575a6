"""The `jamba` family (Mamba-1 selective-scan mixers with an attention
layer among them, a SwiGLU after each, a tied head) at a tiny size on
the CPU: the system agrees with its plain reference through the
benchmark's own sample check (`jit_train.sample_check`), whole and in
blocks; the tolerances catch a lower precision and each of the named
faults; the FLOP and byte functions equal a hand count at the
published widths; the configuration keeps every published key."""

import contextlib
import os
from unittest import mock

import pytest

from perfbench import run
from perfbench.tests.test_harness import ROOT
from perfbench.tests.test_xing4 import _with, check, rounded_weights

TINY = {
    "config": {
        "attn_layer_offset": 2, "attn_layer_period": 4,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
        "mamba_dt_rank": 8, "mamba_expand": 2, "mamba_proj_bias": False,
        "num_attention_heads": 2, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 8,
        "num_key_value_heads": 1, "rms_norm_eps": 1e-6,
        "sliding_window": None, "tie_word_embeddings": True,
        "vocab_size": 128, "mamba_channels_held": 64,
        "ffn_columns_held": 64, "initializer_range": 0.15,
        "published": {"num_attention_heads": 4},
        # the equations are what this rehearsal holds; the chip holds
        # the precision (reference/jamba.py TOLERANCE)
        "dtype": "float32"},
    # a sample of 4 of the scan's chunks of 32
    "cell": {"batch_per_chip": 2, "seq": 64,
             "rate_metric": "tokens_per_s_chip",
             "sample": {"per_chip": 2, "seq": 128}}}


def _traced_with(patches):
    """A fault that traces the system with names of `models/jamba.py`
    or `parallel/selective_scan.py` replaced: `patches(jamba, ss)`
    gives {"jamba" or "ss": {name: replacement}}."""
    def fault(loss_fn):
        def faulty(params, batch):
            from horovod_tpu.models import jamba
            from horovod_tpu.parallel import selective_scan as ss
            modules = {"jamba": jamba, "ss": ss}
            with contextlib.ExitStack() as stack:
                for name, names in patches(jamba, ss).items():
                    stack.enter_context(
                        mock.patch.multiple(modules[name], **names))
                return loss_fn(params, batch)
        return faulty
    return fault


def _dropped_carry(jamba, ss):
    """Every chunk of the scan starts from a zero state, on either path
    (the forward kernel's state is its last scratch buffer)."""
    real_chunk, real_kernel = ss._chunk, ss._fwd_kernel

    def kernel(*refs, **kw):
        refs[-1][...] = refs[-1][...] * 0
        real_kernel(*refs, **kw)
    return {"ss": {"_chunk": lambda h0, *a: real_chunk(h0 * 0, *a),
                   "_fwd_kernel": kernel}}


def _no_softplus(jamba, ss):
    real = jamba.selective_scan

    def scan(x, delta, *a):
        import jax.numpy as jnp
        # softplus undone: delta = dt W_dt + b_dt as it came
        return real(x, jnp.log(jnp.expm1(delta)), *a)
    return {"jamba": {"selective_scan": scan}}


def _no_ssm_norms(widths):
    """dt, B and C go into the scan as x_proj gave them: the norms of
    those `widths` hand their input back."""
    def patches(jamba, ss):
        real = jamba.rmsnorm

        def norm(x, w, eps=1e-6):
            return x if w.shape[-1] in widths else real(x, w, eps)
        return {"jamba": {"rmsnorm": norm}}
    return patches


def _conv_not_causal(jamba, ss):
    """The conv's window centred on the position: it reads two ahead."""
    def conv(x, w, b):
        import jax.numpy as jnp
        K, L = w.shape[0], x.shape[1]
        xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (1, K - 2), (0, 0)))
        return sum(xp[:, k:k + L] * w[k].astype(jnp.float32)
                   for k in range(K)) + b
    return {"jamba": {"causal_conv": conv}}


def _rope_on(jamba, ss):
    real = jamba.attention

    def roped(q, k, v, causal=True):
        import jax.numpy as jnp
        from horovod_tpu.models.transformer import _rope
        positions = jnp.arange(q.shape[1])
        return real(_rope(q, positions, 1e4), _rope(k, positions, 1e4), v,
                    causal=causal)
    return {"jamba": {"attention": roped}}


def _no_d(path, a):
    import jax
    return a * 0 if jax.tree_util.keystr(path).endswith("['D']") else a


def probes(config):
    """(name, what is built differently, what the loss is wrapped in):
    the named faults, for a configuration's sizes. Attention at the
    wrong index is the system built with the attention layer one
    earlier in its period."""
    return (
        ("as it is", {}, None),
        ("dropped_carry", {}, _traced_with(_dropped_carry)),
        ("no_softplus", {}, _traced_with(_no_softplus)),
        ("no_d_skip", {}, _with(_no_d)),
        ("no_ssm_norms", {}, _traced_with(_no_ssm_norms(
            (config["mamba_dt_rank"], config["mamba_d_state"])))),
        ("conv_not_causal", {}, _traced_with(_conv_not_causal)),
        ("rope_on", {}, _traced_with(_rope_on)),
        ("attention_at_wrong_index",
         {"attn_layer_offset": config["attn_layer_offset"] - 1}, None),
        ("rounded_weights", {}, rounded_weights))


def load():
    return (run.load_module(ROOT, "drivers", "jit_train"),
            run.load_module(ROOT, "models", "jamba"),
            run.load_module(ROOT, "reference", "jamba"))


PROBES = probes(TINY["config"])
# The rehearsal runs the system in float32, like the reference; these
# are the float32 rehearsal's limits, where the chip's are bfloat16's.
REHEARSAL_TOLERANCE = {"loss": 2e-6, "grad_norm": 2e-5}


@pytest.mark.parametrize("blocks", [None, (32, 16, 32)],
                         ids=["whole", "in-blocks"])
@pytest.mark.parametrize("name,change,fault", PROBES,
                         ids=[p[0].replace(" ", "_") for p in PROBES])
def test_system_against_reference(monkeypatch, name, change, fault, blocks):
    import jax
    from horovod_tpu.parallel.mesh import data_parallel_mesh
    jit_train, model, reference = load()
    monkeypatch.setattr(reference, "TOLERANCE", REHEARSAL_TOLERANCE)
    if blocks:
        for key, value in zip(("POSITION_BLOCK", "QUERY_BLOCK",
                               "TOKEN_BLOCK"), blocks):
            monkeypatch.setattr(reference, key, value)
    mesh = data_parallel_mesh(jax.devices()[:2])
    assert check(jit_train, model, reference, TINY["config"], TINY["cell"],
                 mesh, 7, change, fault) is (name == "as it is")


def _published():
    config = run.read_json(
        os.path.join(ROOT, "configs", "jamba2-3b-tp2vp4.json"))
    spec = run.read_json(os.path.join(
        ROOT, "workloads", "jamba2-3b-tp2vp4.jit-dp1.json"))
    return run.load_module(ROOT, "models", "jamba"), config, spec


def test_flops_equal_the_hand_count():
    model, config, spec = _published()
    # By hand, this chip's share at the published widths. A Mamba
    # layer: in_proj 2560 * 5120, the conv 4 * 2560, x_proj
    # 2560 * 192, dt_proj 160 * 2560, out_proj 2560 * 2560, and the
    # FFN's held half 3 * 2560 * 4096                  = 52,029,440
    mamba = 2560 * 5120 + 4 * 2560 + 2560 * 192 + 160 * 2560 \
        + 2560 * 2560 + 3 * 2560 * 4096
    assert mamba == 52_029_440
    # the attention layer: W_q, W_o 2560 * 1280, W_k, W_v 2560 * 128,
    # and the FFN                                      = 38,666,240
    attention = 2 * 2560 * 1280 + 2 * 2560 * 128 + 3 * 2560 * 4096
    assert attention == 38_666_240
    weights = 13 * mamba + attention + 2560 * 16384
    assert model.matmul_weights_a_token(config) == weights
    # the attention core over the causal pairs, 10 heads of 128, and
    # the recurrence's 7 operations a held channel and state
    core = 4 * 128 * 10 * 16385 / 2 + 13 * 7 * 2560 * 16
    by_hand = 3 * (2 * weights + core)
    assert model.flops_per_unit(config, spec) == by_hand
    assert round(by_hand / 1e9, 2) == 4.68             # GFLOP a token
    assert round(by_hand * 16384 / 1e12, 1) == 76.7    # TFLOP a step


def test_scan_kernels_counts_equal_the_hand_count():
    model, config, spec = _published()
    # 13 layers x 16,384 tokens x 2,560 channels x 16 states x (7
    # forward, twice, + 17 backward): 0.27 TFLOP, 1.4 ms at the MXU's
    # peak; bytes: forward x bf16, delta and y f32 (10 bytes a token
    # and channel) and B, C, twice; backward x, delta, dy, dx, d delta
    # (16 bytes) and B, C, dB, dC, dA
    rows, bc = 16384 * 2560, 2 * 16384 * 16 * 4
    assert model.selective_scan_flops(config, spec) \
        == 13 * 16384 * 2560 * 16 * 31 == 270_448_721_920
    assert model.selective_scan_bytes(config, spec) == 13 * (
        2 * (10 * rows + bc) + 16 * rows + 2 * bc + 2560 * 16 * 4)
    assert round(model.selective_scan_bytes(config, spec) / 819e9 * 1e3,
                 1) == 24.1
    assert model.selective_scan_bytes(config, spec) / 819e9 > \
        model.selective_scan_flops(config, spec) / 197e12


def test_parameter_count_of_the_share():
    """757.70 M parameters, 6.06 GB at 8 bytes (bf16 weights,
    gradients, both AdamW moments)."""
    import jax
    from horovod_tpu.models import jamba
    model, config, _ = _published()
    cfg = model.library_config(config)
    shapes = jax.eval_shape(lambda k: jamba.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    count = sum(s.size for s in jax.tree.leaves(shapes))
    layer = sum(s.size for s in jax.tree.leaves(shapes["mamba"])) / 13
    assert round(layer / 1e6, 2) == 52.08
    assert round(sum(s.size for s in jax.tree.leaves(
        shapes["attention"])) / 1e6, 2) == 38.67
    assert round(count / 1e6, 2) == 757.70
    assert round(count * 8 / 1e9, 2) == 6.06
    assert cfg.period_kinds == ("mamba",) * 7 + ("attention",) \
        + ("mamba",) * 6


def test_configuration_keeps_every_published_key():
    """Every key of the catalog's `config` is in the file at its
    published value, except the reduced ones, whose originals are
    under `published`."""
    catalog = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
        "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536}
    _, config, spec = _published()
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "num_attention_heads",
                       "mamba_channels_held", "ffn_columns_held",
                       "vocab_size"}
    for key, value in catalog.items():
        if key in reduced:
            assert config["published"][key] == value
            assert config[key] < value
        else:
            assert config[key] == value, key
    assert config["mamba_channels_held"] * 2 \
        == config["published"]["mamba_channels"] \
        == config["mamba_expand"] * config["hidden_size"]
    assert config["ffn_columns_held"] * 2 \
        == config["published"]["ffn_columns"] == config["intermediate_size"]
    assert sorted(config["assumed"])[:5] == [
        "a_layer_order", "b_no_rope", "c_initialiser", "d_partial_sums",
        "e_equations"]
    assert (spec["batch_per_chip"], spec["seq"], spec["ring"],
            spec["traced_steps"]) == (1, 16384, 4, 10)
