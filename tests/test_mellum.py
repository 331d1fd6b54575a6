"""JetBrains Mellum 2's layer on `models/window_moe.py` (plain rope on
the windowed layers and YaRN on the full ones, no output gate and no
post-norms, a softmax top-k router renormalised over fine-grained
experts, no shared expert, no dense layer) against the plain reference
of `perfbench/reference/mellum.py`, on seeded weights at tiny widths
that keep the published ratios: 8 q heads a kv head and q wider than
the hidden size, three windowed layers to one full, a window a
quarter of the sequence, 8 experts chosen of a router 4 times as wide
as the share. Also what the family brought to the library:
`moe.topk_softmax_route` against a written-out loop,
`latent_moe.yarn_inv_freq` against transformers' formula at the
published numbers, and the fields whose defaults keep Trinity's
layer."""

import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import latent_moe, window_moe as wm
from horovod_tpu.parallel import build_train_step, moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLIDING, FULL = "sliding_attention", "full_attention"

# the published config.json's keys at tiny widths, as one share holds
# them: two periods, so that the scan runs twice; a small original
# length and theta, so that YaRN's ramp turns within 64 positions
CONFIG = {
    "attention_bias": False, "head_dim": 8, "hidden_act": "silu",
    "hidden_size": 32, "intermediate_size": 64,
    "layer_types": ([SLIDING] * 3 + [FULL]) * 3,
    "max_position_embeddings": 256, "mlp_layer_types": ["sparse"] * 12,
    "moe_intermediate_size": 16, "norm_topk_prob": True,
    "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 8,
    "num_hidden_layers": 8, "num_key_value_heads": 1, "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 1000, "factor": 16,
            "original_max_position_embeddings": 16, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 1000}},
    "sliding_window": 16, "tie_word_embeddings": False, "vocab_size": 128,
    "experts_first": 0, "initializer_range": 0.02,
    "published": {"num_experts": 64}}
# the expert layer whole: the 4 chips' 16 experts each
UNCUT = {**CONFIG, "num_experts": 64}


def _perfbench(kind):
    from perfbench import run
    return run.load_module(os.path.join(REPO, "perfbench"), kind, "mellum")


@pytest.fixture(scope="module")
def reference():
    return _perfbench("reference")


def _library(config):
    """The adapter's translation, in float32 and without remat, so
    that the comparison is of the mathematics."""
    return dataclasses.replace(_perfbench("models").library_config(config),
                               dtype=jnp.float32, remat=False)


def _seeded(cfg, seed):
    """`init_params` with every leaf seeded: norm gains that are not
    one show where a gain is applied."""
    params = wm.init_params(cfg, jax.random.PRNGKey(seed))
    leaves, tree = jax.tree.flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))

    def made(key, path, a):
        noise = jax.random.normal(key, a.shape, a.dtype)
        if "norm" in jax.tree_util.keystr(path[-1:]):
            return 1.0 + 0.3 * noise
        return 0.15 * noise
    return jax.tree.unflatten(
        tree, [made(k, path, a) for k, (path, a) in zip(keys, leaves)])


@pytest.fixture(scope="module")
def cfg():
    return _library(CONFIG)


@pytest.fixture(scope="module")
def params(cfg):
    return jax.jit(lambda: _seeded(cfg, 3))()


@pytest.fixture(scope="module")
def layer(params):
    return jax.tree.map(lambda a: a[0], params["layers"])


def activations(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def close(got, want, tol=2e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


# -- the router -------------------------------------------------------------

def _softmax_route_loop(logits, k):
    """Token by token in float64: the softmax, the k largest
    probabilities in descending order, their sum-normalised values,
    and the Jacobian of those gates to the token's logits."""
    experts, gates, jacobians = [], [], []
    for row in np.asarray(logits, np.float64):
        p = np.exp(row - row.max())
        p /= p.sum()
        top = sorted(range(len(p)), key=lambda e: -p[e])[:k]
        dp = np.diag(p) - np.outer(p, p)           # dp_i / dl_m
        chosen, d_chosen = p[top], dp[top]
        total = chosen.sum()
        d_total = d_chosen.sum(axis=0)
        d_chosen = d_chosen / total - np.outer(chosen, d_total) / total**2
        chosen = chosen / total
        experts.append(top)
        gates.append(chosen)
        jacobians.append(d_chosen)
    return np.array(experts), np.array(gates), np.array(jacobians)


@pytest.mark.parametrize("k,width", [(8, 64), (2, 16)],
                         ids=["top8-of-64", "top2-of-16"])
def test_topk_softmax_route_against_a_loop(k, width):
    """Experts, gates and the gradient that reaches the router's
    logits through the gates, against the loop; the gates sum to
    one."""
    logits = activations(1, 16, width) * 2.0
    cot = activations(2, 16, k)
    experts, gates = moe.topk_softmax_route(logits, k)
    want_experts, want_gates, jacobian = _softmax_route_loop(logits, k)
    assert experts.dtype == jnp.int32 and gates.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(experts), want_experts)
    close(gates, want_gates, 1e-6)
    close(jnp.sum(gates, axis=-1), np.ones(16), 1e-6)
    d_logits = jax.grad(lambda x: jnp.sum(
        moe.topk_softmax_route(x, k)[1] * cot))(logits)
    close(d_logits, np.einsum("tj,tjm->tm", np.asarray(cot), jacobian), 1e-6)
    # and on to the router's weights: d W_r = h^T d_logits
    h, w_r = activations(3, 16, 32), activations(4, 32, width) * 0.3
    d_w = jax.grad(lambda w: jnp.sum(moe.topk_softmax_route(
        jnp.dot(h, w, precision="highest"), k)[1] * cot))(w_r)
    _, _, jacobian = _softmax_route_loop(
        jnp.dot(h, w_r, precision="highest"), k)
    close(d_w, np.asarray(h, np.float64).T @ np.einsum(
        "tj,tjm->tm", np.asarray(cot), jacobian), 1e-5)


def test_the_router_kind_is_counted(cfg, layer):
    """Each trace of an expert layer counts the router its
    configuration names beside the dispatch it took: softmax here,
    sigmoid at Trinity's defaults."""
    def count(router):
        return hvd.metrics().get("hvd_moe_router_traces_total", {}).get(
            (router,), 0)
    x = activations(5, 2, 64, 32)
    before = count("softmax"), count("sigmoid")
    wm.expert_block(cfg, layer, x, "window")
    assert (count("softmax"), count("sigmoid")) == (before[0] + 1,
                                                    before[1])
    trinity = wm.WindowMoEConfig(d_model=32, dtype=jnp.float32)
    wm.expert_block(trinity, jax.tree.map(
        lambda a: a[0], wm.init_params(trinity, jax.random.PRNGKey(0))[
            "layers"]), x, "window")
    assert count("sigmoid") == before[1] + 1


# -- YaRN -------------------------------------------------------------------

def test_yarn_frequencies_at_the_published_numbers():
    """transformers' `_compute_yarn_parameters`, written out as it
    computes (float32, factor max_position_embeddings / original
    length, truncated correction range), at the full layers'
    rope_parameters: head_dim 128, theta 5e5, factor 16 over 8,192
    positions, betas 32 and 1."""
    dim, base, factor, original = 128, 500000.0, 16.0, 8192
    got = latent_moe.yarn_inv_freq(dim, base, 131072 / original, original,
                                   32.0, 1.0)
    pos_freqs = np.float32(base) ** (np.arange(0, dim, 2).astype(np.float32)
                                     / np.float32(dim))
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor
                                                           * pos_freqs)

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(32)), 0)
    high = min(math.ceil(correction_dim(1)), dim - 1)
    assert (low, high) == (18, 35)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    want = interpolation * (1 - extrapolation_factor) \
        + extrapolation * extrapolation_factor
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # the fast dims keep their frequency, the slow ones are divided by
    # the factor
    np.testing.assert_allclose(got[:18], extrapolation[:18], rtol=2e-6)
    np.testing.assert_allclose(got[35:], extrapolation[35:] / 16, rtol=2e-6)


# -- the sub-layers ----------------------------------------------------------

@pytest.mark.parametrize("kind,name", [("window", SLIDING), ("full", FULL)])
def test_attention(cfg, layer, reference, kind, name):
    """No gate and no post-norm: the sub-layer is `attention_sum`."""
    x = activations(10, 2, 64, 32)
    want = reference.attention(CONFIG, layer, x, name)
    close(wm.attention_sum(cfg, layer, x, kind), want)
    close(wm.gated_attention(cfg, layer, x, kind), want)


def test_the_full_layers_rope_is_yarn(cfg, layer):
    """YaRN with its attention factor, which is neither a plain rope,
    nor YaRN's frequencies alone, nor no rope; the windowed layers'
    plain rope is Trinity's."""
    x = activations(10, 2, 64, 32)
    yarn = cfg.full_rope
    assert yarn.factor == 16.0 and yarn.original_len == 16
    got = wm.attention_sum(cfg, layer, x, "full")
    for other in (wm.Rope(yarn.theta),
                  dataclasses.replace(yarn, attention_factor=1.0), None):
        changed = dataclasses.replace(cfg, full_rope=other)
        assert float(jnp.max(jnp.abs(
            wm.attention_sum(changed, layer, x, "full") - got))) > 1e-3
    assert cfg.rope_theta == 1000.0


@pytest.mark.parametrize("kind,name", [("window", SLIDING), ("full", FULL)])
def test_expert_block(cfg, layer, reference, kind, name):
    """The whole layer: both sub-layers' sums join the residual as
    they are."""
    x = activations(12, 2, 64, 32)
    close(wm.expert_block(cfg, layer, x, kind),
          reference.layer(CONFIG, name, layer, x))


# -- the share --------------------------------------------------------------

def test_the_four_expert_shares_add_up_to_the_uncut_expert_layer(reference):
    """model-configs guide, section 4: what the 4 chips of the
    expert-parallel group compute (experts 0-15, 16-31, 32-47, 48-63
    under the router's 64) adds up to the uncut layer's routed sum."""
    uncut = jax.jit(lambda: _seeded(_library(UNCUT), 5))()
    whole = jax.tree.map(lambda a: a[0], uncut["layers"])
    x = activations(15, 2, 64, 32)
    m = wm.rmsnorm(x, whole["mlp_norm"], 1e-6)
    total = jnp.zeros_like(x)
    for chip in range(4):
        held = slice(16 * chip, 16 * chip + 16)
        share = {**whole, **{n: whole[n][held]
                             for n in ("w_gate", "w_up", "w_down")}}
        share_cfg = dataclasses.replace(_library(CONFIG),
                                        experts_first=16 * chip)
        part = latent_moe.expert_ffn(share_cfg, share, x, shared=False,
                                     router=share_cfg.router)
        close(part, reference.expert_ffn(
            {**CONFIG, "experts_first": 16 * chip}, share, m))
        total = total + part
        assert float(jnp.max(jnp.abs(part))) > 1e-3
    close(total, reference.expert_ffn(UNCUT, whole, m))


# -- the model --------------------------------------------------------------

def test_loss_and_gradients_through_build_train_step(cfg, params, reference):
    """Two data shards through `build_train_step` with every layer
    checkpointed and the period scanned twice, as the cell runs it,
    and an optimizer that changes nothing and hands back the
    gradients; against the plain reference's mean over the shards:
    the loss and every gradient."""
    tokens = jax.random.randint(jax.random.PRNGKey(17), (4, 64), 0, 128)

    def mean_loss(p):
        return jnp.mean(jnp.stack([
            reference.loss(CONFIG, p, {"tokens": tokens[i:i + 2]})
            for i in (0, 2)]))
    want, want_grads = jax.jit(jax.value_and_grad(mean_loss))(params)
    mesh = Mesh(np.array(jax.devices()[:2]), axis_names=("data",))
    keep = optax.GradientTransformation(
        lambda p: jax.tree.map(jnp.zeros_like, p),
        lambda g, s, p=None: (jax.tree.map(jnp.zeros_like, g), g))
    conf = dataclasses.replace(cfg, remat=True)
    step = build_train_step(
        lambda p, b: wm.loss_fn(conf, p, b), keep, mesh,
        batch_spec={"tokens": P("data")}, donate=False)
    _, grads, metrics = step(params, keep.init(params), {"tokens": tokens})
    close(metrics["loss"], want)
    flat, _ = jax.tree.flatten_with_path(grads)
    for (path, a), b in zip(flat, jax.tree.leaves(want_grads)):
        assert a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5,
            atol=2e-5 * float(jnp.max(jnp.abs(b))),
            err_msg=jax.tree_util.keystr(path))
    # every layer of both periods got its own gradient, the router too
    for name in ("wq", "router", "w_down"):
        per_layer = jnp.sqrt(jnp.sum(jnp.square(
            grads["layers"][name].reshape(8, -1)), axis=1))
        assert float(jnp.min(per_layer)) > 1e-6, name


# -- the fields and their defaults ------------------------------------------

def test_the_layer_holds_only_what_the_family_has(params):
    layer = params["layers"]
    assert "dense" not in params
    for absent in ("wg", "attn_post_norm", "mlp_post_norm", "router_bias",
                   "s_gate", "s_up", "s_down"):
        assert absent not in layer
    assert layer["wq"].shape == (8, 32, 64)      # q wider than the hidden
    assert layer["router"].shape == (8, 32, 64)  # the router whole
    assert layer["w_gate"].shape == (8, 16, 32, 16)


def test_the_defaults_are_trinity_s_layer():
    cfg = wm.WindowMoEConfig()
    assert (cfg.output_gate, cfg.post_norms, cfg.router) == (
        True, True, "sigmoid")
    assert cfg.full_rope is None and cfg.d_ff_shared > 0
    params = wm.init_params(cfg, jax.random.PRNGKey(0))
    assert {"wg", "attn_post_norm", "mlp_post_norm", "router_bias",
            "s_gate"} <= set(params["layers"])
    assert "dense" in params and "attn_post_norm" in params["dense"]
