"""`models/latent_moe.py` and `parallel/moe.py`'s dropless expert share
against the plain reference of `perfbench/reference/xing4.py`, on
seeded weights at tiny widths that keep every ratio of the published
model: 4 residual streams, 64 experts of which 8 are held and 4
chosen a token, a rope part on every head, values narrower than the
keys, one leading dense layer, one multi-token module."""

import dataclasses
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.metrics import snapshot
from horovod_tpu.models import latent_moe as lm
from horovod_tpu.parallel import build_train_step, moe
from horovod_tpu.parallel.fused_attention import fused_causal_attention

# `horovod_tpu.parallel.ring_attention` the attribute is the function
ra = importlib.import_module("horovod_tpu.parallel.ring_attention")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the published config.json's keys at tiny widths
CONFIG = {
    "first_k_dense_replace": 1, "hidden_size": 32, "intermediate_size": 80,
    "kv_lora_rank": 16, "moe_intermediate_size": 16, "n_group": 1,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_attention_heads": 4,
    "num_experts_per_tok": 4, "num_hidden_layers": 3,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 24, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "topk_group": 1, "v_head_dim": 8,
    "vocab_size": 128, "experts_first": 0, "mtp_lambda": 0.3,
    "initializer_range": 0.02,
    "published": {"n_routed_experts": 64}}


def _perfbench(kind):
    from perfbench import run
    return run.load_module(os.path.join(REPO, "perfbench"), kind, "xing4")


@pytest.fixture(scope="module")
def reference():
    return _perfbench("reference")


@pytest.fixture(scope="module")
def cfg():
    """The adapter's translation of CONFIG, in float32 and without
    remat, so that the comparison is of the mathematics."""
    made = _perfbench("models").library_config(CONFIG)
    return dataclasses.replace(made, dtype=jnp.float32, remat=False)


@pytest.fixture(scope="module")
def params(cfg):
    return jax.jit(lambda k: lm.init_params(cfg, k))(jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def layer(params):
    return jax.tree.map(lambda a: a[0], params["layers"])


def activations(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def to_reference(streams):
    """n arrays (B, L, D), the program's streams -> (B, L, n, D)."""
    return jnp.stack(streams, axis=2)


def close(got, want, tol=2e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


# -- residual streams -------------------------------------------------------

def test_mixer_coefficients(cfg, layer, reference):
    streams = tuple(activations(0, 4, 2, 16, 32))
    pre, post, res = lm.mixer_coefficients(cfg, layer["hc_attn"], streams)
    want = reference.mixer_coefficients(CONFIG, layer["hc_attn"],
                                        to_reference(streams))
    close(jnp.moveaxis(pre, 0, -1), want[0])
    close(jnp.moveaxis(post, 0, -1), want[1])
    close(jnp.moveaxis(res, (0, 1), (-2, -1)), want[2])
    # no H is the identity, and the gate of a stream is not constant
    assert float(jnp.std(pre)) > 1e-3 and float(jnp.std(res)) > 1e-3


@pytest.mark.parametrize("iters, doubly_stochastic", [(20, True),
                                                      (2, False)])
def test_sinkhorn_rows_and_columns(iters, doubly_stochastic):
    m = jnp.exp(activations(1, 4, 4, 64))
    out = lm.sinkhorn(m, iters, 1e-6)
    off = max(float(jnp.max(jnp.abs(jnp.sum(out, axis=a) - 1.0)))
              for a in (0, 1))
    assert (off < 1e-4) is doubly_stochastic, off


def test_mixed_sublayer(cfg, layer, reference):
    streams = tuple(activations(2, 4, 2, 16, 32))
    w = activations(3, 32, 32) * 0.2

    def sublayer(u):
        return jnp.tanh(u @ w)
    got = lm.mixed(cfg, layer["hc_ffn"], streams, sublayer)
    want = reference.mixed(CONFIG, layer["hc_ffn"], to_reference(streams),
                           sublayer)
    close(to_reference(got), want)


# -- latent attention -------------------------------------------------------

def test_latent_attention(cfg, layer, reference):
    u = activations(4, 2, 16, 32)
    close(lm.latent_attention(cfg, layer, u),
          reference.latent_attention(CONFIG, layer, u))


def test_yarn_frequencies_and_scale(cfg, reference):
    close(lm.yarn_inv_freq(cfg.qk_rope_dim, cfg.rope_theta, cfg.rope_factor,
                           cfg.rope_original_len, cfg.rope_beta_fast,
                           cfg.rope_beta_slow),
          reference.yarn_inv_freq(CONFIG), 1e-7)
    assert lm.softmax_scale(cfg) == pytest.approx(
        reference.softmax_scale(CONFIG))
    # the published model: 192^-0.5 * (0.1 ln 64 + 1)^2
    published = dataclasses.replace(cfg, qk_nope_dim=128, qk_rope_dim=64)
    assert lm.softmax_scale(published) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64.0) + 1.0) ** 2)


def test_fused_core_takes_latent_widths(monkeypatch):
    """q / k 192 wide, v 128: the fused path hands the kernels all
    three as they are (two heads of 192 are a whole number of lanes),
    keeps the scale it is handed, and returns the kernels' 128-wide
    output; forward and gradients agree with `dense_attention`. With
    grouped kv, q and k are zero-padded to 256. Equal widths that are
    no whole number of lanes keep the dense path."""
    import functools
    from horovod_tpu.parallel import fused_attention
    seen = []

    def interpreted(q, k, v, scale, window=None):
        seen.append((q.shape, k.shape, v.shape, scale))
        return fused_causal_attention(q, k, v, scale, window=window,
                                      interpret=True)
    monkeypatch.setattr(fused_attention, "fused_causal_attention",
                        interpreted)
    q, k = activations(5, 1, 128, 2, 192), activations(6, 1, 128, 2, 192)
    v = activations(7, 1, 128, 2, 128)
    scale = 192 ** -0.5 * 2.0048

    def loss(path, q, k, v):
        out = path(q, k, v, True, scale)
        assert out.shape == (1, 128, 2, 128)
        return jnp.sum(out * jnp.cos(out)), out
    (_, got), g_got = jax.value_and_grad(
        functools.partial(loss, ra.flash_attention_path), (0, 1, 2),
        has_aux=True)(q, k, v)
    (_, want), g_want = jax.value_and_grad(
        functools.partial(loss, ra.dense_attention), (0, 1, 2),
        has_aux=True)(q, k, v)
    assert seen[0] == ((1, 128, 2, 192),) * 2 + ((1, 128, 2, 128), scale)
    close(got, want, 2e-4)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape
        close(a, b, 2e-4)
    assert ra._fused_qk_width(q, k, v) == 192
    assert ra._fused_qk_width(q, k[:, :, :1], v[:, :, :1]) == 256
    assert ra._fused_qk_width(q[..., :64], k[..., :64], v[..., :64]) == 64


def test_attention_counts_the_latent_call_as_dense_on_the_cpu():
    def count(path):
        return snapshot().get("hvd_attention_traces_total", {}).get(
            (path,), 0.0)
    before = count("dense"), count("fused")
    out = ra.attention(activations(8, 1, 16, 2, 12),
                       activations(9, 1, 16, 2, 12),
                       activations(10, 1, 16, 2, 8), scale=0.3)
    assert out.shape == (1, 16, 2, 8)
    assert (count("dense"), count("fused")) == (before[0] + 1, before[1])


# -- experts ----------------------------------------------------------------

def test_expert_ffn_with_a_bias_that_flips_a_choice(cfg, layer, reference):
    u = activations(11, 2, 16, 32)
    h = u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True) + 1e-6)
    scores = jax.nn.sigmoid(h.reshape(-1, 32) @ layer["router"])
    with_bias = jax.lax.top_k(scores + layer["router_bias"], 4)[1]
    without = jax.lax.top_k(scores, 4)[1]
    assert bool(jnp.any(jnp.sort(with_bias) != jnp.sort(without)))
    got = lm.expert_ffn(cfg, layer, u)
    close(got, reference.expert_ffn(CONFIG, layer, u))
    # the shared expert alone is the difference
    close(got - lm.expert_ffn(cfg, layer, u, shared=False),
          reference.expert_ffn(CONFIG, layer, u)
          - reference.expert_ffn(CONFIG, layer, u, shared=False))


def test_the_eight_shares_add_up_to_the_uncut_layer(cfg, reference):
    """model-configs guide, section 4: what the 8 chips of a layer
    compute, the shared expert counted once, is the uncut layer."""
    whole_cfg = dataclasses.replace(cfg, experts_held=64, n_expert_layers=1)
    whole = jax.tree.map(
        lambda a: a[0], jax.jit(lambda k: lm.init_params(whole_cfg, k))(
            jax.random.PRNGKey(5))["layers"])
    u = activations(12, 2, 16, 32)
    uncut = reference.expert_ffn(
        {**CONFIG, "n_routed_experts": 64}, whole, u)
    total = jnp.zeros_like(u)
    for chip in range(8):
        share = {**whole, **{k: whole[k][8 * chip:8 * chip + 8]
                             for k in ("w_gate", "w_up", "w_down")}}
        share_cfg = dataclasses.replace(cfg, experts_first=8 * chip)
        part = lm.expert_ffn(share_cfg, share, u, shared=chip == 0)
        # and each share is what the reference gives for that share
        close(part, reference.expert_ffn(
            {**CONFIG, "experts_first": 8 * chip}, share, u,
            shared=chip == 0))
        total = total + part
    close(total, uncut)
    assert float(jnp.max(jnp.abs(uncut - part))) > 1e-3


def _skewed(layer):
    """A router under which held expert 2 takes nearly every token."""
    bias = layer["router_bias"].at[2].set(5.0)
    return {**layer, "router_bias": bias}


def test_no_pair_is_dropped_under_a_skewed_router(cfg, layer, reference):
    skewed = _skewed(layer)
    u = activations(13, 2, 16, 32)
    experts, _ = moe.topk_sigmoid_route(
        u.reshape(-1, 32) @ skewed["router"], skewed["router_bias"], 4, 2.0)
    assert float(jnp.mean(jnp.any(experts == 2, axis=-1))) == 1.0
    close(lm.expert_ffn(cfg, skewed, u),
          reference.expert_ffn(CONFIG, skewed, u))
    assert moe.max_pairs(32, 4, 8) == 128 and moe.max_pairs(32, 4, 2) == 64


def test_a_buffer_filled_to_its_bound_drops_nothing(cfg, layer, reference):
    """Two experts held and every token choosing both: tokens x 2
    pairs, which is `max_pairs`, the whole dispatch buffer."""
    bias = layer["router_bias"].at[:2].set(5.0)
    full = {**layer, "router_bias": bias,
            **{k: layer[k][:2] for k in ("w_gate", "w_up", "w_down")}}
    u = activations(13, 2, 16, 32)
    experts, _ = moe.topk_sigmoid_route(
        u.reshape(-1, 32) @ full["router"], bias, 4, 2.0)
    assert int(jnp.sum(experts < 2)) == moe.max_pairs(32, 4, 2)
    close(lm.expert_ffn(cfg, full, u),
          reference.expert_ffn(CONFIG, full, u))
    assert snapshot()["hvd_moe_pairs_bound"][()] == 64
    assert snapshot()["hvd_moe_traces_total"][("sorted_ragged",)] >= 1


def test_expert_gradients(cfg, layer, reference):
    u = activations(14, 2, 16, 32)

    def through(fn, conf):
        def f(w, u):
            out = fn(conf, w, u)
            out = out[0] if isinstance(out, tuple) else out
            return jnp.sum(out * jnp.sin(out))
        return jax.jit(jax.grad(f, (0, 1)))(layer, u)
    got, want = through(lm.expert_ffn, cfg), through(reference.expert_ffn,
                                                     CONFIG)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        close(a, b, 1e-4)
    assert float(jnp.max(jnp.abs(got[0]["router_bias"]))) == 0.0


# -- the whole model --------------------------------------------------------
# Two Sinkhorn iterations here: the 20 of the published model are
# the mixer tests' above, and unrolled through four blocks and their
# backward they are most of these tests' compile time.
MODEL = {**CONFIG, "hc_sinkhorn_iters": 2}


@pytest.fixture(scope="module")
def model_cfg(cfg):
    return dataclasses.replace(cfg, hc_iters=2)


@pytest.fixture(scope="module")
def two_shards(params, reference):
    """(tokens (4, 16), the reference's mean loss over two data shards
    of two, its gradients)."""
    tokens = jax.random.randint(jax.random.PRNGKey(17), (4, 16), 0, 128)

    def mean_loss(p):
        return jnp.mean(jnp.stack([
            reference.loss(MODEL, p, {"tokens": tokens[i:i + 2]})
            for i in (0, 2)]))
    return (tokens, *jax.jit(jax.value_and_grad(mean_loss))(params))


@pytest.mark.parametrize("mtp", [True, False], ids=["mtp", "no-mtp"])
def test_loss_with_and_without_the_multi_token_module(model_cfg, params,
                                                      reference, mtp):
    tokens = jax.random.randint(jax.random.PRNGKey(15), (2, 16), 0, 128)
    conf = {**MODEL, "num_nextn_predict_layers": int(mtp)}
    got = jax.jit(lambda p, b: lm.loss_fn(
        dataclasses.replace(model_cfg, mtp=mtp), p, b))(params, {"tokens": tokens})
    close(got, jax.jit(lambda p, b: reference.loss(conf, p, b))(
        params, {"tokens": tokens}), 1e-5)


def test_multi_token_loss_alone(model_cfg, params, reference):
    """lambda = 1 minus lambda = 0 is the module's own loss."""
    tokens = jax.random.randint(jax.random.PRNGKey(16), (2, 16), 0, 128)

    def both(fn, make):
        return [jax.jit(lambda p, b, c=make(lam): fn(c, p, b))(
            params, {"tokens": tokens}) for lam in (0.0, 1.0)]
    got = both(lm.loss_fn,
               lambda lam: dataclasses.replace(model_cfg, mtp_lambda=lam))
    want = both(reference.loss, lambda lam: {**MODEL, "mtp_lambda": lam})
    close(got[1] - got[0], want[1] - want[0], 1e-5)
    assert float(got[1] - got[0]) > 1.0


def test_loss_and_gradients_through_build_train_step(model_cfg, params,
                                                     two_shards):
    """Two data shards through `build_train_step` with every layer
    checkpointed, as the cell runs it, and an optimizer that changes
    nothing and hands back the gradients; against the plain
    reference's mean over the shards."""
    conf = dataclasses.replace(model_cfg, remat=True)
    tokens, want, want_grads = two_shards
    mesh = Mesh(np.array(jax.devices()[:2]), axis_names=("data",))
    keep = optax.GradientTransformation(
        lambda p: jax.tree.map(jnp.zeros_like, p),
        lambda g, s, p=None: (jax.tree.map(jnp.zeros_like, g), g))
    step = build_train_step(
        lambda p, b: lm.loss_fn(conf, p, b), keep, mesh,
        batch_spec={"tokens": P("data")}, donate=False)
    _, grads, metrics = step(params, keep.init(params), {"tokens": tokens})

    close(metrics["loss"], want, 1e-5)
    flat, _ = jax.tree.flatten_with_path(grads)
    for (path, a), b in zip(flat, jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-6,
            err_msg=jax.tree_util.keystr(path))
    norm = float(optax.global_norm(grads))
    assert norm > 0.1


def test_parameter_count_of_the_published_share():
    """1,170 M parameters: the share of ISSUE 31's arithmetic."""
    from perfbench import run
    config = run.read_json(os.path.join(
        REPO, "perfbench", "configs", "xing4-29b-ep8.json"))
    made = _perfbench("models").library_config(config)
    shapes = jax.eval_shape(lambda k: lm.init_params(made, k),
                            jax.random.PRNGKey(0))
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert round(count / 1e6) == 1170
    assert shapes["layers"]["w_gate"].shape == (6, 8, 3584, 1024)
    assert shapes["layers"]["router"].shape == (6, 3584, 64)
    assert shapes["head"].shape == (3584, 16384)
