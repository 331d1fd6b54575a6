"""`models/window_moe.py` (sliding-window and full attention mixed
inside a scanned period, gated grouped-query heads with q / k norms,
a norm before and after each sub-layer, sparse experts with a shared
one) against the plain reference of `perfbench/reference/afmoe.py`, on
seeded weights at tiny widths that keep the published model's ratios:
a group of 6 q heads a kv head, three windowed layers to one full,
a window a quarter of the sequence, 4 experts chosen of a router 8
times as wide as the share, one leading dense layer."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.models import latent_moe, window_moe as wm
from horovod_tpu.parallel import build_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLIDING, FULL = "sliding_attention", "full_attention"

# the published config.json's keys at tiny widths, as one share holds
# them: two periods after the dense layer, so that the scan runs twice
CONFIG = {
    "global_attn_every_n_layers": 4, "head_dim": 8, "hidden_size": 32,
    "intermediate_size": 64, "layer_types": ([SLIDING] * 3 + [FULL]) * 3,
    "moe_intermediate_size": 16, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 6, "num_dense_layers": 1, "num_experts": 2,
    "num_experts_per_tok": 4, "num_hidden_layers": 9,
    "num_key_value_heads": 1, "num_shared_experts": 1,
    "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 10000,
    "route_norm": True, "route_scale": 2.448, "score_func": "sigmoid",
    "sliding_window": 16, "tie_word_embeddings": False, "topk_group": 1,
    "vocab_size": 128, "dense_columns_held": 16, "shared_columns_held": 4,
    "experts_first": 0, "initializer_range": 0.02,
    "published": {"num_experts": 64}}
# the layer whole: 4 head shares, 4 column shares, 32 expert shares
UNCUT = {**CONFIG, "num_attention_heads": 24, "num_key_value_heads": 4,
         "dense_columns_held": 64, "shared_columns_held": 16,
         "num_experts": 64}


def _perfbench(kind):
    from perfbench import run
    return run.load_module(os.path.join(REPO, "perfbench"), kind, "afmoe")


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
    one show where a gain is applied, a wide router bias flips
    choices."""
    params = wm.init_params(cfg, jax.random.PRNGKey(seed))
    leaves, tree = jax.tree.flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))

    def made(key, path, a):
        name = jax.tree_util.keystr(path[-1:])
        noise = jax.random.normal(key, a.shape, a.dtype)
        if "norm" in name:
            return 1.0 + 0.3 * noise
        if "router_bias" in name:
            return 0.05 * noise
        return 0.15 * noise if a.ndim >= 2 else a
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


# -- the configuration ------------------------------------------------------

def test_the_period_is_read_from_the_layer_kinds(cfg):
    assert cfg.layer_kinds == (("window",) * 3 + ("full",)) * 2 + ("window",)
    assert cfg.period_kinds == ("window", "window", "full", "window")
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.window) == (6, 1, 16)
    assert cfg.embed_scale == pytest.approx(32 ** 0.5)


@pytest.mark.parametrize("change", [
    dict(layer_kinds=("window", "full", "window")),       # no whole period
    dict(layer_kinds=("window",) + ("window", "full") * 2 + ("full",) * 4),
    dict(layer_kinds=("window", "local", "full", "full", "full")),
    dict(n_dense_layers=5)])
def test_a_stack_that_is_no_whole_periods_is_refused(change):
    with pytest.raises(ValueError, match="whole periods"):
        wm.WindowMoEConfig(**change)


# -- the sub-layers ---------------------------------------------------------

@pytest.mark.parametrize("kind,name", [("window", SLIDING), ("full", FULL)])
def test_gated_attention(cfg, layer, reference, kind, name):
    x = activations(10, 2, 64, 32)
    close(wm.attention_sum(cfg, layer, x, kind),
          reference.attention_sum(CONFIG, layer, x, name))
    close(wm.gated_attention(cfg, layer, x, kind),
          reference.attention(CONFIG, layer, x, name))


def test_the_kinds_differ_in_window_and_rope(cfg, layer, reference):
    """Neither kind is the other with one of its two differences
    taken away: a full layer with rope, a windowed one without its
    window."""
    x = activations(10, 2, 64, 32)
    full = wm.attention_sum(cfg, layer, x, "full")
    windowed = wm.attention_sum(cfg, layer, x, "window")
    no_window = wm.attention_sum(
        dataclasses.replace(cfg, window=64), layer, x, "window")
    for a, b in ((full, windowed), (full, no_window), (windowed, no_window)):
        assert float(jnp.max(jnp.abs(a - b))) > 1e-2
    # the first `window` queries see the same keys with or without it
    close(windowed[:, :16], no_window[:, :16])


def test_the_gate_reads_the_normed_input(cfg, layer):
    """sigmoid(u W_g) with W_g = 0 is one half everywhere: twice that
    is the attention without a gate."""
    x = activations(10, 2, 64, 32)
    open_gate = {**layer, "wg": jnp.zeros_like(layer["wg"])}
    ungated = 2.0 * wm.attention_sum(cfg, open_gate, x, "full")
    gated = wm.attention_sum(cfg, layer, x, "full")
    assert float(jnp.max(jnp.abs(ungated - gated))) > 1e-2
    doubled = {**layer, "attn_norm": 2.0 * layer["attn_norm"],
               "wg": 0.5 * layer["wg"]}
    # q and k are normed again, v doubles, the gate is unchanged
    close(wm.attention_sum(cfg, doubled, x, "full"), 2.0 * gated)


def test_dense_block(cfg, params, reference):
    dense = jax.tree.map(lambda a: a[0], params["dense"])
    x = activations(11, 2, 64, 32)
    close(wm.dense_block(cfg, dense, x, "window"),
          reference.layer(CONFIG, SLIDING, True, dense, x))


@pytest.mark.parametrize("kind,name", [("window", SLIDING), ("full", FULL)])
def test_expert_block(cfg, layer, reference, kind, name):
    x = activations(12, 2, 64, 32)
    close(wm.expert_block(cfg, layer, x, kind),
          reference.layer(CONFIG, name, False, layer, x))


@pytest.mark.parametrize("tile", [8, 128, 512])
def test_the_dispatch_tile_changes_no_output_and_no_gradient(
        cfg, layer, reference, tile):
    """Tiles below, near and far above the rows an expert gets (128
    tokens x 4 choices over a router of 64)."""
    x = activations(12, 2, 64, 32)
    tiled = dataclasses.replace(cfg, dispatch_tile=tile)
    close(wm.expert_block(tiled, layer, x, "window"),
          reference.layer(CONFIG, SLIDING, False, layer, x))

    def energy(c):
        return lambda p, x: jnp.sum(wm.expert_block(c, p, x, "window") ** 2)
    got = jax.grad(energy(tiled), argnums=(0, 1))(layer, x)
    want = jax.grad(energy(cfg), argnums=(0, 1))(layer, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        close(g, w)


def test_expert_ffn_with_a_bias_that_flips_a_choice(cfg, layer, reference):
    x = activations(13, 2, 64, 32)
    m = wm.rmsnorm(x, layer["mlp_norm"], 1e-5)
    scores = jax.nn.sigmoid(m.reshape(-1, 32) @ layer["router"])
    with_bias = jax.lax.top_k(scores + layer["router_bias"], 4)[1]
    without = jax.lax.top_k(scores, 4)[1]
    assert bool(jnp.any(jnp.sort(with_bias) != jnp.sort(without)))
    got = latent_moe.expert_ffn(cfg, layer, x)
    close(got, reference.expert_ffn(CONFIG, layer, m))
    no_bias = {**layer, "router_bias": jnp.zeros_like(layer["router_bias"])}
    assert float(jnp.max(jnp.abs(
        got - latent_moe.expert_ffn(cfg, no_bias, x)))) > 1e-3


def test_each_post_norm_is_there(cfg, params, layer):
    """A sub-layer's output is normalised before it joins the residual
    stream: a W_o or a down-projection three times as large changes
    nothing but what `rms_norm_eps` weighs at these widths."""
    x = activations(12, 2, 64, 32)
    dense = jax.tree.map(lambda a: a[0], params["dense"])
    for block, w, name in ((wm.gated_attention, layer, "wo"),
                           (wm.dense_block, dense, "w_down")):
        scaled = {**w, name: 3.0 * w[name]}
        close(block(cfg, scaled, x, "full"), block(cfg, w, x, "full"), 2e-2)


# -- the share --------------------------------------------------------------

@pytest.fixture(scope="module")
def whole():
    """One uncut expert layer and one uncut dense layer."""
    uncut = jax.jit(lambda: _seeded(_library(UNCUT), 5))()
    return (jax.tree.map(lambda a: a[0], uncut["layers"]),
            jax.tree.map(lambda a: a[0], uncut["dense"]))


def _columns(w, names, axis, lo, hi):
    """`names` of `w` cut to [lo, hi) along `axis` (-1: columns, 0:
    rows)."""
    cut = (slice(lo, hi),) if axis == 0 else (Ellipsis, slice(lo, hi))
    return {n: w[n][cut] for n in names}


@pytest.mark.parametrize("kind,name", [("window", SLIDING), ("full", FULL)])
def test_the_four_head_shares_add_up_to_the_uncut_attention(
        cfg, reference, whole, kind, name):
    """model-configs guide, section 4: what the 4 chips of a
    tensor-parallel group compute after W_o (6 q heads and their kv
    head each) adds up to the uncut layer's sum before its
    post-norm."""
    layer, _ = whole
    x = activations(14, 2, 64, 32)
    uncut = reference.attention_sum(UNCUT, layer, x, name)
    total = jnp.zeros_like(x)
    for chip in range(4):
        q, kv = slice(48 * chip, 48 * chip + 48), slice(8 * chip, 8 * chip + 8)
        share = {**layer, "wq": layer["wq"][:, q], "wg": layer["wg"][:, q],
                 "wo": layer["wo"][q], "wk": layer["wk"][:, kv],
                 "wv": layer["wv"][:, kv]}
        part = wm.attention_sum(cfg, share, x, kind)
        close(part, reference.attention_sum(CONFIG, share, x, name))
        total = total + part
    close(total, uncut)
    assert float(jnp.max(jnp.abs(uncut - part))) > 1e-2


def test_the_expert_and_column_shares_add_up_to_the_uncut_ffn(
        cfg, reference, whole):
    """The 32 expert shares (2 of 64 experts each) plus the shared
    expert, its four column shares counted once (every data-parallel
    group computes it alike), add up to the uncut FFN before its
    post-norm."""
    layer, _ = whole
    x = activations(15, 2, 64, 32)
    m = wm.rmsnorm(x, layer["mlp_norm"], 1e-5)
    uncut = reference.expert_ffn(UNCUT, layer, m)
    total = jnp.zeros_like(x)
    for chip in range(32):
        t = chip % 4                      # its place in its group of 4
        share = {
            **layer,
            **{n: layer[n][2 * chip:2 * chip + 2]
               for n in ("w_gate", "w_up", "w_down")},
            **_columns(layer, ("s_gate", "s_up"), -1, 4 * t, 4 * t + 4),
            **_columns(layer, ("s_down",), 0, 4 * t, 4 * t + 4)}
        share_cfg = dataclasses.replace(cfg, experts_first=2 * chip)
        first_group = chip < 4
        part = latent_moe.expert_ffn(share_cfg, share, x,
                                     shared=first_group)
        close(part, reference.expert_ffn(
            {**CONFIG, "experts_first": 2 * chip}, share, m,
            shared=first_group))
        total = total + part
    close(total, uncut)
    assert float(jnp.max(jnp.abs(uncut - part))) > 1e-3


def test_the_four_column_shares_add_up_to_the_uncut_dense_ffn(cfg, whole):
    _, dense = whole
    x = activations(16, 2, 64, 32)
    uncut = latent_moe.dense_ffn(cfg, dense, x)
    parts = [latent_moe.dense_ffn(cfg, {
        **dense,
        **_columns(dense, ("w_gate", "w_up"), -1, 16 * t, 16 * t + 16),
        **_columns(dense, ("w_down",), 0, 16 * t, 16 * t + 16)}, x)
        for t in range(4)]
    close(sum(parts), uncut)
    assert float(jnp.max(jnp.abs(uncut - parts[0]))) > 1e-3


# -- the model --------------------------------------------------------------

@pytest.fixture(scope="module")
def two_shards(params, reference):
    """(tokens (4, 64), the reference's mean loss over two data shards
    of two, its gradients)."""
    tokens = jax.random.randint(jax.random.PRNGKey(17), (4, 64), 0, 128)

    def mean_loss(p):
        return jnp.mean(jnp.stack([
            reference.loss(CONFIG, p, {"tokens": tokens[i:i + 2]})
            for i in (0, 2)]))
    return (tokens, *jax.jit(jax.value_and_grad(mean_loss))(params))


def test_hidden_states_over_two_periods(cfg, params, reference):
    tokens = jax.random.randint(jax.random.PRNGKey(15), (2, 64), 0, 128)
    got = jax.jit(lambda p, t: wm.forward(cfg, p, t))(params, tokens)
    close(got, jax.jit(lambda p, t: reference.hidden_states(CONFIG, p, t))(
        params, tokens), 1e-4)


def test_the_reference_in_blocks_is_the_reference_whole(
        monkeypatch, params, reference, two_shards):
    """Blocks of queries and of tokens change no arithmetic."""
    tokens, want, _ = two_shards
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)
    monkeypatch.setattr(reference, "TOKEN_BLOCK", 32)
    got = jnp.mean(jnp.stack([
        reference.loss(CONFIG, params, {"tokens": tokens[i:i + 2]})
        for i in (0, 2)]))
    close(got, want, 1e-6)


def test_loss_and_gradients_through_build_train_step(cfg, params,
                                                     two_shards):
    """Two data shards through `build_train_step` with every layer
    checkpointed and the period scanned twice, as the cell runs it,
    and an optimizer that changes nothing and hands back the
    gradients; against the plain reference's mean over the shards."""
    conf = dataclasses.replace(cfg, remat=True)
    tokens, want, want_grads = two_shards
    mesh = Mesh(np.array(jax.devices()[:2]), axis_names=("data",))
    keep = optax.GradientTransformation(
        lambda p: jax.tree.map(jnp.zeros_like, p),
        lambda g, s, p=None: (jax.tree.map(jnp.zeros_like, g), g))
    step = build_train_step(
        lambda p, b: wm.loss_fn(conf, p, b), keep, mesh,
        batch_spec={"tokens": P("data")}, donate=False)
    _, grads, metrics = step(params, keep.init(params), {"tokens": tokens})

    close(metrics["loss"], want, 1e-5)
    flat, _ = jax.tree.flatten_with_path(grads)
    for (path, a), b in zip(flat, jax.tree.leaves(want_grads)):
        assert a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-6,
            err_msg=jax.tree_util.keystr(path))
    assert float(optax.global_norm(grads)) > 0.1
    # every layer of both periods got its own gradient
    per_layer = jnp.sqrt(jnp.sum(jnp.square(grads["layers"]["wg"]),
                                 axis=(1, 2)))
    assert per_layer.shape == (8,) and float(jnp.min(per_layer)) > 1e-4


def test_windowed_layers_trace_the_windowed_path(cfg, params):
    """Six of the nine layers' cores are traced with a window (one
    dense layer and the period's three, traced once for the scan), the
    period's full layer without; `hvd.attn.window` names the former
    in the lowered program."""
    from horovod_tpu.metrics import snapshot

    def traces():
        snap = snapshot().get("hvd_attention_traces_total", {})
        return {p: snap.get((p,), 0.0) for p in ("dense", "dense_window")}
    tokens = jnp.zeros((1, 64), jnp.int32)
    before = traces()
    lowered = jax.jit(lambda p, t: wm.forward(cfg, p, t)).lower(params,
                                                                tokens)
    after = traces()
    assert {p: after[p] - before[p] for p in after} == {
        "dense_window": 4.0, "dense": 1.0}
    text = lowered.as_text(debug_info=True)
    assert "hvd.attn.window" in text and "hvd.attn.core" in text


def test_parameter_count_of_the_published_share():
    """1,198.2 M parameters: the share of ISSUE 35's arithmetic."""
    from perfbench import run
    config = run.read_json(os.path.join(
        REPO, "perfbench", "configs", "trinity-large-ep32tp4.json"))
    made = _perfbench("models").library_config(config)
    shapes = jax.eval_shape(lambda k: wm.init_params(made, k),
                            jax.random.PRNGKey(0))
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert round(count / 1e6, 1) == 1198.2
    assert shapes["layers"]["w_gate"].shape == (4, 8, 3072, 3072)
    assert shapes["layers"]["router"].shape == (4, 3072, 256)
    assert shapes["layers"]["s_gate"].shape == (4, 3072, 768)
    assert shapes["layers"]["wq"].shape == (4, 3072, 1536)
    assert shapes["layers"]["wk"].shape == (4, 3072, 256)
    assert shapes["dense"]["w_gate"].shape == (1, 3072, 3072)
    assert shapes["head"].shape == (3072, 25024)
    assert made.period_kinds == ("window", "window", "full", "window")
