"""`models/jamba.py` (Mamba-1 selective-scan mixers with an attention
layer among them, scanned in periods) and its core,
`parallel/selective_scan.py`: both scan paths against the recurrence a
position at a time, in the forward pass and in all seven gradients; the
layer order of the published configuration; the two tensor-parallel
shares adding up to the uncut layer; the model against the plain
reference of `perfbench/reference/jamba.py` at tiny widths."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import jamba
from horovod_tpu.parallel import build_train_step
from horovod_tpu.parallel import selective_scan as ss

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the published config.json's keys at tiny widths, as one share holds
# them: two periods of four, attention at index 2 of each
CONFIG = {
    "attn_layer_offset": 2, "attn_layer_period": 4,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 8, "mamba_expand": 2, "mamba_proj_bias": False,
    "num_attention_heads": 2, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 8, "num_key_value_heads": 1,
    "rms_norm_eps": 1e-6, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 128,
    "mamba_channels_held": 64, "ffn_columns_held": 64,
    "initializer_range": 0.02, "published": {"num_attention_heads": 4},
    "dtype": "float32"}
# the layer whole: 2 channel shares, 2 head shares, 2 column shares
UNCUT = {**CONFIG, "num_attention_heads": 4, "mamba_channels_held": 128,
         "ffn_columns_held": 128}


def _perfbench(kind):
    from perfbench import run
    return run.load_module(os.path.join(REPO, "perfbench"), kind, "jamba")


def _scan_inputs(Bt, L, C, N=16, seed=0):
    """delta small (0.01 to 0.1), so that the state reaches across
    chunk borders."""
    k = jax.random.split(jax.random.PRNGKey(seed), 8)

    def normal(i, *shape):
        return jax.random.normal(k[i], shape, jnp.float32)
    delta = jax.nn.softplus(normal(1, Bt, L, C) * 0.5 - 3.5)
    A = -jnp.arange(1, N + 1, dtype=jnp.float32) * jnp.exp(
        0.3 * normal(2, C, N))
    return ((normal(0, Bt, L, C), delta, A, normal(3, Bt, L, N),
             normal(4, Bt, L, N), normal(5, C), normal(6, Bt, L, C)),
            normal(7, Bt, L, C))


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(1.0, float(np.max(np.abs(want))))
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * scale)


@pytest.mark.parametrize("kernels, Bt, L, C, chunk, rows_cap", [
    (False, 2, 100, 64, 32, None),        # 4 chunks, the last part-filled
    (False, 1, 96, 48, 16, None),         # 6 chunks
    (True, 2, 128, 256, 32, None),        # 4 chunks, 3 borders
    (True, 1, 64, 2048, 16, 8),           # 2 channel blocks of 8 rows
], ids=["chunks-ragged", "chunks-6", "kernel", "kernel-2-blocks"])
def test_scan_paths_match_the_loop(monkeypatch, kernels, Bt, L, C, chunk,
                                   rows_cap):
    """Forward and every gradient to 2e-5 of the recurrence written out
    a position at a time, in float32; the kernels in Pallas's
    interpreter."""
    monkeypatch.setattr(ss, "CHUNK", chunk)
    if rows_cap:
        monkeypatch.setattr(ss, "ROWS_CAP", rows_cap)
        assert ss._block_rows(C // 128) == rows_cap
    args, w = _scan_inputs(Bt, L, C)

    def scan(*a):
        return ss.selective_scan(*a, kernels=kernels, interpret=True)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * w)
    _close(scan(*args), ss.recurrent_selective_scan(*args))
    got = jax.grad(loss(scan), argnums=tuple(range(7)))(*args)
    want = jax.grad(loss(ss.recurrent_selective_scan),
                    argnums=tuple(range(7)))(*args)
    for name, g, h in zip(("x", "delta", "A", "B", "C", "D", "z"), got,
                          want):
        assert g.shape == h.shape, name
        _close(g, h)


def test_the_state_is_carried_across_chunks():
    """The inputs above reach across chunk borders: a scan that
    restarted from zero at each chunk would be far off."""
    args, _ = _scan_inputs(1, 128, 64)
    args = (*args[:5], args[5] * 0)         # the scan alone, no skip or gate
    whole = ss.recurrent_selective_scan(*args)
    pieces = jnp.concatenate([ss.recurrent_selective_scan(
        *(a[:, i:i + 32] if a.ndim == 3 else a for a in args))
        for i in range(0, 128, 32)], axis=1)
    assert float(jnp.max(jnp.abs(whole - pieces))) > 0.1 * float(
        jnp.max(jnp.abs(whole)))


def test_path_rule_and_counter():
    x = jnp.zeros((1, 64, 256), jnp.bfloat16)
    assert ss.supported(x.shape, 32) and not ss.supported((1, 60, 256), 32)
    assert not ss.supported((1, 64, 200), 32)
    assert not ss.kernels_engage(x, 32)         # the CPU
    args, _ = _scan_inputs(1, 32, 128)

    def count(path):
        return hvd.metrics().get("hvd_selective_scan_traces_total",
                                 {}).get((path,), 0)
    before = count("chunks"), count("kernel")
    ss.selective_scan(*args)
    ss.selective_scan(*args, kernels=True, interpret=True)
    assert (count("chunks"), count("kernel")) == (before[0] + 1,
                                                  before[1] + 1)
    with pytest.raises(ValueError, match="selective scan takes"):
        ss.selective_scan(*args[:5], args[5][:3])


def test_layer_order_of_the_published_configuration():
    """Attention where i % 14 == 7: layers 7 and 21 of 28; the model
    file, the benchmark's model and its reference agree."""
    kinds = jamba.layer_kinds(28, 14, 7)
    assert [i for i, k in enumerate(kinds) if k == jamba.ATTN] == [7, 21]
    assert kinds.count(jamba.MAMBA) == 26
    published = {**CONFIG, "num_hidden_layers": 28, "attn_layer_period": 14,
                 "attn_layer_offset": 7}
    assert _perfbench("models").layer_kinds(published) == list(kinds)
    assert _perfbench("reference").layer_types(published) == list(kinds)
    cfg = jamba.JambaConfig(layer_kinds=kinds, period=14)
    assert cfg.period_kinds == kinds[:14]
    with pytest.raises(ValueError, match="whole periods"):
        jamba.JambaConfig(layer_kinds=kinds[:20], period=14)


def _layer(config, kind, seed=0):
    cfg = _perfbench("models").library_config(config)
    params = jamba.init_params(cfg, jax.random.PRNGKey(seed))
    # random conv bias, norm gains and D, so that each is seen
    layer = jax.tree.map(lambda a: a[0], params[kind])
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))
    for name in ("conv_b", "D", "input_norm", "mlp_norm", "dt_norm",
                 "b_norm", "c_norm"):
        if name in layer:
            layer[name] = layer[name] + 0.3 * jax.random.normal(
                next(keys), layer[name].shape)
    return cfg, layer


def _share(layer, s, channels, heads_cols, ffn_cols):
    """Share s of two of an uncut layer's weights."""
    def cols(a, n, first=0):
        return a[..., first + s * n:first + (s + 1) * n]
    out = dict(layer)
    if "in_proj" in layer:
        full = layer["in_proj"].shape[-1] // 2
        out["in_proj"] = jnp.concatenate(
            [cols(layer["in_proj"], channels),
             cols(layer["in_proj"], channels, full)], axis=-1)
        for name in ("conv_w", "conv_b", "dt_proj", "dt_bias", "D"):
            out[name] = cols(layer[name], channels)
        out["A_log"] = layer["A_log"][s * channels:(s + 1) * channels]
        for name in ("x_proj", "out_proj"):
            out[name] = layer[name][s * channels:(s + 1) * channels]
    if "wq" in layer:
        out["wq"] = cols(layer["wq"], heads_cols)
        out["wo"] = layer["wo"][s * heads_cols:(s + 1) * heads_cols]
    out["w_gate"], out["w_up"] = (cols(layer[n], ffn_cols)
                                  for n in ("w_gate", "w_up"))
    out["w_down"] = layer["w_down"][s * ffn_cols:(s + 1) * ffn_cols]
    return out


@pytest.mark.parametrize("kind", [jamba.MAMBA, jamba.ATTN])
def test_tensor_parallel_shares_add_up(kind):
    """Two shares of a layer, with the one exchange inside the Mamba
    mixer (the sum of x W_x over the shares) made between them: their
    sums after W_out / W_o and after the down-projection add up to the
    uncut layer's. Without that exchange each share's dt, B and C are
    its own channels' partial sum (the configuration's cut), which is
    not the uncut layer's."""
    cfg_full, full = _layer(UNCUT, kind)
    cfg, _ = _layer(CONFIG, kind)
    shares = [_share(full, s, 64, 32, 64) for s in (0, 1)]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 48, 64))
    if kind == jamba.MAMBA:
        want = jamba.mamba_sum(cfg_full, full, x)
        inputs = [jamba.ssm_inputs(cfg, p, x) for p in shares]
        summed = inputs[0][2] + inputs[1][2]
        got = sum(jamba.ssm_outputs(cfg, p, xs, z, summed)
                  for p, (xs, z, _) in zip(shares, inputs))
        alone = sum(jamba.mamba_sum(cfg, p, x) for p in shares)
        assert float(jnp.max(jnp.abs(alone - want))) > 1e-3
    else:
        want = jamba.attention_sum(cfg_full, full, x)
        got = sum(jamba.attention_sum(cfg, p, x) for p in shares)
    _close(got, want, 1e-5)
    h = x + want
    _close(sum(jamba.latent_moe.dense_ffn(cfg, p, h) for p in shares),
           jamba.latent_moe.dense_ffn(cfg_full, full, h), 1e-5)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_loss_and_gradients_match_the_reference(remat):
    """The stack (two periods, so the scan over periods runs twice and
    each run of one kind is a scan of its own) against the plain
    reference, in float32."""
    import dataclasses
    reference = _perfbench("reference")
    cfg = dataclasses.replace(_perfbench("models").library_config(CONFIG),
                              remat=remat)
    assert cfg.period_kinds == ("mamba", "mamba", "attention", "mamba")
    # the benchmark's initialisers (small delta), matrices at 0.15
    config = {**CONFIG, "initializer_range": 0.15}
    params, _ = _perfbench("models").build(config, {
        "seq": 96, "batch_per_chip": 2, "sample": {"per_chip": 2,
                                                   "seq": 96}},
        1).init(jax.random.PRNGKey(3))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(4), (2, 96), 0,
                                          CONFIG["vocab_size"])}
    loss, grads = jax.value_and_grad(
        lambda p: jamba.loss_fn(cfg, p, batch))(params)
    want, want_grads = jax.value_and_grad(
        lambda p: reference.loss(config, p, batch))(params)
    assert abs(float(loss) - float(want)) < 2e-6 * abs(float(want))
    for (path, g), h in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.max(jnp.abs(g))) > 0, path
        _close(g, h, 1e-4)


def test_train_step_on_a_data_mesh_lowers_the_loss():
    """Two devices, the batch split over them: a few AdamW steps
    through `build_train_step` take the loss down."""
    cfg = _perfbench("models").library_config(CONFIG)
    params = jamba.init_params(cfg, jax.random.PRNGKey(0))
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    tx = optax.adamw(1e-2)
    step = build_train_step(lambda p, b: jamba.loss_fn(cfg, p, b), tx,
                            mesh, batch_spec={"tokens": P("data")},
                            donate=False)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                                          CONFIG["vocab_size"])}
    state = tx.init(params)
    losses = []
    for _ in range(4):
        params, state, metrics = step(params, state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
