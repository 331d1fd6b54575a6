"""The `hvd.*` device scopes (tracing.DEVICE_SCOPES): every scope a
path runs reaches the compiled program's `op_name`s, the layer scopes
with their backward and recompute variants; every gradient all-reduce
of the bucketed step carries exactly one bucket's name; the names add
nothing to the lowered text; `aot_compile` counts its two halves."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu import tracing
from horovod_tpu.metrics import snapshot
from horovod_tpu.models import latent_moe, resnet, transformer
from horovod_tpu.parallel.aot import aot_compile
from horovod_tpu.parallel.train import build_train_step, plan_overlap

OP_NAME = re.compile(r'op_name="([^"]*)"')
SCOPE = re.compile(r"hvd\.[a-z0-9_.]+")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(")
BACKWARD, RECOMPUTE = "transpose(", "rematted_computation"


def op_names(compiled):
    return set(OP_NAME.findall(compiled.as_text()))


def small_config(**kw):
    return transformer.TransformerConfig(**{**dict(
        vocab=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=8, d_ff=64, max_seq=16, tp_axis=None, sp_axis=None,
        ep_axis=None, dtype=jnp.float32), **kw})


def variants(names, scope):
    """Which of forward / backward / recompute the names show for one
    scope."""
    mine = [n for n in names if scope in SCOPE.findall(n)]
    return {"recompute" if RECOMPUTE in n else
            "backward" if BACKWARD in n else "forward" for n in mine}


@pytest.fixture(scope="module")
def transformer_step():
    """A small remat'd, scanned transformer on a 4-device data mesh,
    with a threshold that gives several buckets."""
    cfg = small_config(remat=True)
    mesh = Mesh(np.array(jax.devices()[:4]), axis_names=("data",))
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tx = optax.adamw(1e-3)
    kwargs = dict(batch_spec=P("data"), donate=False,
                  overlap_threshold=8192)
    step = build_train_step(
        lambda p, b: transformer.loss_fn(cfg, p, b), tx, mesh, **kwargs)
    tokens = jnp.zeros((8, 16), jnp.int32)
    args = (params, tx.init(params),
            {"tokens": tokens, "targets": tokens})
    plan = plan_overlap(params, mesh, overlap_threshold=8192)
    return step, args, plan


@pytest.fixture(scope="module")
def transformer_names(transformer_step):
    step, args, _ = transformer_step
    return op_names(step.lower(*args).compile())


@pytest.fixture(scope="module")
def resnet_names():
    """One bottleneck stage of ResNet through `build_train_step`."""
    model = resnet.ResNet(stage_sizes=[1], num_classes=10, num_filters=8,
                          dtype=jnp.float32)
    variables = resnet.init_resnet(model, jax.random.PRNGKey(0), 16)
    mesh = Mesh(np.array(jax.devices()[:1]), axis_names=("data",))
    tx = optax.sgd(0.1)

    def loss(params, batch):
        return resnet.resnet_loss_fn(
            model, {"params": params, "batch_stats": batch["stats"]},
            batch)
    step = build_train_step(loss, tx, mesh, loss_has_aux=True,
                            donate=False, batch_spec={
                                "images": P("data"), "labels": P("data"),
                                "stats": P()})
    batch = {"images": jnp.zeros((2, 16, 16, 3)),
             "labels": jnp.zeros((2,), jnp.int32),
             "stats": variables["batch_stats"]}
    params = variables["params"]
    return op_names(step.lower(params, tx.init(params), batch).compile())


@pytest.mark.parametrize("scope, want", [
    ("hvd.embed", {"forward", "backward"}),
    ("hvd.attn.proj", {"forward", "backward", "recompute"}),
    ("hvd.attn.core", {"forward", "backward", "recompute"}),
    ("hvd.ffn", {"forward", "backward", "recompute"}),
    ("hvd.head_loss", {"forward", "backward"}),
    ("hvd.optimizer", {"forward"}),
])
def test_transformer_scopes_reach_the_compiled_program(
        transformer_names, scope, want):
    assert want <= variants(transformer_names, scope)


@pytest.mark.parametrize("scope, want", [
    ("hvd.conv", {"forward", "backward"}),
    ("hvd.batchnorm", {"forward", "backward"}),
    ("hvd.head_loss", {"forward", "backward"}),
    ("hvd.optimizer", {"forward"}),
])
def test_resnet_scopes_reach_the_compiled_program(resnet_names, scope,
                                                  want):
    assert want <= variants(resnet_names, scope)


@pytest.fixture(scope="module")
def latent_moe_names():
    """The latent-attention, sparse-expert, multi-stream model with
    every layer checkpointed, through `build_train_step`."""
    cfg = latent_moe.LatentMoEConfig(
        vocab=64, d_model=16, n_expert_layers=1, n_heads=2, qk_nope_dim=4,
        qk_rope_dim=4, v_head_dim=4, q_rank=8, kv_rank=8, d_ff_dense=32,
        d_ff_expert=8, n_experts=8, experts_held=2, top_k=2, hc_mult=2,
        hc_iters=2, mtp=True, dtype=jnp.float32, remat=True)
    mesh = Mesh(np.array(jax.devices()[:1]), axis_names=("data",))
    params = latent_moe.init_params(cfg, jax.random.PRNGKey(0))
    tx = optax.sgd(0.1)
    step = build_train_step(
        lambda p, b: latent_moe.loss_fn(cfg, p, b), tx, mesh,
        batch_spec={"tokens": P("data")}, donate=False)
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32)}
    return op_names(step.lower(params, tx.init(params), batch).compile())


@pytest.mark.parametrize("scope, want", [
    ("hvd.hc", {"forward", "backward", "recompute"}),
    ("hvd.moe.route", {"forward", "backward", "recompute"}),
    ("hvd.moe.experts", {"forward", "backward", "recompute"}),
    ("hvd.moe.shared", {"forward", "backward", "recompute"}),
    ("hvd.mtp", {"forward", "backward"}),
    ("hvd.attn.proj", {"forward", "backward", "recompute"}),
    ("hvd.attn.core", {"forward", "backward", "recompute"}),
    ("hvd.ffn", {"forward", "backward", "recompute"}),
    ("hvd.embed", {"forward", "backward"}),
    ("hvd.head_loss", {"forward", "backward"}),
])
def test_latent_moe_scopes_reach_the_compiled_program(latent_moe_names,
                                                      scope, want):
    assert want <= variants(latent_moe_names, scope)
    assert not variants(latent_moe_names, "hvd.moe")


@pytest.fixture(scope="module")
def jamba_names():
    """Mamba mixers and an attention layer in one period, every layer
    checkpointed, through `build_train_step`."""
    from horovod_tpu.models import jamba
    cfg = jamba.JambaConfig(vocab=64, d_model=32, channels=64, dt_rank=4,
                            d_ff=32, n_heads=2, head_dim=16,
                            dtype=jnp.float32, remat=True)
    mesh = Mesh(np.array(jax.devices()[:1]), axis_names=("data",))
    params = jamba.init_params(cfg, jax.random.PRNGKey(0))
    tx = optax.sgd(0.1)
    step = build_train_step(
        lambda p, b: jamba.loss_fn(cfg, p, b), tx, mesh,
        batch_spec={"tokens": P("data")}, donate=False)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32)}
    return op_names(step.lower(params, tx.init(params), batch).compile())


@pytest.mark.parametrize("scope, want", [
    ("hvd.ssm.proj", {"forward", "backward", "recompute"}),
    ("hvd.ssm.scan", {"forward", "backward", "recompute"}),
    ("hvd.attn.proj", {"forward", "backward", "recompute"}),
    ("hvd.attn.core", {"forward", "backward", "recompute"}),
    ("hvd.ffn", {"forward", "backward", "recompute"}),
    ("hvd.embed", {"forward", "backward"}),
    ("hvd.head_loss", {"forward", "backward"}),
])
def test_jamba_scopes_reach_the_compiled_program(jamba_names, scope, want):
    assert want <= variants(jamba_names, scope)
    found = {s for n in jamba_names for s in SCOPE.findall(n)}
    assert found <= set(tracing.DEVICE_SCOPES)


@pytest.fixture(scope="module")
def mellum_names():
    """Mellum 2's layer (`window_moe` with no gate, no post-norm, no
    shared expert, plain rope and YaRN, a softmax router) over one
    period, every layer checkpointed, through `build_train_step`."""
    from horovod_tpu.models import window_moe as wm
    cfg = wm.WindowMoEConfig(
        vocab=64, d_model=16, layer_kinds=("window",) * 3 + ("full",),
        n_dense_layers=0, window=4, n_heads=4, n_kv_heads=1, head_dim=8,
        d_ff_expert=8, d_ff_shared=0, n_experts=8, experts_held=4,
        top_k=4, router="softmax", output_gate=False, post_norms=False,
        rope_theta=1000.0, full_rope=wm.Rope(
            1000.0, factor=4.0, original_len=8, attention_factor=1.1),
        embed_scale=1.0, dtype=jnp.float32, remat=True)
    mesh = Mesh(np.array(jax.devices()[:1]), axis_names=("data",))
    params = wm.init_params(cfg, jax.random.PRNGKey(0))
    tx = optax.sgd(0.1)
    step = build_train_step(
        lambda p, b: wm.loss_fn(cfg, p, b), tx, mesh,
        batch_spec={"tokens": P("data")}, donate=False)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32)}
    return op_names(step.lower(params, tx.init(params), batch).compile())


@pytest.mark.parametrize("scope, want", [
    ("hvd.attn.proj", {"forward", "backward", "recompute"}),
    ("hvd.attn.window", {"forward", "backward", "recompute"}),
    ("hvd.attn.core", {"forward", "backward", "recompute"}),
    ("hvd.moe.route", {"forward", "backward", "recompute"}),
    ("hvd.moe.experts", {"forward", "backward", "recompute"}),
    ("hvd.embed", {"forward", "backward"}),
    ("hvd.head_loss", {"forward", "backward"}),
    ("hvd.moe.shared", set()),
    ("hvd.ffn", set()),
])
def test_mellum_scopes_reach_the_compiled_program(mellum_names, scope, want):
    """Every scope the layer runs, in all its passes; none of the
    shared expert or of a dense FFN, which the family has not."""
    got = variants(mellum_names, scope)
    assert want <= got and (want or not got)
    found = {s for n in mellum_names for s in SCOPE.findall(n)}
    assert found <= set(tracing.DEVICE_SCOPES)


def test_every_name_in_a_program_is_registered(transformer_names,
                                               resnet_names,
                                               latent_moe_names):
    found = {s for n in transformer_names | resnet_names | latent_moe_names
             for s in SCOPE.findall(n)}
    buckets = {s for s in found if s.startswith("hvd.grad_reduce.b")}
    assert buckets and found - buckets <= set(tracing.DEVICE_SCOPES)
    for name in found:
        tracing.device_scope(name)      # does not raise


def test_every_all_reduce_carries_exactly_one_bucket(transformer_step):
    """In the program as XLA is handed it: the CPU compiler's combiner
    would merge the small buckets of this test into one instruction,
    which keeps one of their names."""
    step, args, plan = transformer_step
    text = step.lower(*args).compile(compiler_options={
        "xla_disable_hlo_passes": "cpu-all-reduce-combiner"}).as_text()
    carried, unnamed = [], []
    for line in text.splitlines():
        found = INSTRUCTION.match(line)
        if not found or found.group(2) not in ("all-reduce",
                                               "all-reduce-start"):
            continue
        (name,) = OP_NAME.findall(line)
        buckets = re.findall(r"hvd\.grad_reduce\.b(\d+)", name)
        if buckets:
            assert len(buckets) == 1, line
            carried.append(int(buckets[0]))
        else:
            unnamed.append(name)
    assert len(plan.bucket_leaf_indices) > 2
    assert sorted(carried) == list(range(len(plan.bucket_leaf_indices)))
    # the loss's mean over the data axis is the one other all-reduce
    assert len(unnamed) == 1 and BACKWARD not in unnamed[0]


def test_one_device_step_has_no_reduce_scope(transformer_step):
    """Nothing to reduce over: no bucket, no scale, no name of either."""
    _, (params, state, batch), _ = transformer_step
    cfg = small_config()
    mesh = Mesh(np.array(jax.devices()[:1]), axis_names=("data",))
    step = build_train_step(
        lambda p, b: transformer.loss_fn(cfg, p, b), optax.adamw(1e-3),
        mesh, batch_spec=P("data"), donate=False)
    names = op_names(step.lower(params, state, batch).compile())
    assert not any("hvd.grad_reduce" in n for n in names)
    assert variants(names, "hvd.optimizer")


def test_moe_scope(transformer_step):
    cfg = small_config(n_layers=1, n_kv_heads=4, moe=True, n_experts=2)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    grad = jax.jit(jax.grad(lambda p: transformer.loss_fn(
        cfg, p, {"tokens": tokens, "targets": tokens})))
    names = op_names(grad.lower(params).compile())
    assert {"forward", "backward"} <= variants(names, "hvd.moe")
    assert not variants(names, "hvd.ffn")


def test_scopes_are_not_in_the_lowered_text(transformer_step):
    step, args, _ = transformer_step
    assert "hvd." not in step.lower(*args).as_text()


@pytest.mark.parametrize("name", [
    "hvd.attention", "attn.core", "hvd.grad_reduce.b", "hvd.grad_reduce.bx",
    "hvd.grad_reduce.b-1", "hvd.grad_reduce.b1.2", ""])
def test_device_scope_refuses_an_unregistered_name(name):
    with pytest.raises(ValueError, match="no registered device scope"):
        tracing.device_scope(name)


def test_registry_names_and_version():
    """A scope added, renamed or moved raises DEVICE_SCOPES_VERSION,
    which is part of the persistent compile cache's key: the cache's
    own key leaves names out."""
    assert all(name.startswith("hvd.") and SCOPE.fullmatch(name)
               for name in tracing.DEVICE_SCOPES)
    assert (tracing.DEVICE_SCOPES_VERSION,
            sorted(tracing.DEVICE_SCOPES)) == (5, [
        "hvd.attn.core", "hvd.attn.linear", "hvd.attn.proj",
        "hvd.attn.select", "hvd.attn.sparse", "hvd.attn.window",
        "hvd.batchnorm", "hvd.conv",
        "hvd.embed", "hvd.ffn", "hvd.grad_reduce", "hvd.hc",
        "hvd.head_loss", "hvd.moe", "hvd.moe.experts", "hvd.moe.route",
        "hvd.moe.shared", "hvd.mtp", "hvd.optimizer", "hvd.ssm.proj",
        "hvd.ssm.scan"])
    with tracing.bucket_scope(3):
        pass


def test_resnet_parameter_tree_is_unchanged():
    model = resnet.ResNet(stage_sizes=[1, 1], num_classes=10,
                          num_filters=8)
    variables = jax.eval_shape(
        lambda k: resnet.init_resnet(model, k, 32), jax.random.PRNGKey(0))
    blocks = {"BottleneckBlock_0", "BottleneckBlock_1"}
    assert set(variables["params"]) == {
        "conv_init", "bn_init", "Dense_0"} | blocks
    assert set(variables["batch_stats"]) == {"bn_init"} | blocks
    for block in blocks:
        assert set(variables["params"][block]) == {
            "Conv_0", "Conv_1", "Conv_2", "BatchNorm_0", "BatchNorm_1",
            "BatchNorm_2", "conv_proj", "norm_proj"}


def test_aot_compile_moves_its_counters():
    def total(name):
        return snapshot().get(name, {}).get((), 0.0)
    names = ("hvd_aot_lower_seconds_total", "hvd_aot_compile_seconds_total",
             "hvd_aot_programs_total")
    before = [total(n) for n in names]
    fn, _ = aot_compile(jax.jit(lambda x: x * 2.0), jnp.ones((4,)))
    assert float(fn(jnp.ones((4,)))[0]) == 2.0
    after = [total(n) for n in names]
    assert after[0] > before[0] and after[1] > before[1]
    assert after[2] == before[2] + 1


_STALE_NAMES_PROBE = """
import re, sys
import jax, jax.numpy as jnp
from horovod_tpu import tracing
from horovod_tpu.common import compile_cache
scope, tracing.DEVICE_SCOPES_VERSION = sys.argv[1], int(sys.argv[2])
compile_cache.enable()
def f(x):
    with jax.named_scope(scope):
        return jnp.sin(x) @ x
text = jax.jit(f).lower(jnp.ones((8, 8))).compile().as_text()
print(sorted(set(re.findall(r"hvd\\.[a-z]+", text))))
"""


def test_the_compile_cache_does_not_hand_back_old_names(tmp_path):
    """JAX's cache key leaves names out: the same computation under a
    new scope hits the old entry and runs under the old names (the
    second run). The catalogue's version in the key (the third) is
    what gives a trace of a renamed program its names."""
    import os
    import subprocess
    import sys
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    seen = []
    for scope, version in (("hvd.one", 1), ("hvd.two", 1), ("hvd.two", 2)):
        r = subprocess.run(
            [sys.executable, "-c", _STALE_NAMES_PROBE, scope, str(version)],
            env=env, capture_output=True, text=True, timeout=120,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert r.returncode == 0, r.stderr[-2000:]
        seen.append(r.stdout.strip().splitlines()[-1])
    assert seen == ["['hvd.one']", "['hvd.one']", "['hvd.two']"]
