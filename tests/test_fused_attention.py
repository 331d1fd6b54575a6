"""The fused causal attention kernels (parallel/fused_attention.py) in
Pallas's interpreter against `dense_attention`, and the rule by which
`attention()` picks its path. On the CPU the rule never engages by
itself: a test that needs the TPU branch patches
`jax.default_backend`, which only steers tracing (nothing is lowered)."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.metrics import REGISTRY
from horovod_tpu.parallel import fused_attention as fa
from horovod_tpu.parallel.ring_attention import (attention,
                                                 dense_attention,
                                                 flash_attention_path,
                                                 flash_possible_cfg)

# `horovod_tpu.parallel.ring_attention` the attribute is the function.
ra = importlib.import_module("horovod_tpu.parallel.ring_attention")


def _qkv(B, L, H, Hkv, D, Dv=None, dtype=jnp.float32, seed=0):
    """q, k (D wide), v (Dv wide, D where not given) and a weight of
    the output's shape."""
    Dv = Dv or D
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, L, H, D), dtype),
            jax.random.normal(ks[1], (B, L, Hkv, D), dtype),
            jax.random.normal(ks[2], (B, L, Hkv, Dv), dtype),
            jax.random.normal(ks[3], (B, L, H, Dv), jnp.float32))


def _engages(q, k, causal=True, v=None):
    """`_flash_supported` as a trace sees it, for shapes."""
    seen = []
    jax.eval_shape(
        lambda q, k, v: seen.append(ra._flash_supported(q, k, v, causal)),
        q, k, k if v is None else v)
    return seen[0]


def _fused(q, k, v):
    return fa.fused_causal_attention(q, k, v, q.shape[-1] ** -0.5,
                                     interpret=True)


def _latent(q, k, v):
    """The latent call: `flash_attention_path` with the kernels in the
    interpreter, at the scale of q's own width."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fa, "fused_causal_attention", functools.partial(
            fa.fused_causal_attention, interpret=True))
        return ra.flash_attention_path(q, k, v, True,
                                       q.shape[-1] ** -0.5)


def _loss(f, w):
    return lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) * w)


# (B, L, H, Hkv, D[, Dv]): the issue's (1, 4, 256, 128) in
# (B, H, L, D) terms, a grouped-query case, and two that cross block
# borders (two 512-blocks; five 128-blocks), where the masked blocks
# are skipped. The fifth has a group of 8 q heads, which takes two
# grid steps of 4. Then v narrower than q / k: one block, across block
# borders, with grouped kv, and the latent call through
# `flash_attention_path` (192 / 128: four and two heads a step
# unpadded, and with grouped kv, where q and k are padded to 256).
# Then the head layouts of the cells' backward across block borders:
# the Mistral group of 4 (32 / 8 scaled down to 8 / 2), `trinity`'s 12
# q heads on 2 kv heads (3 a step, two steps a kv head), and `xing4`'s
# four heads of 192 / 128 side by side.
SHAPES = [(1, 256, 4, 4, 128), (1, 256, 4, 2, 128),
          (1, 1024, 2, 1, 128), (2, 640, 2, 2, 128),
          (1, 256, 8, 1, 128),
          (1, 256, 4, 4, 256, 128), (2, 640, 2, 2, 256, 128),
          (1, 256, 4, 2, 256, 128), (1, 256, 4, 4, 192, 128),
          (1, 256, 2, 2, 192, 128), (1, 256, 4, 2, 192, 128),
          (1, 512, 8, 2, 128), (1, 640, 12, 2, 128),
          (1, 512, 4, 4, 192, 128)]
IDS = ["mha256", "gqa256", "gqa1024", "mha640", "mqa256",
       "mha256-v128", "mha640-v128", "gqa256-v128", "latent192-v128",
       "latent192-two-heads", "latent192-gqa-padded",
       "gqa512-group4", "gqa640-12on2", "latent512-four-heads"]


def _path(shape):
    return _fused if shape[4] % fa.LANES == 0 else _latent


@pytest.mark.parametrize("shape", SHAPES, ids=IDS)
def test_fused_matches_dense_forward_and_gradients(shape):
    q, k, v, w = _qkv(*shape)
    fused = _path(shape)
    out = fused(q, k, v)
    assert out.shape == w.shape
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(dense_attention(q, k, v, causal=True)),
        rtol=2e-5, atol=2e-5)
    got = jax.grad(_loss(fused, w), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(_loss(dense_attention, w), argnums=(0, 1, 2))(q, k, v)
    for g, o, name in zip(got, want, "qkv"):
        assert g.shape == o.shape and g.dtype == o.dtype
        np.testing.assert_allclose(np.asarray(g), np.asarray(o),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("shape", [(1, 256, 4, 2, 128),
                                   (1, 256, 4, 2, 256, 128)],
                         ids=["gqa256", "gqa256-v128"])
def test_fused_bf16_within_rounding_of_dense(shape):
    q, k, v, w = _qkv(*shape, dtype=jnp.bfloat16)
    out = _fused(q, k, v)
    assert out.dtype == jnp.bfloat16 and out.shape == w.shape
    ref = dense_attention(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < 0.05
    got = jax.grad(_loss(_fused, w), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(_loss(dense_attention, w), argnums=(0, 1, 2))(q, k, v)
    for g, o in zip(got, want):
        assert g.shape == o.shape and g.dtype == jnp.bfloat16
        assert float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                     - o.astype(jnp.float32)))) < 0.25


# (B, L, H, Hkv, Dqk, Dv), window: the backward's head layouts as the
# cells run them, scaled down (the Mistral group of 4; 12 q heads on 2
# kv heads, two steps a kv head; four heads of 192 / 128 side by side;
# grouped kv with v narrower), a single block, and windows whose edge
# cuts a block (one of them with two steps a kv head).
BACKWARDS = [((1, 512, 8, 2, 128, 128), None),
             ((1, 768, 12, 2, 128, 128), None),
             ((2, 512, 4, 4, 192, 128), None),
             ((1, 512, 4, 2, 256, 128), None),
             ((1, 256, 4, 1, 128, 128), None),
             ((1, 896, 2, 2, 128, 128), 300),
             ((1, 640, 12, 2, 128, 128), 200)]


@pytest.mark.parametrize("shape,window", BACKWARDS, ids=[
    f"L{s[1]}-h{s[2]}kv{s[3]}-{s[4]}x{s[5]}-w{w}" for s, w in BACKWARDS])
def test_one_backward_kernel_matches_two_and_dense(shape, window):
    """The one kernel's dQ, dK, dV against the two kernels' from the
    same residuals (the same f32 sums in another order) and against
    `dense_attention`'s gradients."""
    q, k, v, do = _qkv(*shape)
    scale = shape[4] ** -0.5
    o, lse = fa._forward(q, k, v, scale, True, window)
    one = fa._backward(q, k, v, o, lse, do, scale, True, window, True)
    two = fa._backward(q, k, v, o, lse, do, scale, True, window, False)
    _, vjp = jax.vjp(_dense_windowed(window), q, k, v)
    for a, b, want, name in zip(one, two, vjp(do), "qkv"):
        assert a.shape == b.shape == want.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=name)
        np.testing.assert_allclose(np.asarray(a), np.asarray(want),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


# (B, L, H, Hkv, Dqk, Dv) -> one backward kernel, and the VMEM it
# declares (MiB; None: the compiler's default budget): the cells' cores
# (Mistral 2 MB of resident f32 dK / dV, `trinity` 16, `xing4` 20 MiB)
# and samples, the longest sequence one kv head of 128 / 128 fits
# (32 MiB), and over the budget: seq 65,536 (64 MiB), four kv heads a
# step at 32,768.
ONE_KERNEL = [((2, 2048, 32, 8, 128, 128), True, None),
              ((1, 256, 32, 8, 128, 128), True, None),
              ((1, 16384, 12, 2, 128, 128), True, 41.5),
              ((1, 8192, 12, 2, 128, 128), True, 25.5),
              ((2, 4096, 32, 32, 192, 128), True, 54.0),
              ((1, 256, 32, 32, 192, 128), True, None),
              ((1, 32768, 6, 1, 128, 128), True, 73.5),
              ((1, 65536, 6, 1, 128, 128), False, None),
              ((1, 32768, 8, 8, 128, 128), False, None)]


@pytest.mark.parametrize("shape,one,vmem_mib", ONE_KERNEL)
def test_one_backward_kernel_rule(shape, one, vmem_mib):
    B, L, H, Hkv, Dqk, Dv = shape
    q, k, v = (B, L, H, Dqk), (B, L, Hkv, Dqk), (B, L, Hkv, Dv)
    assert fa.supported(q, k, v)
    assert fa.one_kernel_backward(q, k, v) is one
    if one:
        vmem = fa._bwd_vmem(shape, fa.step_heads(H, Hkv, Dqk, Dv),
                            fa.block_size(L), 2)
        assert vmem == (None if vmem_mib is None else vmem_mib * 2**20)


def _backward_traces():
    snap = REGISTRY.snapshot().get("hvd_attention_backward_traces_total",
                                   {})
    return {n: snap.get((n,), 0.0) for n in ("one", "two")}


@pytest.mark.parametrize("seq,kernels", [(256, "one"), (16384, "one"),
                                         (65536, "two")])
def test_backward_counter_counts_each_trace(seq, kernels):
    """Each trace of the backward counts the kernels it got; a forward
    alone counts nothing."""
    q = jax.ShapeDtypeStruct((1, seq, 6, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, seq, 1, 128), jnp.bfloat16)

    def fwd(q, k, v):
        return fa.fused_causal_attention(q, k, v, 1.0).astype(
            jnp.float32).sum()
    before = _backward_traces()
    jax.eval_shape(fwd, q, k, k)
    assert _backward_traces() == before
    jax.eval_shape(jax.grad(fwd, argnums=(0, 1, 2)), q, k, k)
    after = _backward_traces()
    assert {n: after[n] - before[n] for n in after} == {
        **dict.fromkeys(after, 0.0), kernels: 1.0}


def test_fused_under_shard_map_with_the_replication_checker_on():
    """Outputs are typed varying as q is: the kernels trace under
    check_vma=True, and gradients of replicated inputs still arrive
    summed over the data axis."""
    mesh = Mesh(np.array(jax.devices()[:2]), axis_names=("data",))
    q, k, v, w = _qkv(2, 256, 2, 1, 128)

    def local(q, k, v, w):
        def loss(q, k, v):
            return _loss(_fused, w)(q, k, v)
        l, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return jax.lax.psum(l, "data"), g

    f = jax.jit(shard_map(local, mesh=mesh, in_specs=(P("data"),) * 4,
                          out_specs=(P(), (P("data"),) * 3),
                          check_vma=True))
    l, g = f(q, k, v, w)
    want_l, want_g = jax.value_and_grad(
        _loss(dense_attention, w), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(l), float(want_l), rtol=1e-5)
    for a, b in zip(g, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_fused_refuses_shapes_it_does_not_take():
    q, k, v, _ = _qkv(1, 200, 2, 2, 128)
    with pytest.raises(ValueError, match="128-blocks"):
        fa.fused_causal_attention(q, k, v, 1.0, interpret=True)


@pytest.mark.parametrize("seq,block", [
    (2048, 512), (1024, 512), (256, 256), (128, 128), (384, 384),
    (640, 128), (1536, 512), (200, 0), (64, 0)])
def test_block_rule(seq, block):
    assert fa.block_size(seq) == block


@pytest.mark.parametrize("group,heads", [
    (1, 1), (2, 2), (4, 4), (8, 4), (6, 3), (7, 1), (32, 4)])
def test_heads_per_step_rule(group, heads):
    assert fa.heads_per_step(group) == heads


# q, k, v shapes and whether the kernels take them
SUPPORTED = [
    ((1, 256, 4, 128), (1, 256, 4, 128), (1, 256, 4, 128), True),
    ((1, 256, 4, 256), (1, 256, 4, 256), (1, 256, 4, 128), True),
    ((2, 640, 2, 256), (2, 640, 2, 256), (2, 640, 2, 128), True),
    ((1, 256, 4, 256), (1, 256, 2, 256), (1, 256, 2, 128), True),
    ((1, 256, 4, 128), (1, 256, 4, 128), (1, 256, 4, 256), True),
    # every q head its own kv head: an even number of heads of 192
    # side by side is a whole number of lanes
    ((1, 256, 4, 192), (1, 256, 4, 192), (1, 256, 4, 128), True),
    ((2, 4096, 32, 192), (2, 4096, 32, 192), (2, 4096, 32, 128), True),
    ((1, 256, 3, 192), (1, 256, 3, 192), (1, 256, 3, 128), False),
    ((1, 256, 4, 192), (1, 256, 2, 192), (1, 256, 2, 128), False),
    ((1, 256, 4, 192), (1, 256, 4, 192), (1, 256, 4, 192), False),
    ((1, 256, 4, 256), (1, 256, 4, 256), (1, 256, 4, 64), False),
    ((1, 256, 4, 256), (1, 256, 4, 128), (1, 256, 4, 128), False),
    ((1, 256, 4, 256), (1, 256, 4, 256), (1, 256, 2, 128), False),
    ((1, 256, 4, 256), (1, 256, 4, 256), (1, 128, 4, 128), False),
    ((1, 256, 4, 256), (1, 256, 4, 256), (256, 4, 128), False),
]


@pytest.mark.parametrize("q,k,v,takes", SUPPORTED)
def test_supported_shapes(q, k, v, takes):
    assert fa.supported(q, k, v) is takes


# (H, Hkv, Dqk, Dv) -> (q heads, kv heads, steps a kv head) a grid step
STEP_HEADS = [
    ((32, 8, 128, 128), (4, 1, 1)),       # the Mistral cells
    ((8, 1, 128, 128), (4, 1, 2)),
    ((8, 2, 256, 256), (4, 1, 1)),
    ((6, 2, 128, 128), (3, 1, 1)),
    ((4, 2, 192, 128), None),             # grouped kv wants whole lanes
    ((32, 32, 192, 128), (4, 4, 1)),      # the latent cell
    ((2, 2, 192, 128), (2, 2, 1)),
    ((3, 3, 192, 128), None),
    ((3, 3, 128, 128), (3, 3, 1)),
    ((32, 32, 128, 128), (4, 4, 1)),
    ((32, 32, 256, 128), (4, 4, 1)),
    ((32, 32, 256, 256), (2, 2, 1)),      # four do not fit VMEM
    ((7, 7, 512, 512), (1, 1, 1)),
]


@pytest.mark.parametrize("heads,step", STEP_HEADS)
def test_step_heads_rule(heads, step):
    assert fa.step_heads(*heads) == step


# backend, (L, H, Hkv, D[, Dv]), kv length, causal, engages
RULE = [
    ("tpu", (2048, 32, 8, 128), None, True, True),
    ("tpu", (256, 32, 8, 128), None, True, True),     # the cells' sample
    ("tpu", (256, 32, 32, 128), None, True, True),
    ("tpu", (256, 4, 4, 256), None, True, True),
    ("tpu", (256, 4, 4, 256, 128), None, True, True),
    ("tpu", (640, 2, 2, 256, 128), None, True, True),
    ("tpu", (256, 4, 2, 256, 128), None, True, True),
    ("tpu", (256, 4, 4, 192, 128), None, True, True),   # the latent call
    ("tpu", (256, 4, 2, 192, 128), None, True, True),   # q / k padded
    ("tpu", (256, 3, 3, 160, 128), None, True, True),   # q / k padded
    ("tpu", (4096, 32, 32, 192, 128), None, True, True),
    ("tpu", (256, 4, 4, 192, 64), None, True, False),   # v no whole lanes
    ("tpu", (256, 4, 4, 192, 192), None, True, False),  # equal, unpadded
    ("cpu", (256, 4, 4, 192, 128), None, True, False),
    ("tpu", (200, 32, 8, 128), None, True, False),    # not in 128-blocks
    ("tpu", (512, 16, 16, 64), None, True, False),    # the flagship's heads
    ("tpu", (256, 6, 4, 128), None, True, False),     # no whole groups
    ("tpu", (256, 32, 8, 128), 512, True, False),     # cross-attention
    ("tpu", (256, 32, 8, 128), None, False, False),   # not causal
    ("cpu", (2048, 32, 8, 128), None, True, False),
    ("gpu", (256, 32, 8, 128), None, True, False),
]


@pytest.mark.parametrize("backend,shape,kv_len,causal,engages", RULE)
def test_engagement_rule(monkeypatch, backend, shape, kv_len, causal,
                         engages):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    L, H, Hkv, D = shape[:4]
    q = jax.ShapeDtypeStruct((1, L, H, D), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, kv_len or L, Hkv, D), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((*k.shape[:3], shape[-1]), jnp.bfloat16)
    assert _engages(q, k, causal, v) is engages


@pytest.mark.parametrize("dtype,engages", [
    (jnp.bfloat16, True), (jnp.float32, False), (jnp.float16, False)])
def test_engagement_rule_dtype(monkeypatch, dtype, engages):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((1, 256, 4, 128), dtype)
    assert _engages(q, q) is engages


@pytest.mark.parametrize("axis,engages", [("data", True), ("seq", False)])
def test_engagement_rule_live_axis(monkeypatch, axis, engages):
    """A live sequence-parallel axis on q (Ulysses after its
    all-to-all) keeps the dense path; a data axis does not."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(jax.devices()[:2]), axis_names=(axis,))
    seen = []

    def local(q):
        seen.append(ra._flash_supported(q, q, q, True))
        return q

    q = jax.ShapeDtypeStruct((2, 256, 4, 128), jnp.bfloat16)
    jax.eval_shape(shard_map(local, mesh=mesh, in_specs=P(axis),
                             out_specs=P(axis)), q)
    assert seen == [engages]


@pytest.mark.parametrize("seq", [2048, 256])
@pytest.mark.parametrize("sp_live", [False, True])
def test_flash_possible_cfg_keeps_the_checker_on(monkeypatch, seq, sp_live):
    """What perfbench's builder asks before `build_train_step`: no
    config needs check_vma off, on the TPU or off it."""
    for backend in ("tpu", "cpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert flash_possible_cfg(128, seq, sp_live=sp_live) is False


def _trace_attention(*shapes, **kw):
    """One fresh trace of attention() (eval_shape caches by function)."""
    return jax.eval_shape(lambda *a: attention(*a, **kw), *shapes)


def _traces():
    snap = REGISTRY.snapshot().get("hvd_attention_traces_total", {})
    return {path: snap.get((path,), 0.0)
            for path in ("fused", "fused_padded_qk", "dense",
                         "fused_window", "dense_window")}


def test_path_counter_counts_each_trace(monkeypatch):
    q = jax.ShapeDtypeStruct((1, 256, 4, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
    before = _traces()
    out = _trace_attention(q, k, k)            # the CPU: dense
    assert out.shape == q.shape
    mid = _traces()
    assert (mid["dense"], mid["fused"]) == (before["dense"] + 1,
                                            before["fused"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    out = _trace_attention(q, k, k)
    assert out.shape == q.shape and out.dtype == q.dtype
    after = _traces()
    assert (after["dense"], after["fused"]) == (mid["dense"],
                                                mid["fused"] + 1)


def test_path_counter_names_the_padded_form(monkeypatch):
    """Latent attention's q / k of 192 reach the kernels as they are
    where every q head has its own kv head, and count as `fused` like
    equal widths; with grouped kv they are zero-padded to 256 and
    count as `fused_padded_qk`. v goes at its own 128 either way."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for width, kv_heads, path in ((192, 4, "fused"), (256, 4, "fused"),
                                  (128, 2, "fused"),
                                  (192, 2, "fused_padded_qk"),
                                  (160, 1, "fused_padded_qk")):
        q = jax.ShapeDtypeStruct((1, 256, 4, width), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((1, 256, kv_heads, width), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((1, 256, kv_heads, 128), jnp.bfloat16)
        before = _traces()
        assert _trace_attention(q, k, v).shape == (1, 256, 4, 128)
        after = _traces()
        assert {p: after[p] - before[p] for p in after} == {
            **dict.fromkeys(after, 0.0), path: 1.0}


@pytest.mark.parametrize("backend,path", [("tpu", "fused"),
                                          ("cpu", "dense")])
def test_the_backend_decides_the_path(monkeypatch, backend, path):
    """No knob: the same call runs fused on the TPU and dense off it."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    q = jax.ShapeDtypeStruct((1, 256, 4, 128), jnp.bfloat16)
    before = _traces()
    _trace_attention(q, q, q)
    after = _traces()
    assert after[path] == before[path] + 1


def test_named_fused_path_raises_on_unsupported_calls():
    """`flash_attention_path`, for callers that name it, keeps its own
    checks; attention() never sends it such a call."""
    q = jax.ShapeDtypeStruct((1, 200, 4, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="128-blocks"):
        jax.eval_shape(lambda a: flash_attention_path(
            a, a, a, True, 128 ** -0.5), q)
    with pytest.raises(ValueError, match="no bidirectional attention"):
        q = jax.ShapeDtypeStruct((1, 256, 4, 128), jnp.bfloat16)
        jax.eval_shape(lambda a: flash_attention_path(
            a, a, a, False, 128 ** -0.5), q)


def test_attention_takes_grouped_kv_on_the_dense_path():
    q, k, v, _ = _qkv(1, 64, 4, 2, 16)
    rep = lambda x: jnp.repeat(x, 2, axis=2)  # noqa: E731
    np.testing.assert_allclose(
        np.asarray(attention(q, k, v)),
        np.asarray(dense_attention(q, rep(k), rep(v))), rtol=1e-6)


# ---------------------------------------------------------------------------
# A sliding window: a query sees its own position and the window - 1
# before it
# ---------------------------------------------------------------------------

def _windowed(window):
    return lambda q, k, v: fa.fused_causal_attention(
        q, k, v, q.shape[-1] ** -0.5, window=window, interpret=True)


def _dense_windowed(window):
    return lambda q, k, v: dense_attention(q, k, v, True, None, window)


# (B, L, H, Hkv, D), window. Seven 128-blocks at a group of 6 and of
# 1: a window of two whole blocks, one that ends inside a block, one
# block, a single key. Two 512-blocks: one whole block, and one and a
# part (nothing to skip, the edge still masked). Three 384-blocks with
# two heads a step side by side.
WINDOWED = [((1, 896, 6, 1, 128), 256), ((1, 896, 6, 1, 128), 300),
            ((1, 896, 2, 2, 128), 256), ((1, 896, 2, 2, 128), 300),
            ((2, 896, 2, 1, 128), 128), ((1, 896, 2, 1, 128), 1),
            ((1, 1024, 6, 1, 128), 512), ((1, 1024, 2, 2, 128), 700),
            ((1, 1152, 4, 4, 256, 128), 400)]


@pytest.mark.parametrize("shape,window", WINDOWED, ids=[
    f"L{s[1]}-h{s[2]}kv{s[3]}-w{w}" for s, w in WINDOWED])
def test_windowed_fused_matches_dense_forward_and_gradients(shape, window):
    q, k, v, w = _qkv(*shape)
    fused, dense = _windowed(window), _dense_windowed(window)
    np.testing.assert_allclose(np.asarray(fused(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(_loss(fused, w), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(_loss(dense, w), argnums=(0, 1, 2))(q, k, v)
    for g, o, name in zip(got, want, "qkv"):
        assert g.shape == o.shape and g.dtype == o.dtype
        np.testing.assert_allclose(np.asarray(g), np.asarray(o),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("window", [1, 5, 64, 200])
def test_dense_window_is_the_mask_written_out(window):
    q, k, v, _ = _qkv(1, 64, 2, 1, 16)
    i, j = np.arange(64)[:, None], np.arange(64)[None, :]
    seen = (j <= i) & (i - j < window)
    scores = np.einsum("bqhd,bkhd->bhqk", q, np.repeat(k, 2, 2)) / 4.0
    scores = np.where(seen, scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", p, np.repeat(v, 2, 2))
    np.testing.assert_allclose(
        np.asarray(dense_attention(q, k, v, True, None, window)), want,
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(                   # attention() off the TPU
        np.asarray(attention(q, k, v, window=window)), want,
        rtol=1e-5, atol=1e-6)


def test_no_window_is_the_causal_program_bit_for_bit():
    """`window=None` is the causal program (its lowered text in the
    benchmark's cells is the parent's, PERF.md PR 35); a window that
    reaches the whole sequence is no window to attention(), and in
    the kernels themselves it masks nothing: equal bits."""
    q, k, v, w = _qkv(1, 896, 2, 1, 128)
    causal = _fused(q, k, v)
    assert np.array_equal(np.asarray(_windowed(None)(q, k, v)),
                          np.asarray(causal))
    assert np.array_equal(np.asarray(_windowed(896)(q, k, v)),
                          np.asarray(causal))
    got = jax.grad(_loss(_windowed(896), w), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(_loss(_fused, w), argnums=(0, 1, 2))(q, k, v)
    for g, o in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(o))


def _blocks_with_a_visible_pair(seq, window):
    blk = fa.block_size(seq)
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    return int(seen.reshape(seq // blk, blk, seq // blk, blk)
               .any(axis=(1, 3)).sum())


@pytest.mark.parametrize("seq,window,visited,causal", [
    (16384, 4096, 252, 528), (16384, None, 528, 528),
    (8192, 4096, 108, 136), (896, 256, 18, 28), (896, 300, 22, 28),
    (896, 1, 7, 28), (1024, 512, 3, 3), (2048, 4096, 10, 10)])
def test_a_window_skips_key_blocks(seq, window, visited, causal):
    """The blocks the forward walk computes are those with a visible
    pair, and the grid has no step for the others: skipping, not
    masking."""
    assert fa.blocks_visited(seq, window) == (visited, causal)
    if seq <= 2048:
        assert _blocks_with_a_visible_pair(seq, window) == visited
    blk = fa.block_size(seq)
    steps = fa.walk_steps(seq, blk, window)
    assert steps == (seq // blk if window is None else
                     min(seq // blk, (window + blk - 2) // blk + 1))
    q = jax.ShapeDtypeStruct((1, seq, 6, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, seq, 1, 128), jnp.bfloat16)
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(jax.grad(
        lambda q, k, v: fa.fused_causal_attention(
            q, k, v, 1.0, window=window).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(q, k, k).jaxpr)
    n = seq // blk
    assert sorted(grids) == sorted([(1, 2, n, steps), (1, 1, 2, n, steps)])


def _key_blocks():
    snap = REGISTRY.snapshot().get("hvd_attention_key_blocks_total", {})
    return {b: snap.get((b,), 0.0) for b in ("visited", "causal")}


@pytest.mark.parametrize("backend,window,path,visited", [
    ("tpu", 4096, "fused_window", 252), ("tpu", None, "fused", 528),
    ("tpu", 16384, "fused", 528), ("tpu", 1 << 30, "fused", 528),
    ("cpu", 4096, "dense_window", 0), ("cpu", 16384, "dense", 0)])
def test_window_paths_and_the_block_counter(monkeypatch, backend, window,
                                            path, visited):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    q = jax.ShapeDtypeStruct((1, 16384, 12, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 16384, 2, 128), jnp.bfloat16)
    paths, blocks = _traces(), _key_blocks()
    assert _trace_attention(q, k, k, window=window).shape == q.shape
    paths_after, blocks_after = _traces(), _key_blocks()
    assert {p: paths_after[p] - paths[p] for p in paths} == {
        **dict.fromkeys(paths, 0.0), path: 1.0}
    assert {b: blocks_after[b] - blocks[b] for b in blocks} == {
        "visited": visited, "causal": 528.0 if visited else 0.0}


@pytest.mark.parametrize("kw", [dict(causal=False, window=8),
                                dict(window=0), dict(window=-4)])
def test_a_window_is_causal_and_holds_the_query(kw):
    q, k, v, _ = _qkv(1, 64, 2, 1, 16)
    with pytest.raises(ValueError, match="sliding window"):
        attention(q, k, v, **kw)
    if kw.get("causal", True):
        with pytest.raises(ValueError, match="window holds at least"):
            fa.fused_causal_attention(q, k, v, 1.0, interpret=True, **kw)
