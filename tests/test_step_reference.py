"""`build_train_step` against a reference that shares nothing with it:
plain `jax.value_and_grad` of the global-batch mean loss on one device,
the same optimizer, no mesh and no bucket. Parameters, loss and
optimizer state after two steps over the meshes the builder decides
between (one device: nothing to reduce; data axes; fsdp specs, whose
gather transposes to a scatter; a live model axis the loss is
replicated over) and the bucket sizes it packs to. Then the numerics
guard's veto on every chip, and the fp16 / bf16 wire casts within the
error of their dtype."""

from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.metrics import REGISTRY
from horovod_tpu.parallel import train
from horovod_tpu.parallel.train import (ASYNC_REDUCE_OPTIONS,
                                        async_reduce_hbm_bytes,
                                        build_train_step,
                                        infer_opt_state_specs,
                                        last_overlap_info)

MESHES = {
    "data1": ((1,), ("data",)),
    "data4": ((4,), ("data",)),
    "data8": ((8,), ("data",)),
    "data2xfsdp2": ((2, 2), ("data", "fsdp")),
    "data2xtensor2": ((2, 2), ("data", "tensor")),
}
# overlap_threshold -> buckets on a mesh with something to reduce:
# the knob's default packs the 4 KB of gradients into one bucket,
# 1 KiB cuts the six leaves into several, 0 gives every leaf its own.
THRESHOLDS = {"default": (None, 1), "1KiB": (1024, 4), "nofusion": (0, 6)}
ROWS = 16


def _mesh(name):
    shape, axes = MESHES[name]
    n = int(np.prod(shape))
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), axes)


def _params(dtype=None):
    """A three-layer MLP: f32 but for `w2` in bf16 (or all `dtype`)."""
    k = jax.random.split(jax.random.PRNGKey(7), 3)
    p = {"w1": jax.random.normal(k[0], (16, 32)) * 0.3,
         "b1": jnp.full((32,), 0.1),
         "w2": (jax.random.normal(k[1], (32, 16)) * 0.3
                ).astype(jnp.bfloat16),
         "b2": jnp.zeros((16,)),
         "w3": jax.random.normal(k[2], (16, 4)) * 0.3,
         "b3": jnp.zeros((4,))}
    return p if dtype is None else jax.tree.map(
        lambda a: a.astype(dtype), p)


def _fsdp_specs():
    return {"w1": P("fsdp", None), "b1": P(), "w2": P("fsdp", None),
            "b2": P(), "w3": P("fsdp", None), "b3": P()}


def _loss(p, batch):
    """Mean over the rows it is handed: the global batch for the
    reference, a device's shard inside the step."""
    x, y = batch["x"], batch["y"]
    h = jnp.tanh(x @ p["w1"].astype(jnp.float32) + p["b1"])
    h = jnp.tanh(h @ p["w2"].astype(jnp.float32) + p["b2"])
    out = h @ p["w3"].astype(jnp.float32) + p["b3"]
    return jnp.mean((out - y) ** 2)


def _batches(n=2):
    out = []
    for i in range(n):
        kx, ky = jax.random.split(jax.random.PRNGKey(100 + i))
        out.append({"x": jax.random.normal(kx, (ROWS, 16)),
                    "y": jax.random.normal(ky, (ROWS, 4))})
    return out


def _reference(opt, params, batches):
    """[(params, optimizer state, loss)] after each batch: no mesh, no
    shard_map."""
    state, out = opt.init(params), []
    for batch in batches:
        loss, grads = jax.value_and_grad(_loss)(params, batch)
        updates, state = opt.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        out.append((params, state, float(loss)))
    return out


def _close(got, want, what, loose):
    """After the first step an f32 leaf agrees to the f32 rounding of
    a differently ordered sum and the bf16 leaf to a bf16 ulp or two.
    In the second (`loose`) that ulp of `w2` has moved every
    gradient, so all leaves get the bf16 bound: a lost factor, a
    missing reduction or another rank's rows are far outside it."""
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        rtol, atol = ((3e-2, 2e-3) if loose or g.dtype == jnp.bfloat16
                      else (1e-4, 1e-6))
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=rtol, atol=atol,
            err_msg=f"{what}{jax.tree_util.keystr(path)}")


def _step_inputs(mesh, opt, params):
    """(builder keyword arguments, placed params, placed state)."""
    kwargs = {}
    specs = state_specs = P()
    if "fsdp" in mesh.shape:
        specs = _fsdp_specs()
        state_specs = infer_opt_state_specs(opt, params, specs)
        kwargs = dict(param_specs=specs, opt_state_specs=state_specs)

    def place(tree, spec_tree):
        if isinstance(spec_tree, P):
            spec_tree = jax.tree.map(lambda _: spec_tree, tree)
        return jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            tree, spec_tree)
    return kwargs, place(params, specs), place(opt.init(params),
                                               state_specs)


@pytest.mark.parametrize("threshold", sorted(THRESHOLDS))
@pytest.mark.parametrize("mesh_name", sorted(MESHES))
def test_step_matches_plain_reference(mesh_name, threshold, monkeypatch):
    for k in ("HOROVOD_NUMERICS_GUARD", "HOROVOD_COMPRESSION",
              "HOROVOD_FUSION_THRESHOLD"):
        monkeypatch.delenv(k, raising=False)
    mesh = _mesh(mesh_name)
    opt = optax.sgd(0.1, momentum=0.9)
    params, batches = _params(), _batches()
    want = _reference(opt, params, batches)

    bytes_, buckets = THRESHOLDS[threshold]
    kwargs, p, s = _step_inputs(mesh, opt, params)
    step = build_train_step(_loss, opt, mesh, donate=False,
                            overlap_threshold=bytes_, **kwargs)
    for i, (batch, (want_p, want_s, want_l)) in enumerate(
            zip(batches, want)):
        p, s, metrics = step(p, s, batch)
        np.testing.assert_allclose(float(metrics["loss"]), want_l,
                                   rtol=2e-3 if i else 1e-5)
        _close(p, want_p, f"step {i} params", loose=i > 0)
        _close(s, want_s, f"step {i} optimizer state", loose=i > 0)
    info = last_overlap_info()
    if mesh.devices.size == 1:
        assert info["buckets"] == 0     # nothing to reduce over
    elif "fsdp" in mesh.shape:
        # sharded leaves cross `data` alone: a family of their own
        assert info["buckets"] == {"default": 2, "1KiB": 3,
                                   "nofusion": 6}[threshold], info
    else:
        assert info["buckets"] == buckets, info


@pytest.mark.parametrize("mesh_name",
                         ["data4", "data8", "data2xtensor2"])
def test_guard_veto_skips_the_step_everywhere(mesh_name, monkeypatch):
    """A NaN in one chip's rows: parameters and the optimizer's own
    state stay as they were on every chip, and the skip is counted;
    the same step on clean rows moves them."""
    from horovod_tpu import numerics
    monkeypatch.setenv("HOROVOD_NUMERICS_GUARD", "1")
    monkeypatch.delenv("HOROVOD_COMPRESSION", raising=False)
    mesh = _mesh(mesh_name)
    opt = numerics.guard_non_finite(optax.sgd(0.1, momentum=0.9),
                                    enabled=True)
    params = _params()
    (clean,) = _batches(1)
    # momentum that is not zero, so an untouched state shows
    kwargs, p0, s0 = _step_inputs(mesh, opt, params)
    step = build_train_step(_loss, opt, mesh, donate=False,
                            overlap_threshold=1024, **kwargs)
    p1, s1, _ = step(p0, s0, clean)
    assert numerics.consecutive_skips(s1) == 0
    assert float(jnp.abs(p1["w1"] - p0["w1"]).max()) > 0

    bad = dict(clean, x=clean["x"].at[ROWS - 1, 3].set(jnp.nan))
    p2, s2, _ = step(p1, s1, bad)
    assert numerics.consecutive_skips(s2) == 1

    def every_shard_equal(got, want, what):
        for (path, g), w in zip(
                jax.tree_util.tree_leaves_with_path(got),
                jax.tree.leaves(want)):
            assert len(g.addressable_shards) == mesh.devices.size
            for shard in g.addressable_shards:
                np.testing.assert_array_equal(
                    np.asarray(shard.data, np.float32),
                    np.asarray(w[shard.index], np.float32),
                    err_msg=f"{what}{jax.tree_util.keystr(path)} "
                            f"on {shard.device}")
    every_shard_equal(p2, p1, "params")
    trace = lambda s: [a for a in jax.tree.leaves(s) if a.ndim]
    every_shard_equal(trace(s2), trace(s1), "momentum")


@pytest.mark.parametrize("param_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["fp16", "bf16"])
def test_wire_cast_within_its_dtype_error(kind, param_dtype,
                                          monkeypatch):
    """One step with the buckets' wire cast, against the uncast
    reference: every gradient (read back from the SGD update) within
    the rounding of four addends in the wire dtype, or of the leaf's
    own where that is the coarser."""
    monkeypatch.delenv("HOROVOD_NUMERICS_GUARD", raising=False)
    mesh = _mesh("data4")
    lr = 0.5
    opt = optax.sgd(lr)
    params = _params(jnp.dtype(param_dtype))
    (batch,) = _batches(1)
    _, grads = jax.value_and_grad(_loss)(
        jax.tree.map(lambda a: a.astype(jnp.float32), params), batch)

    step = build_train_step(_loss, opt, mesh, donate=False,
                            overlap_threshold=1024, compression=kind)
    p1, _, _ = step(params, opt.init(params), batch)
    info = last_overlap_info()
    assert info["compression"] == kind and info["buckets"] >= 4
    if param_dtype == "float32":
        assert 2 * info["wire_bucket_bytes"] == info["raw_bucket_bytes"]
    eps = max(float(jnp.finfo(jnp.dtype(
        {"fp16": "float16", "bf16": "bfloat16"}[kind])).eps),
        float(jnp.finfo(jnp.dtype(param_dtype)).eps))
    for key, g in grads.items():
        got = (np.asarray(params[key], np.float32)
               - np.asarray(p1[key], np.float32)) / lr
        scale = float(jnp.abs(g).max())
        # the wire's four addends, and for a bf16 leaf the rounding
        # of the parameter the update was read back through
        slack = 8 * eps * scale + (
            2 * float(jnp.abs(params[key].astype(jnp.float32)).max())
            * eps / lr if param_dtype == "bfloat16" else 0.0)
        np.testing.assert_allclose(got, np.asarray(g), rtol=0,
                                   atol=slack, err_msg=key)
        assert np.abs(got).max() > 0.5 * scale   # and it is a gradient


def _tpu_mesh(n, kind="TPU v5 lite"):
    """What the options' rule reads of a mesh, for a TPU host that is
    not here."""
    chip = SimpleNamespace(platform="tpu", device_kind=kind,
                           client="a TPU's compiler")
    return SimpleNamespace(shape={"data": n}, local_devices=[chip] * n)


def _builds(label):
    return REGISTRY.snapshot().get(
        "hvd_train_step_builds_total", {}).get((label,), 0)


@pytest.mark.parametrize("mesh_name", sorted(MESHES))
def test_cpu_mesh_compiles_under_no_option(mesh_name):
    """Off the TPU a step is the plain jitted function with no
    compile option, whatever its mesh, and says so before and after
    it traces."""
    mesh = _mesh(mesh_name)
    assert async_reduce_hbm_bytes(mesh) is None
    off, on = _builds("off"), _builds("on")
    opt = optax.sgd(0.1)
    step = build_train_step(_loss, opt, mesh, donate=False)
    assert type(step) is type(jax.jit(_loss))
    assert last_overlap_info() == {
        "threshold": last_overlap_info()["threshold"], "traced": False,
        "compiler_options": []}
    assert (_builds("off"), _builds("on")) == (off + 1, on)
    params = _params()
    step(params, opt.init(params), _batches(1)[0])
    info = last_overlap_info()
    assert info["traced"] and info["compiler_options"] == []


@pytest.mark.parametrize("chips, kind, knows, want", [
    (1, "TPU v5 lite", True, None), (4, "TPU v5 lite", True, 16 << 30),
    (4, "TPU v5 lite", False, None), (4, "TPU v9", True, None)],
    ids=["one-chip", "four-chips", "compiler-refuses", "unknown-chip"])
def test_tpu_mesh_reduces_asynchronously_by_what_it_is(
        monkeypatch, chips, kind, knows, want):
    """The options go to a mesh of known TPU chips with a live axis
    whose compiler takes them, and to no other; a one-chip mesh is not
    even probed."""
    asked = []
    monkeypatch.setattr(
        train, "_compiler_knows",
        lambda device, options: asked.append(sorted(options)) or knows)
    assert async_reduce_hbm_bytes(_tpu_mesh(chips, kind)) == want
    probed = chips > 1 and kind in train.HBM_BYTES
    assert asked == ([sorted(ASYNC_REDUCE_OPTIONS) +
                      [train.MEMORY_LIMIT_OPTION]] if probed else [])


@pytest.mark.parametrize("held, grads, bucket, want", [
    (6021399040, 2006679552, 469762048, 50),
    (2617913856, 872600000, 201326592, 22),
    (12 << 30, 4 << 30, 1 << 30, 100)],
    ids=["mistral7b-l4-dp4", "flagship-dp4", "does-not-fit"])
def test_scheduler_memory_limit_is_what_the_tail_holds(held, grads,
                                                       bucket, want):
    """Arguments + gradients + one bucket in flight, in whole percent
    of a v5e's HBM, rounded up: inside the window in which the compiler
    makes the pairs and sinks nothing into them (46-52 for the Mistral
    cell's step, 20-26 for the flagship's; PERF.md, PR 34)."""
    assert train.scheduler_memory_limit_pct(
        held, grads, bucket, train.HBM_BYTES["TPU v5 lite"]) == want


def test_device_bytes_follow_the_specs():
    """What one device holds of a tree: whole where replicated, a
    share where a spec names an axis, inexact leaves alone on
    request."""
    mesh = _mesh("data2xfsdp2")
    tree = {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32),
            "n": jax.ShapeDtypeStruct((6,), jnp.int32)}
    assert train._device_nbytes(tree, P(), mesh) == (152, 128)
    assert train._device_nbytes(
        tree, {"w": P("fsdp", None), "n": P()}, mesh) == (88, 64)
    assert train._device_nbytes(tree, P(("data", "fsdp")), mesh,
                                inexact_only=True) == (32, 32)


def test_unknown_option_is_found_by_a_probe_compile(monkeypatch):
    """The probe compiles the identity under the options on the
    device's own compiler, once: the CPU's knows none of the TPU's, so
    it answers no (and warns) without a step having failed."""
    warned = []
    monkeypatch.setattr(train.hlog, "warning",
                        lambda *a: warned.append(a))
    monkeypatch.setattr(train, "_options_known", {})
    cpu = jax.devices()[0]
    assert train._compiler_knows(cpu, {}) is True
    assert train._compiler_knows(cpu, ASYNC_REDUCE_OPTIONS) is False
    assert train._compiler_knows(cpu, ASYNC_REDUCE_OPTIONS) is False
    assert len(warned) == 1 and "No such compile option" in str(warned[0])
    assert len(train._options_known) == 2


def test_options_reach_the_call_and_the_ahead_of_time_compile(
        monkeypatch):
    """A step that reduces asynchronously computes its options from
    its arguments and compiles under them however it is compiled:
    here on the CPU, whose compiler refuses the set, so both the call
    and `aot_compile` must raise it, and the step's record names it."""
    from horovod_tpu.parallel.aot import aot_compile
    monkeypatch.setattr(train, "async_reduce_hbm_bytes",
                        lambda mesh: 1 << 20)
    on = _builds("on")
    opt = optax.sgd(0.1)
    step = build_train_step(_loss, opt, _mesh("data4"), donate=False)
    names = sorted(ASYNC_REDUCE_OPTIONS) + [train.MEMORY_LIMIT_OPTION]
    assert _builds("on") == on + 1
    assert last_overlap_info()["compiler_options"] == names
    params = _params()
    args = (params, opt.init(params), _batches(1)[0])
    # a few KB of parameters, gradients and batch a device and one
    # 64 MiB bucket, against an "HBM" of 1 MiB
    assert step.compiler_options(*args) == {
        **ASYNC_REDUCE_OPTIONS, train.MEMORY_LIMIT_OPTION: 100}
    for compile_it in (lambda: step(*args),
                       lambda: aot_compile(step, *args)):
        with pytest.raises(Exception, match="No such compile option"):
            compile_it()
    assert last_overlap_info()["compiler_options"] == names
