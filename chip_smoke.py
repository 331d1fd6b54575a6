#!/usr/bin/env python
"""Quickest proof that the trainer starts on the chip.

    python chip_smoke.py              one TPU chip, one process
    python chip_smoke.py --multichip  one host with four chips

Default mode drives the training path once through the entry points a
user calls (hvd.init, data_parallel_mesh, build_train_step,
aot_compile, hvd.DistributedOptimizer, hvd.make_pipelined_step) at
the full width of the flagship transformer and ResNet-50, checks what
comes out, and prints as its LAST line

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

It fails at once when JAX finds no TPU: there is no CPU fallback, no
interpret mode and no reference path standing in for a kernel. Every
other line it prints is a per-phase record labelled with the device;
none of them is a benchmark.

--multichip runs four legs (data-parallel jit, eager collectives,
sharded MoE model, four ranks of one chip each) and no phase of the
default mode. Each leg is a child process, one after the other,
because a process that has touched the chips holds them until it
exits; this script's own process never initialises a JAX backend.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

# bench.py's flagship cell (transformer_main): 24 x d1024 x h16,
# ff 4096, vocab 32768, seq 512, per-chip batch 16 — 436.3M params.
FLAGSHIP = dict(vocab=32768, d_model=1024, n_layers=24, n_heads=16,
                d_ff=4096, seq=512, batch=16)
# bench.py's default cell (main): ResNet-50, bs 128, 224 px, bf16.
RESNET = dict(batch=128, image=224, stages=None)
# One fusion-threshold-sized f32 bucket (64 MiB) and an odd length.
ADASUM_SIZES = (16 * 1024 * 1024, 1_000_003)
# bf16 compute, two differently-fused programs of the same math.
BF16_LOSS_TOL = 5e-2
# --multichip's last leg: one rank per chip of a four-chip host.
N_RANKS = 4


def say(phase: str, **kv) -> None:
    print(json.dumps({"phase": phase, **kv}), flush=True)


class CompileCounter:
    """Programs handed to the backend compiler (cache hits included)
    while the block runs: the rise of the program's own
    `hvd_jit_programs_total` (common/compile_cache.py)."""

    def __init__(self):
        from horovod_tpu.common import compile_cache
        compile_cache.listen()
        self.n = 0

    @staticmethod
    def _total() -> int:
        from horovod_tpu.metrics import snapshot
        return int(sum(snapshot()["hvd_jit_programs_total"].values()))

    def __enter__(self):
        self.n, self._before = 0, self._total()
        return self

    def __exit__(self, *exc):
        self.n = self._total() - self._before


def device_record() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def require_tpu() -> dict:
    dev = device_record()
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform="
            f"{dev['platform']!r}); this script has no CPU fallback")
    return dev


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def check_losses(phase: str, losses) -> None:
    import math
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{phase}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"{phase}: loss did not fall on a fixed batch: {losses}")


def flagship_config(shape: dict):
    import jax.numpy as jnp
    from horovod_tpu.models import transformer as tfm
    return tfm.TransformerConfig(
        vocab=shape["vocab"], d_model=shape["d_model"],
        n_layers=shape["n_layers"], n_heads=shape["n_heads"],
        n_kv_heads=shape["n_heads"],
        head_dim=shape["d_model"] // shape["n_heads"],
        d_ff=shape["d_ff"], max_seq=shape["seq"], moe=False,
        dtype=jnp.bfloat16, remat=True, remat_mode="full",
        tp_axis=None, sp_axis=None, ep_axis=None)


def flagship_tokens(shape: dict, global_batch: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return rng.integers(0, shape["vocab"], (global_batch, shape["seq"]),
                        dtype=np.int32)


def run_jit_steps(phase, step, params, opt_state, make_batch, steps,
                  counter):
    """aot_compile `step`, run `steps` steps, return (losses,
    executable). `make_batch(aux)` returns the step's batch; aux is the previous
    step's metrics["aux"] (None first) so BN stats thread through."""
    import jax
    from horovod_tpu.parallel.aot import aot_compile
    from horovod_tpu.parallel.train import last_overlap_info

    batch = make_batch(None)
    t0 = time.perf_counter()
    with counter:
        step_exec, _flops = aot_compile(step, params, opt_state, batch)
    compile_s = time.perf_counter() - t0
    n_compiled = counter.n
    losses, step_s = [], []
    with counter:
        for _ in range(steps):
            t0 = time.perf_counter()
            params, opt_state, metrics = step_exec(params, opt_state,
                                                   batch)
            jax.block_until_ready((params, opt_state, metrics))
            step_s.append(time.perf_counter() - t0)
            losses.append(float(metrics["loss"]))
            batch = make_batch(metrics.get("aux"))
    n_compiled += counter.n
    info = last_overlap_info()
    rec = dict(compile_s=round(compile_s, 2),
               step_s=[round(s, 4) for s in step_s],
               compilations=n_compiled,
               losses=[round(x, 4) for x in losses],
               peak_bytes=peak_bytes(),
               buckets=info.get("buckets", 0),
               bucket_digest=info.get("digest", ""))
    say(phase, **rec)
    check_losses(phase, losses)
    if n_compiled != 1:
        raise AssertionError(
            f"{phase}: expected exactly one compilation, saw "
            f"{n_compiled}")
    return losses, step_exec


def flagship_step(shape: dict, mesh):
    """(cfg, optimizer, jitted step) of the flagship cell on `mesh`,
    built as bench.py's transformer_main builds it."""
    import optax
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel import build_train_step

    cfg = flagship_config(shape)
    opt = optax.adamw(1e-4)
    step = build_train_step(
        lambda p, b: tfm.loss_fn(cfg, p, b), opt, mesh,
        batch_spec={"tokens": P("data"), "targets": P("data")},
        donate=True)
    return cfg, opt, step


def phase_flagship_jit(shape=FLAGSHIP, steps=4, seed=0, mesh=None,
                       counter=None):
    """Flagship transformer through build_train_step + aot_compile."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel.mesh import data_parallel_mesh

    mesh = mesh if mesh is not None else data_parallel_mesh()
    cfg, opt, step = flagship_step(shape, mesh)
    params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    n_params = sum(int(p.size) for p in jax.tree.leaves(params))
    opt_state = opt.init(params)
    tokens = jax.device_put(
        flagship_tokens(shape, shape["batch"] * mesh.devices.size, seed),
        NamedSharding(mesh, P("data")))
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}
    shard_devices = sorted(
        s.device.id for s in tokens.addressable_shards)
    say("flagship_jit.setup", params_m=round(n_params / 1e6, 1),
        mesh=dict(mesh.shape), batch_shard_devices=shard_devices)
    losses, step_exec = run_jit_steps(
        "flagship_jit", step, params, opt_state, lambda aux: batch,
        steps, counter or CompileCounter())
    return dict(losses=losses, step_exec=step_exec,
                shard_devices=shard_devices)


def phase_flagship_eager(ref_losses, shape=FLAGSHIP, steps=2, seed=0,
                         counter=None):
    """The same model and batch through the negotiated engine:
    hvd.DistributedOptimizer (grouped allreduce), then
    hvd.make_pipelined_step with the bf16 wire."""
    import jax
    import jax.numpy as jnp
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as tfm

    counter = counter or CompileCounter()
    cfg = flagship_config(shape)
    tokens = jnp.asarray(flagship_tokens(shape, shape["batch"], seed))
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}

    def loss_fn(p, b):
        return tfm.loss_fn(cfg, p, b)

    # -- hvd.DistributedOptimizer: grad program, grouped allreduce
    #    through the controller, apply program.
    opt = hvd.DistributedOptimizer(optax.adamw(1e-4))
    params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    opt_state = opt.init(params)
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    apply_fn = jax.jit(optax.apply_updates, donate_argnums=(0,))
    losses, step_s, compiles = [], [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        with counter:
            loss, grads = grad_fn(params, batch)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = apply_fn(params, updates)
            jax.block_until_ready((params, opt_state))
        step_s.append(time.perf_counter() - t0)
        compiles.append(counter.n)
        losses.append(float(loss))
    say("flagship_eager.distributed_optimizer",
        step_s=[round(s, 3) for s in step_s],
        compilations_per_step=compiles,
        losses=[round(x, 4) for x in losses], peak_bytes=peak_bytes())
    check_losses("flagship_eager.distributed_optimizer", losses)
    if compiles[-1] != 0:
        raise AssertionError(
            "flagship_eager: the second DistributedOptimizer step "
            f"compiled {compiles[-1]} new program(s)")
    if abs(losses[0] - ref_losses[0]) > BF16_LOSS_TOL:
        raise AssertionError(
            f"flagship_eager: first-step loss {losses[0]} vs the jit "
            f"path's {ref_losses[0]} (tolerance {BF16_LOSS_TOL})")
    del params, opt_state, grads, updates

    # -- hvd.make_pipelined_step(compression=bf16): init() computes
    #    the grads of step 1, so call k returns the loss of step k+1.
    inner = optax.adamw(1e-4)
    params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    pipe = hvd.make_pipelined_step(loss_fn, inner,
                                   compression=hvd.Compression.bf16)
    state = pipe.init(params, inner.init(params), batch)
    plosses, step_s, compiles = [], [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        with counter:
            state, loss = pipe(state, batch)
            jax.block_until_ready(state)
        step_s.append(time.perf_counter() - t0)
        compiles.append(counter.n)
        plosses.append(float(loss))
    params, opt_state = pipe.finalize(state)
    jax.block_until_ready((params, opt_state))
    say("flagship_eager.pipelined_bf16",
        step_s=[round(s, 3) for s in step_s],
        compilations_per_step=compiles,
        losses_from_step_2=[round(x, 4) for x in plosses],
        peak_bytes=peak_bytes())
    if compiles[-1] != 0:
        raise AssertionError(
            "flagship_eager: the second pipelined step compiled "
            f"{compiles[-1]} new program(s)")
    for k, got in enumerate(plosses):
        if k + 1 < len(ref_losses) and \
                abs(got - ref_losses[k + 1]) > BF16_LOSS_TOL:
            raise AssertionError(
                f"flagship_eager: pipelined loss of step {k + 2} "
                f"{got} vs the jit path's {ref_losses[k + 1]} "
                f"(tolerance {BF16_LOSS_TOL})")
    return dict(losses=losses, pipelined_losses=plosses)


def resnet_step(shape: dict, mesh):
    """(model, optimizer, jitted step) of the ResNet-50 cell on
    `mesh`, built as bench.py's main builds it."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.models.resnet import ResNet, create_resnet50
    from horovod_tpu.parallel import build_train_step

    model = (ResNet(stage_sizes=list(shape["stages"]),
                    dtype=jnp.bfloat16) if shape.get("stages")
             else create_resnet50(dtype=jnp.bfloat16))

    def loss_fn(params, batch):
        logits, updates = model.apply(
            {"params": params, "batch_stats": batch["batch_stats"]},
            batch["images"], train=True, mutable=["batch_stats"])
        onehot = jax.nn.one_hot(batch["labels"], logits.shape[-1])
        loss = jnp.mean(
            -jnp.sum(onehot * jax.nn.log_softmax(logits), axis=-1))
        return loss, updates["batch_stats"]

    opt = optax.sgd(0.0125 * mesh.devices.size, momentum=0.9)
    step = build_train_step(
        loss_fn, opt, mesh,
        batch_spec={"images": P("data"), "labels": P("data"),
                    "batch_stats": P()},
        loss_has_aux=True, donate=True)
    return model, opt, step


def phase_resnet_jit(shape=RESNET, steps=4, seed=0, counter=None):
    """ResNet-50 through build_train_step with BN batch_stats
    threaded — bench.py's default cell."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from horovod_tpu.models.resnet import init_resnet
    from horovod_tpu.parallel.mesh import data_parallel_mesh

    mesh = data_parallel_mesh()
    n_chips = mesh.devices.size
    model, opt, step = resnet_step(shape, mesh)
    variables = init_resnet(model, jax.random.PRNGKey(seed),
                            shape["image"])
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt_state = opt.init(params)
    rng = np.random.default_rng(seed)
    gb = shape["batch"] * n_chips
    data_sh = NamedSharding(mesh, P("data"))
    images = jax.device_put(
        rng.standard_normal((gb, shape["image"], shape["image"], 3),
                            dtype=np.float32), data_sh)
    labels = jax.device_put(
        rng.integers(0, 1000, gb, dtype=np.int32), data_sh)
    stats0 = jax.device_put(batch_stats, NamedSharding(mesh, P()))

    def make_batch(aux):
        return {"images": images, "labels": labels,
                "batch_stats": stats0 if aux is None else aux}

    losses, _ = run_jit_steps("resnet50_jit", step, params, opt_state,
                                 make_batch, steps,
                                 counter or CompileCounter())
    return dict(losses=losses)


def pair_combine_case(n: int, seed: int = 0) -> dict:
    """Compile the library's dispatch-time pair-combine entry at `n`
    f32 elements and compare it with the jnp combine."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.ops import adasum, pallas_kernels

    ka, kb = jax.random.split(jax.random.PRNGKey(seed + n % 97))
    a = jax.random.normal(ka, (n,), jnp.float32)
    b = jax.random.normal(kb, (n,), jnp.float32) + 0.5 * a
    t0 = time.perf_counter()
    compiled = jax.jit(pallas_kernels.pair_combine).lower(a, b).compile()
    compile_s = time.perf_counter() - t0
    got = jax.block_until_ready(compiled(a, b))
    want = jax.block_until_ready(
        adasum._pair_combine(a, b, use_pallas=False))
    rec = dict(elements=n, nbytes=4 * n, compile_s=round(compile_s, 2),
               tpu_custom_call="tpu_custom_call" in compiled.as_text(),
               max_abs_err=float(jnp.max(jnp.abs(got - want))),
               peak_bytes=peak_bytes())
    say("adasum.pair_combine", **rec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)
    return rec


def phase_adasum(sizes=ADASUM_SIZES):
    """The Adasum Pallas pair-combine, compiled, against the jnp
    combine; then one hvd.allreduce(op=hvd.Adasum)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.ops import adasum

    for n in sizes:
        if not pair_combine_case(n)["tpu_custom_call"]:
            raise AssertionError(
                "adasum: the compiled program holds no tpu_custom_call")
    x = jnp.arange(4096, dtype=jnp.float32)
    out = jax.block_until_ready(hvd.allreduce(x, op=hvd.Adasum))
    picks = adasum._use_pallas()
    say("adasum.allreduce", size=hvd.size(), use_pallas=picks,
        note="one rank: the fold has a single contribution")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    if not picks:
        raise AssertionError(
            "adasum: _use_pallas() did not pick the kernel")
    return dict(use_pallas=picks)


def controller_core() -> str:
    from horovod_tpu.common.basics import _state
    ctl = _state.engine.controller if _state.engine else None
    return type(ctl.core).__name__ if ctl is not None else "inline"


def require_native_core() -> str:
    core = controller_core()
    if core != "NativeCore" and shutil.which("make") and \
            shutil.which(os.environ.get("CXX", "g++").split()[0]):
        raise AssertionError(
            f"controller core is {core}, not the C++ NativeCore, "
            "although make and a C++ compiler are present")
    return core


def start(tag: str) -> dict:
    """Common set-up of every process that touches the chip."""
    from horovod_tpu.common import compile_cache
    cache_dir = compile_cache.enable()
    dev = require_tpu()
    say(tag, device=dev, compile_cache_dir=cache_dir)
    return dev


def run_one_chip() -> dict:
    import horovod_tpu as hvd

    dev = start("start")
    # Force the negotiation stack at size 1 (auto would dispatch
    # inline): the C++ core, the response cache, fusion.
    t0 = time.perf_counter()
    hvd.init(config_overrides={"HOROVOD_CONTROLLER": "native"})
    say("init", seconds=round(time.perf_counter() - t0, 2),
        size=hvd.size(), controller_core=require_native_core())
    counter = CompileCounter()
    ref_losses = phase_flagship_jit(counter=counter)["losses"]
    phase_flagship_eager(ref_losses, counter=counter)
    phase_resnet_jit(counter=counter)
    phase_adasum()
    t0 = time.perf_counter()
    hvd.shutdown()
    say("shutdown", seconds=round(time.perf_counter() - t0, 2))
    return dev


# ---------------------------------------------------------------------------
# --multichip: each leg is a child of a parent that stays off JAX
# ---------------------------------------------------------------------------

def leg_dp(shape=FLAGSHIP) -> None:
    """One process, four chips, data parallel, shipped defaults."""
    import jax
    import horovod_tpu as hvd
    from horovod_tpu.parallel.mesh import data_parallel_mesh

    start("dp.start")
    hvd.init()
    n = len(jax.devices())
    wide = phase_flagship_jit(shape, steps=3)
    if len(set(wide["shard_devices"])) != n:
        raise AssertionError(
            f"dp: batch shards sit on {wide['shard_devices']}, not on "
            f"{n} distinct devices")
    from horovod_tpu.parallel.train import last_overlap_info
    buckets = last_overlap_info().get("buckets", 0)
    hlo = wide.pop("step_exec").as_text()
    n_ar = hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(")
    say("dp.collectives", buckets=buckets, all_reduce_ops=n_ar)
    if not (buckets > 0 and n_ar > 0):
        raise AssertionError(
            f"dp: buckets={buckets} all_reduce_ops={n_ar} on {n} chips")
    # The same global batch on a one-device mesh, same process.
    one = phase_flagship_jit(
        dict(shape, batch=shape["batch"] * n), steps=3,
        mesh=data_parallel_mesh(jax.devices()[:1]))
    one.pop("step_exec")
    for k, (a, b) in enumerate(zip(wide["losses"], one["losses"])):
        if abs(a - b) > BF16_LOSS_TOL:
            raise AssertionError(
                f"dp: step {k + 1} loss {a} on {n} chips vs {b} on one "
                f"(tolerance {BF16_LOSS_TOL})")
    say("dp.ok", losses_wide=wide["losses"], losses_one=one["losses"],
        tolerance=BF16_LOSS_TOL)
    hvd.shutdown()


def leg_eager() -> None:
    """One process, every local chip: each eager op against NumPy."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.ops import dispatch

    start("eager.start")
    hvd.init(config_overrides={"HOROVOD_CONTROLLER": "native"})
    core = require_native_core()
    n = hvd.size()
    x = np.arange(1 << 18, dtype=np.float32).reshape(-1, 16)
    got = hvd.allreduce(jnp.asarray(x), op=hvd.Sum)
    np.testing.assert_allclose(np.asarray(got), x * n)
    outs = hvd.grouped_allreduce(
        [jnp.asarray(x), jnp.asarray(x) * 2], op=hvd.Average)
    np.testing.assert_allclose(np.asarray(outs[0]), x)
    np.testing.assert_allclose(np.asarray(outs[1]), x * 2)
    got = hvd.allgather(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got),
                               np.concatenate([x] * n))
    got = hvd.reducescatter(jnp.asarray(x), op=hvd.Sum)
    np.testing.assert_allclose(np.asarray(got),
                               np.array_split(x * n, n)[hvd.rank()])
    got = hvd.alltoall(jnp.asarray(x))
    got = got[0] if isinstance(got, tuple) else got
    np.testing.assert_allclose(np.asarray(got), x)
    say("eager.ok", size=n, local_devices=len(jax.local_devices()),
        controller_core=core,
        allreduce_path=dispatch.last_allreduce_info().get("path"),
        result_devices=sorted(d.id for d in got.devices()))
    hvd.shutdown()


def leg_sharded(steps=3) -> None:
    """One process, four chips, expert 2 x tensor 2 MoE mesh: the
    sharded loss against the unsharded oracle."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding
    import __graft_entry__ as graft
    from horovod_tpu.models import flagship
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel import MeshSpec, build_mesh

    start("sharded.start")
    n = len(jax.devices())
    mesh = build_mesh(MeshSpec(data=n // 4, expert=2, tensor=2),
                      devices=jax.devices())
    cfg0 = dataclasses.replace(graft._flagship_cfg(jnp.float32, moe=True),
                               capacity_factor=8.0)
    cfg, params, opt_state, step = flagship.make_flagship(
        mesh, cfg0, optax.adam(1e-2))
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (8, 64), 0, cfg.vocab, jnp.int32))
    host = {"tokens": tokens, "targets": np.roll(tokens, -1, axis=1)}
    oracle = float(tfm.loss_fn(
        dataclasses.replace(cfg, tp_axis=None, sp_axis=None,
                            ep_axis=None),
        jax.tree.map(np.asarray, jax.device_get(params)), host))
    sh = NamedSharding(mesh, flagship.batch_spec(mesh))
    batch = {k: jax.device_put(v, sh) for k, v in host.items()}
    param_devices = sorted({d.id for p in jax.tree.leaves(params)
                            for d in p.devices()})
    losses = []
    for _ in range(steps):
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(jax.block_until_ready(m["loss"])))
    say("sharded.ok", mesh=dict(mesh.shape), oracle_loss=oracle,
        losses=losses, param_devices=param_devices)
    # f32 model; the chip's default matmul precision is below f32.
    np.testing.assert_allclose(losses[0], oracle, rtol=2e-2, atol=2e-2)
    check_losses("sharded", losses)
    if len(param_devices) != n:
        raise AssertionError(
            f"sharded: params sit on {param_devices}, not {n} devices")


def leg_rank() -> None:
    """Worker of `python -m horovod_tpu.runner -np 4 --per-chip`."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import horovod_tpu as hvd

    from horovod_tpu.common import compile_cache
    compile_cache.enable()
    hvd.init()
    r, n = hvd.rank(), hvd.size()
    local = jax.local_devices()
    if len(local) != 1 or local[0].platform != "tpu":
        raise AssertionError(
            f"rank {r}: expected one local TPU device, got {local}")
    if n != N_RANKS:
        raise AssertionError(f"rank {r}: hvd.size() == {n}")
    got = hvd.allreduce(jnp.full((1024,), float(r + 1)), op=hvd.Sum)
    np.testing.assert_array_equal(
        np.asarray(got), np.full(1024, n * (n + 1) / 2, np.float32))
    x = (jnp.arange(4096, dtype=jnp.float32) if r == 0
         else jnp.full((4096,), -1.0, jnp.float32))
    got = hvd.broadcast(x, root_rank=0)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.arange(4096, dtype=np.float32))
    opt = hvd.DistributedOptimizer(optax.sgd(0.1))
    params = {"w": jnp.ones((256, 256)), "b": jnp.zeros((256,))}
    opt_state = opt.init(params)
    batch = jnp.full((8, 256), float(r + 1))

    def loss_fn(p, b):
        return jnp.mean((b @ p["w"] + p["b"]) ** 2)

    losses = []
    for _ in range(2):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
    digest = float(jnp.sum(params["w"]))
    same = hvd.allgather(jnp.asarray([digest]))
    if float(jnp.max(same) - jnp.min(same)) != 0.0:
        raise AssertionError(
            f"rank {r}: params diverged across ranks: {same}")
    say("rank.ok", rank=r, size=n, local_device=str(local[0]),
        device=device_record(), controller_core=controller_core(),
        losses=losses)
    hvd.shutdown()


LEGS = {"dp": leg_dp, "eager": leg_eager, "sharded": leg_sharded,
        "rank": leg_rank}


def run_child(cmd, timeout: int) -> str:
    """Run one leg in its own process group; kill the whole group on
    timeout so no rank outlives the script. Returns its stdout."""
    proc = subprocess.Popen(cmd, cwd=HERE, text=True,
                            stdout=subprocess.PIPE,
                            start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait()
        raise RuntimeError(
            f"{' '.join(cmd[-3:])}: no end after {timeout}s; killed")
    sys.stdout.write(out)
    sys.stdout.flush()
    if proc.returncode != 0:
        raise RuntimeError(
            f"{' '.join(cmd[-3:])}: exit code {proc.returncode}")
    return out


def run_multichip() -> dict:
    me = os.path.abspath(__file__)
    out = ""
    for leg in ("dp", "eager", "sharded"):
        out += run_child([sys.executable, me, "--leg", leg], 1500)
    ranks = run_child(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", str(N_RANKS),
         "--per-chip", sys.executable, me, "--leg", "rank"], 300)
    if ranks.count('"phase": "rank.ok"') != N_RANKS:
        raise RuntimeError(
            f"per-chip leg: {ranks.count('rank.ok')} of {N_RANKS} ranks "
            "reported")
    first = next(json.loads(line) for line in out.splitlines()
                 if line.startswith('{"phase": "dp.start"'))
    return first["device"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run the four-chip legs and nothing else")
    ap.add_argument("--leg", choices=sorted(LEGS),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.leg:
        LEGS[args.leg]()
        return 0
    dev = run_multichip() if args.multichip else run_one_chip()
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
