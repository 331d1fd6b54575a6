#!/usr/bin/env python
"""ResNet-50 synthetic training benchmark — the BASELINE.md headline
metric (img/sec/chip), TPU-native equivalent of the reference's
examples/pytorch/pytorch_synthetic_benchmark.py.

Trains ResNet-50 (NHWC, bfloat16 compute) on synthetic ImageNet-shaped
data through the framework's own path: hvd lifecycle + the jitted
data-parallel train step (build_train_step over a data mesh — the same
program scales to a pod by adding devices; gradient reduction rides
XLA psum over ICI, no NCCL anywhere).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "img/sec/chip", "vs_baseline": 1.0,
   "platform": ..., "device_kind": ..., "device_count": N, ...}

Every line names the device it ran on. vs_baseline is the constant
1.0: no record taken from today's code on the device exists to divide
by (the benchmark PR defines the cells and their bounds).

MFU is reported to stderr from the XLA-compiled FLOP count and the
chip's peak (device_kind table below; a device that is not in the
table is an error). Profiling (`--profile` or BENCH_PROFILE=dir)
writes a jax.profiler trace.

Roofline context (measured on TPU v5e, 2026-07, trace in hand):
ResNet-50 training is ~24 GFLOP/img compiled (MAC=2, fwd+bwd). The
convolutions themselves run at ~76% MFU (~20 ms of a 47 ms bs-128
step); the other half is BatchNorm statistics/normalization
reductions (convert_reduce fusions, ~22 ms), which are pure HBM
bandwidth — reading ~3 GB of bf16 activations several times per step
against v5e's 819 GB/s. Net ~31% MFU, which is the known shape of
BN-ResNet on any accelerator (MLPerf-class TPU implementations land
in the same band); the headline img/sec cannot move much without
changing the model's BN structure, which the benchmark contract
forbids.

Env knobs: BENCH_BATCH (default 128), BENCH_STEPS (200),
BENCH_WARMUP (5), BENCH_IMAGE (224), BENCH_PROFILE (trace dir).

`--profile` (both jit benches + eager) wraps the MEASURED loop in a
jax.profiler capture and attaches horovod_tpu.profiling's parsed
digest — top-3 time sinks + per-category split (MXU / vector /
copy-reshape / collective / host gap) — to the JSON artifact, so a
recorded round says WHERE the time went, not just the rate. Every
artifact also carries `mfu` and `compiled_gflop_per_img`
(null when the backend can't supply them).

`--trajectory` consolidates the committed per-round artifacts into one
byte-deterministic benchmarks/BENCH_trajectory.json.

`--autotune` (with --model resnet50|transformer) runs the EAGER bench
under HOROVOD_AUTOTUNE=1 twice — hillclimb then gp — in subprocesses,
collects both HOROVOD_AUTOTUNE_LOG trajectories, then A/B-times the
tuner's best config against the shipped defaults and writes one
self-contained artifact (BENCH_AUTOTUNE_OUT, default
benchmarks/AUTOTUNE_<model>_eager_r08.json).
"""

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from horovod_tpu.common import compile_cache  # noqa: E402

compile_cache.enable()

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.models.resnet import create_resnet50, init_resnet  # noqa: E402
from horovod_tpu.parallel import build_train_step  # noqa: E402
from horovod_tpu.parallel.aot import aot_compile  # noqa: E402
from horovod_tpu.parallel.mesh import data_parallel_mesh  # noqa: E402


def log(msg):
    print(msg, file=sys.stderr, flush=True)


# Peak dense bf16 TFLOP/s by PJRT device_kind (public spec sheets).
_PEAK_TFLOPS = {
    "TPU v5 lite": 197.0,   # v5e
    "TPU v5e": 197.0,
    "TPU v5": 459.0,        # v5p
    "TPU v5p": 459.0,
    "TPU v4": 275.0,
    "TPU v6e": 918.0,       # Trillium
    "TPU v6 lite": 918.0,
}


def device_peak_tflops():
    """Peak of the device this run is on. None on the CPU, where no
    utilization is ever computed (mfu stays null); an accelerator
    that is not in the table is an error, not a default."""
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    for k, v in _PEAK_TFLOPS.items():
        if dev.device_kind.startswith(k):
            return v
    raise ValueError(
        f"bench: no peak FLOP/s on record for device_kind "
        f"{dev.device_kind!r} (platform {dev.platform!r}); add it to "
        "_PEAK_TFLOPS with its source — a utilization against an "
        "unknown peak is not a number")


def emit(doc: dict) -> None:
    """Print one result line, stamped with the device it ran on
    (sorted keys: --trajectory's line is on a byte-pinned path)."""
    dev = jax.devices()[0]
    doc.update(platform=dev.platform, device_kind=dev.device_kind,
               device_count=len(jax.devices()))
    print(json.dumps(doc, sort_keys=True), flush=True)


def _metrics_snapshot():
    """Compact hvd.metrics() digest for the JSON artifact: counters
    and gauges summed across label sets, histograms as count/sum —
    so a round's recorded benchmark carries the runtime's own
    accounting (bytes moved, batches fused, programs compiled,
    stalls) alongside the headline rate."""
    try:
        snap = hvd.metrics()
    except Exception as e:  # pragma: no cover - defensive
        log(f"bench: metrics snapshot unavailable ({e})")
        return {}
    out = {}
    for name, series in snap.items():
        total, count, hsum = 0.0, 0, 0.0
        is_hist = False
        for v in series.values():
            if isinstance(v, dict):
                is_hist = True
                count += v["count"]
                hsum += v["sum"]
            else:
                total += v
        if is_hist:
            if count:
                out[name + "_count"] = count
                out[name + "_sum"] = round(hsum, 6)
        elif total:
            out[name] = round(total, 6)
    return out


def _trace_digest():
    """Compact tracing digest for the JSON artifact: negotiation-skew
    p50/p99 (the runtime face of the merged straggler report) and
    per-phase span totals from the flight-recorder ring — so a
    recorded round carries WHERE the time went, not just the rate.
    Merged-format trace runs (benchmarks/TIMELINE_*) additionally set
    HOROVOD_TIMELINE and fuse the per-rank files afterwards with
    `hvdrun --timeline-merge`."""
    try:
        from horovod_tpu import tracing
        return tracing.trace_digest()
    except Exception as e:  # pragma: no cover - defensive
        log(f"bench: trace digest unavailable ({e})")
        return {}


def _journal_digest():
    """Compact lifecycle-journal digest for the JSON artifact: event
    counts by type from this process's own journal ({'enabled':
    False} in the common un-journaled bench run) — a chaos bench run
    under HOROVOD_JOURNAL_DIR carries its recovery accounting in the
    same artifact as its rate."""
    try:
        from horovod_tpu import journal
        return journal.journal_digest()
    except Exception as e:  # pragma: no cover - defensive
        log(f"bench: journal digest unavailable ({e})")
        return {}


def _health_digest(dir_=None):
    """Compact continuous-telemetry digest for the JSON artifact:
    sample/alert/anomaly counts from the time-series shards
    ({'enabled': False} in the common un-recorded bench run) — a run
    under HOROVOD_TELEMETRY_DIR carries its health-plane verdict in
    the same artifact as its rate."""
    try:
        from horovod_tpu import telemetry
        return telemetry.health_digest(dir_)
    except Exception as e:  # pragma: no cover - defensive
        log(f"bench: health digest unavailable ({e})")
        return {}


def _profile_block(profile_dir):
    """The `profile` digest every artifact carries (null when no
    capture ran): top-3 sinks + category split, parsed from the
    capture's XPlane by horovod_tpu.profiling."""
    if not profile_dir:
        return None
    try:
        from horovod_tpu import profiling
        return profiling.profile_digest_block(profile_dir, top=3)
    except Exception as e:  # pragma: no cover - defensive
        log(f"bench: profile digest unavailable ({e})")
        return {"error": str(e)}


def _profile_requested() -> str:
    """BENCH_PROFILE dir, or the default dir under --profile."""
    profile_dir = os.environ.get("BENCH_PROFILE", "")
    if "--profile" in sys.argv:
        profile_dir = profile_dir or "/tmp/hvdtpu_bench_trace"
    return profile_dir


def _mfu(rate_per_chip: float, gflop_per_unit, peak: float):
    """MFU from a per-chip rate and a per-unit (img/token) GFLOP
    count; None when either input is unknown — a null in the JSON
    says 'not computable here' instead of a fake 0."""
    if not gflop_per_unit or not peak:
        return None
    return round(rate_per_chip * gflop_per_unit / 1e3 / peak, 4)


def _make_reduced_resnet(stages: str):
    """Reduced-depth ResNet for multi-process CPU runs (8 procs
    compiling full ResNet-50 on shared cores takes tens of minutes;
    the mesh/collective accounting being validated is
    depth-independent)."""
    from horovod_tpu.models.resnet import ResNet
    return ResNet(stage_sizes=[int(s) for s in stages.split(",")],
                  dtype=jnp.bfloat16)


def eager_main(model_name: str = "resnet50"):
    """Eager/negotiated-path benchmark: the reference's torch-hook
    mechanism (reference: horovod/torch/optimizer.py
    _DistributedOptimizer._make_hook — one allreduce_async_ per
    parameter, named by the parameter, synchronize() before step)
    driven through THIS framework's native C++ controller with the
    response cache, tensor fusion, and fp16 compression all active.

    Same ResNet-50 / synthetic-data contract as the jit bench so the
    eager-vs-jit gap is directly comparable: gradient compute and the
    optimizer update are jitted (the reference's backward/step are
    compiled kernels too); ONLY the collective path is eager.

    Two shapes (BENCH_EAGER_MODE / --eager-hooks):
      grouped (default): hvd.DistributedOptimizer's eager path — ONE
        grouped allreduce of the whole gradient pytree per step. The
        negotiation unit is stable, so the fused kernel (compress +
        concat + reduce + split + decompress in one XLA program)
        compiles once and steady state costs ~3 launches/step.
      hooks: the reference's per-parameter hook storm (one
        allreduce_async per tensor, reverse layer order). Under XLA
        this is the WORST case: every ragged cycle boundary yields a
        new batch composition = a new compiled program. The recorded
        gap vs grouped is the measured argument for why the TPU eager
        API defaults to grouped submission (docs/benchmarks.md).

    Round-5 knobs (BENCH_transformer_eager_r05.json):
      BENCH_EAGER_COMPRESSION=fp16|bf16|none — wire dtype (bf16 is
        the TPU-native choice: free cast for bf16 models).
      BENCH_EAGER_PIPELINED=1 — the hvd.make_pipelined_step pattern
        (optimizer apply fused into the next step's grad program);
        with bf16 wire this benches the flagship transformer at
        1.00x the jit step.
      BENCH_REMAT_MODE=full|mlp_only — transformer remat policy
        (mlp_only saves attention residuals; see
        BENCH_flash_remat_r05.json).
    """
    transformer = model_name == "transformer"
    batch_per_chip = int(os.environ.get(
        "BENCH_BATCH", "16" if transformer else "128"))
    steps = int(os.environ.get("BENCH_STEPS", "60"))
    warmup = int(os.environ.get("BENCH_WARMUP", "5"))
    image = int(os.environ.get("BENCH_IMAGE", "224"))
    seq = int(os.environ.get("BENCH_SEQ", "512"))
    # BASELINE.md config 4 (Llama-class DP + Adasum + fp16): op=Adasum
    # routes every grouped/hook submission through the negotiated
    # Adasum path (vhdd schedule multi-rank; single-rank it still
    # exercises the wire compression round-trip).
    adasum = ("--eager-adasum" in sys.argv or
              os.environ.get("BENCH_EAGER_OP", "") == "adasum")

    # Force the full negotiation stack even at size 1 (auto mode would
    # inline-dispatch): native core, response cache, fusion.
    os.environ.setdefault("HOROVOD_CONTROLLER", "native")
    # Cycle pacing matters far more under XLA than in the reference:
    # a fused batch is a compiled program keyed on its composition, so
    # ragged cycle boundaries = new compositions = recompiles every
    # step. A cycle long enough to gather the whole backward pass
    # yields ONE stable composition (161 tensors, ~50MB fp16 wire —
    # under the 64MiB fusion threshold), compiled once. This is the
    # knob the reference's ParameterManager tunes as cycle-time; the
    # eager autotuner here reaches the same region.
    hooks_mode = ("--eager-hooks" in sys.argv or
                  os.environ.get("BENCH_EAGER_MODE", "") == "hooks")
    os.environ.setdefault(
        "HOROVOD_CYCLE_TIME", os.environ.get("BENCH_CYCLE_MS", "2"))
    if hooks_mode:
        # Quiescence batching: hold the cut until the per-parameter
        # storm stops growing, so the fused batch has ONE stable
        # composition (= one compiled program) instead of a ragged,
        # recompiling-every-step composition.
        os.environ.setdefault("HOROVOD_BATCH_QUIESCENCE", "5")
    hvd.init()
    from horovod_tpu.core import native as _native
    from horovod_tpu.ops.compression import Compression
    import horovod_tpu.ops.collective_ops as C
    from horovod_tpu.common.basics import _state
    ctl = _state.engine.controller
    core_kind = type(ctl.core).__name__ if ctl is not None else "inline"
    log(f"bench[eager]: controller core={core_kind} "
        f"native_available={_native.available()} size={hvd.size()}")

    vgg = model_name == "vgg16"
    tfm_cfg = None
    if transformer:
        # BASELINE.md config 3 (BERT-Large-class fp16+fusion stress)
        # on the EAGER path: same dims/optimizer as the jit
        # transformer bench so the gap is directly comparable.
        from horovod_tpu.models import transformer as tfm
        tfm_cfg = tfm.TransformerConfig(
            vocab=int(os.environ.get("BENCH_TFM_VOCAB", "32768")),
            d_model=int(os.environ.get("BENCH_TFM_DMODEL", "1024")),
            n_layers=int(os.environ.get("BENCH_TFM_LAYERS", "24")),
            n_heads=int(os.environ.get("BENCH_TFM_HEADS", "16")),
            n_kv_heads=int(os.environ.get("BENCH_TFM_HEADS", "16")),
            head_dim=int(os.environ.get("BENCH_TFM_DMODEL", "1024"))
            // int(os.environ.get("BENCH_TFM_HEADS", "16")),
            d_ff=int(os.environ.get("BENCH_TFM_FF", "4096")),
            max_seq=seq,
            moe=False, dtype=jnp.bfloat16, remat=True,
            remat_mode=os.environ.get("BENCH_REMAT_MODE", "full"),
            tp_axis=None, sp_axis=None, ep_axis=None)
        params = tfm.init_params(tfm_cfg, jax.random.PRNGKey(0))
        batch_stats = {}
        model = None
    elif vgg:
        # Multi-fusion-batch stress: ~276 MB fp16 wire/step spans
        # several 64 MiB fusion buffers per cycle.
        from horovod_tpu.models.vgg import create_vgg16, init_vgg
        model = create_vgg16(dtype=jnp.bfloat16)
        variables = init_vgg(model, jax.random.PRNGKey(0), image)
        params, batch_stats = variables["params"], {}
    else:
        stages = os.environ.get("BENCH_RESNET_STAGES", "")
        model = (_make_reduced_resnet(stages) if stages
                 else create_resnet50(dtype=jnp.bfloat16))
        variables = init_resnet(model, jax.random.PRNGKey(0), image)
        params, batch_stats = (variables["params"],
                               variables["batch_stats"])

    def loss_fn(params, batch_stats, images, labels):
        if transformer:
            from horovod_tpu.models import transformer as tfm
            loss = tfm.loss_fn(tfm_cfg, params,
                               {"tokens": images, "targets": labels})
            return loss, {}
        if vgg:
            logits = model.apply({"params": params}, images,
                                 train=True)
            new_stats = {}
        else:
            logits, updates = model.apply(
                {"params": params, "batch_stats": batch_stats},
                images, train=True, mutable=["batch_stats"])
            new_stats = updates["batch_stats"]
        onehot = jax.nn.one_hot(labels, logits.shape[-1])
        loss = jnp.mean(
            -jnp.sum(onehot * jax.nn.log_softmax(logits), axis=-1))
        return loss, new_stats

    grad_fn = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))

    opt = (optax.adamw(1e-4) if transformer
           else optax.sgd(0.0125 * hvd.size(), momentum=0.9))
    opt_state = opt.init(params)

    flat0, treedef = jax.tree_util.tree_flatten_with_path(params)
    # Stable per-parameter names (the response cache keys on them; the
    # reference names hook allreduces after the parameter).
    names = ["DistributedOptimizer.allreduce/" +
             "/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in flat0]
    n_leaves = len(names)

    # donate params/opt_state: the adamw moments (3.5 GB f32 for the
    # flagship) update in place instead of into fresh buffers — the
    # same donation the jit train step's compiled program gets.
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def apply_fn(params, opt_state, reduced_leaves):
        grads = jax.tree_util.tree_unflatten(treedef, reduced_leaves)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    # BENCH_EAGER_PIPELINED=1: fuse step i's optimizer apply with step
    # i+1's grad into ONE program (apply-then-grad), keeping the
    # eager collective between grad output and the next call. On TPU,
    # programs serialize on the device, so a separate apply program's
    # HBM traffic (~8.7 GB for the flagship's adamw moments) cannot
    # hide under compute; fused with the next step's backward it can —
    # the same latency hiding the jit path gets. The warmup performs
    # one zero-grad apply (skipped via an is-first flag so adamw's
    # weight decay is not spuriously applied).
    pipelined = (os.environ.get("BENCH_EAGER_PIPELINED") == "1"
                 and not hooks_mode)

    @functools.partial(jax.jit, donate_argnums=(1, 2),
                       static_argnames=("first",))
    def apply_grad_fn(reduced_leaves, opt_state, params, batch_stats,
                      first=False):
        if not first:
            grads_in = jax.tree_util.tree_unflatten(
                treedef, reduced_leaves)
            updates, opt_state = opt.update(grads_in, opt_state,
                                            params)
            params = optax.apply_updates(params, updates)
        (loss, batch_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats, images, labels)
        return params, opt_state, batch_stats, loss, grads

    rng = np.random.default_rng(0)
    if transformer:
        tokens = jnp.asarray(
            rng.integers(0, tfm_cfg.vocab, (batch_per_chip, seq)),
            jnp.int32)
        images, labels = tokens, jnp.roll(tokens, -1, axis=1)
    else:
        images = jnp.asarray(
            rng.standard_normal((batch_per_chip, image, image, 3),
                                dtype=np.float32))
        labels = jnp.asarray(
            rng.integers(0, 1000, batch_per_chip), jnp.int32)

    rop = hvd.Adasum if adasum else None
    # BENCH_EAGER_COMPRESSION: fp16 (default; the reference's GPU wire
    # dtype, BASELINE config 3), bf16 (the TPU-native wire dtype — for
    # a bf16 model wire == raw, so the compress roundtrip vanishes and
    # multi-rank wire bytes still halve vs f32), none (isolates the
    # roundtrip's cost).
    comp_name = os.environ.get("BENCH_EAGER_COMPRESSION", "fp16")
    try:
        comp = {"none": Compression.none, "bf16": Compression.bf16,
                "fp16": Compression.fp16}[comp_name]
    except KeyError:
        sys.exit(f"bench: BENCH_EAGER_COMPRESSION must be "
                 f"none/bf16/fp16, got {comp_name!r}")
    log(f"bench[eager]: mode={'hooks' if hooks_mode else 'grouped'}"
        f" op={'Adasum' if adasum else 'Average'}"
        f" compression={comp.__name__}")

    phase_times = os.environ.get("BENCH_PHASE_TIMES")

    def run_step(params, opt_state, batch_stats):
        t0 = time.perf_counter()
        (loss, batch_stats), grads = grad_fn(
            params, batch_stats, images, labels)
        leaves = jax.tree_util.tree_flatten(grads)[0]
        t1 = time.perf_counter()
        if hooks_mode:
            # Reverse-layer-order storm, exactly like backward hooks.
            handles = [None] * n_leaves
            for i in range(n_leaves - 1, -1, -1):
                handles[i] = C.allreduce_async(
                    leaves[i], name=names[i], op=rop,
                    compression=comp)
            t2 = time.perf_counter()
            reduced = [C.synchronize(h) for h in handles]
            if phase_times:
                t3 = time.perf_counter()
                log(f"bench[eager]: phases grad={t1-t0:.3f} "
                    f"submit={t2-t1:.3f} sync={t3-t2:.3f}")
        else:
            # hvd.DistributedOptimizer eager mechanism: one grouped
            # submission of the whole gradient tree (stable fused
            # composition, response-cache-friendly stable name).
            reduced = C.grouped_allreduce(
                leaves, name="DistributedOptimizer.grouped_allreduce",
                op=rop, compression=comp)
        params, opt_state = apply_fn(params, opt_state, reduced)
        return params, opt_state, batch_stats, loss

    def step_pipe(params, opt_state, batch_stats, grads):
        leaves = jax.tree_util.tree_flatten(grads)[0]
        reduced = C.grouped_allreduce(
            leaves, name="DistributedOptimizer.grouped_allreduce",
            op=rop, compression=comp)
        return apply_grad_fn(reduced, opt_state, params, batch_stats)

    t_c0 = time.perf_counter()
    if pipelined:
        params, opt_state, batch_stats, loss, grads = apply_grad_fn(
            None, opt_state, params, batch_stats, first=True)
        for _ in range(warmup):
            params, opt_state, batch_stats, loss, grads = step_pipe(
                params, opt_state, batch_stats, grads)
    else:
        for _ in range(warmup):
            params, opt_state, batch_stats, loss = run_step(
                params, opt_state, batch_stats)
    log(f"bench[eager]: warmup ({warmup} steps, compiles) "
        f"{time.perf_counter() - t_c0:.1f}s loss={float(loss):.3f} "
        f"leaves={n_leaves}")
    cycles0 = ctl.core.cycles() if ctl is not None else 0
    ctrl0 = ctl.core.control_bytes() if ctl is not None else 0

    profile_dir = _profile_requested()
    profiler_cm = (jax.profiler.trace(profile_dir) if profile_dir
                   else None)
    if profiler_cm is not None:
        profiler_cm.__enter__()
    t0 = time.perf_counter()
    tprev = t0
    for i in range(steps):
        if pipelined:
            params, opt_state, batch_stats, loss, grads = step_pipe(
                params, opt_state, batch_stats, grads)
        else:
            params, opt_state, batch_stats, loss = run_step(
                params, opt_state, batch_stats)
        if os.environ.get("BENCH_STEP_TIMES"):
            jax.block_until_ready(loss)
            tnow = time.perf_counter()
            log(f"bench[eager]: step {i} {tnow - tprev:.2f}s")
            tprev = tnow
    final_loss = float(loss)
    dt = time.perf_counter() - t0
    if profiler_cm is not None:
        profiler_cm.__exit__(None, None, None)
        log(f"bench[eager]: profiler trace written to {profile_dir}")

    if transformer:
        rate = batch_per_chip * seq * steps / dt
        unit = "tokens/sec/chip"
    else:
        rate = batch_per_chip * steps / dt
        unit = "img/sec/chip"
    log(f"bench[eager]: {steps} steps in {dt:.2f}s -> "
        f"{rate:.1f} {unit} loss={final_loss:.3f}")
    if ctl is not None:
        cyc = ctl.core.cycles() - cycles0
        cb = ctl.core.control_bytes() - ctrl0
        counts = dict(ctl.exec_counts)
        log(f"bench[eager]: negotiation cycles={cyc} "
            f"({cyc / max(steps, 1):.1f}/step) control_bytes={cb} "
            f"({cb / max(steps, 1):.0f}/step) exec_counts={counts}")
    mname = ("flagship_transformer" if transformer
             else "vgg16" if vgg else "resnet50")
    suffix = "_adasum" if adasum else ""
    metric = (f"flagship_transformer_eager{suffix}_tok_sec_per_chip"
              if transformer else
              f"{mname}_synthetic_eager{suffix}_img_sec_per_chip")
    peak = device_peak_tflops()
    if transformer:
        # Analytic FLOPs/token (same accounting as transformer_main;
        # XLA's scan-undercount makes the compiled number useless for
        # deep models).
        n_mm = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params)
                   if getattr(p, "ndim", 0) >= 2)
        fwd = 2 * n_mm + 4 * tfm_cfg.n_layers * seq * tfm_cfg.d_model
        gflop_unit = round(4 * fwd / 1e9, 4)   # fwd+bwd+remat
    else:
        gflop_unit = None  # no compiled count on the eager path
    emit({
        "metric": metric,
        "value": round(rate, 2),
        "unit": unit,
        "vs_baseline": 1.0,
        "mfu": _mfu(rate, gflop_unit, peak),
        "compiled_gflop_per_img": gflop_unit,
        "profile": _profile_block(profile_dir),
        "metrics": _metrics_snapshot(),
        "trace": _trace_digest(),
        "journal": _journal_digest(),
        "health": _health_digest(),
    })


def transformer_main():
    """Second headline: matmul-dominated flagship transformer
    (BERT-Large dims: 24 x d1024 x h16, ff 4096, seq 512, bf16) on the
    jitted DP path — tokens/sec/chip and MFU. Proves the framework
    isn't the bottleneck behind the BN-bound ResNet number (reference:
    docs/benchmarks.rst methodology; BASELINE.md config 3)."""
    from horovod_tpu.models import transformer as tfm

    batch_per_chip = int(os.environ.get("BENCH_BATCH", "16"))
    steps = int(os.environ.get("BENCH_STEPS", "60"))
    warmup = int(os.environ.get("BENCH_WARMUP", "5"))
    seq = int(os.environ.get("BENCH_SEQ", "512"))
    profile_dir = _profile_requested()

    hvd.init()
    mesh = data_parallel_mesh()
    n_chips = mesh.devices.size
    global_batch = batch_per_chip * n_chips
    log(f"bench[transformer]: devices={n_chips} global_batch="
        f"{global_batch} seq={seq}")

    # BENCH_REMAT=0 disables activation recompute entirely — the
    # no-remat ceiling leg of the remat-tax A/B (pick a BENCH_BATCH
    # that fits; the flagship at bs16/seq512 stores ~12 GB of
    # residuals without remat on a 16 GB chip, so bs8 is the fitting
    # point there). BENCH_TFM_LAYERS/DMODEL/FF/HEADS/VOCAB shrink the
    # model for CPU-container runs (defaults = flagship dims).
    cfg = tfm.TransformerConfig(
        vocab=int(os.environ.get("BENCH_TFM_VOCAB", "32768")),
        d_model=int(os.environ.get("BENCH_TFM_DMODEL", "1024")),
        n_layers=int(os.environ.get("BENCH_TFM_LAYERS", "24")),
        n_heads=int(os.environ.get("BENCH_TFM_HEADS", "16")),
        n_kv_heads=int(os.environ.get("BENCH_TFM_HEADS", "16")),
        head_dim=int(os.environ.get("BENCH_TFM_DMODEL", "1024"))
        // int(os.environ.get("BENCH_TFM_HEADS", "16")),
        d_ff=int(os.environ.get("BENCH_TFM_FF", "4096")),
        max_seq=seq,
        moe=False, dtype=jnp.bfloat16,
        remat=os.environ.get("BENCH_REMAT", "1") != "0",
        remat_mode=os.environ.get("BENCH_REMAT_MODE", "full"),
        tp_axis=None, sp_axis=None, ep_axis=None)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    log(f"bench[transformer]: {n_params / 1e6:.1f}M params")

    opt = optax.adamw(1e-4)
    opt_state = opt.init(params)
    step = build_train_step(
        lambda p, b: tfm.loss_fn(cfg, p, b), opt, mesh,
        batch_spec={"tokens": P("data"), "targets": P("data")},
        donate=True)

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab, (global_batch, seq)), jnp.int32)
    data_sh = NamedSharding(mesh, P("data"))
    tokens = jax.device_put(tokens, data_sh)
    batch = {"tokens": tokens,
             "targets": jnp.roll(tokens, -1, axis=1)}

    step_exec, flops_per_step = aot_compile(
        step, params, opt_state, batch)

    t_c0 = time.perf_counter()
    for _ in range(warmup):
        params, opt_state, metrics = step_exec(params, opt_state, batch)
    log(f"bench[transformer]: warmup {warmup} steps "
        f"{time.perf_counter() - t_c0:.1f}s "
        f"loss={float(metrics['loss']):.3f}")

    profiler_cm = (jax.profiler.trace(profile_dir) if profile_dir
                   else None)
    if profiler_cm is not None:
        profiler_cm.__enter__()
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, metrics = step_exec(params, opt_state, batch)
    final_loss = float(metrics["loss"])
    dt = time.perf_counter() - t0
    if profiler_cm is not None:
        profiler_cm.__exit__(None, None, None)
        log(f"bench[transformer]: profiler trace written to "
            f"{profile_dir}")

    tok_sec_chip = global_batch * seq * steps / dt / n_chips
    log(f"bench[transformer]: {steps} steps in {dt:.2f}s -> "
        f"{tok_sec_chip:.0f} tokens/sec/chip loss={final_loss:.3f}")
    peak = device_peak_tflops()
    # Analytic training FLOPs/token: XLA's cost_analysis counts a
    # lax.scan body ONCE (and remat regions not at all), so the
    # compiled number undercounts deep models by ~n_layers x. Matmul
    # params: 2 FLOP/param fwd, 2x that in bwd, +1 fwd under remat;
    # attention scores add 2*2*L*D per token per layer (causal ~halves
    # it; keep the conservative full count).
    n_mm = sum(int(np.prod(p.shape))
               for path, p in
               jax.tree_util.tree_flatten_with_path(params)[0]
               if p.ndim >= 2)
    fwd_per_tok = 2 * n_mm + 4 * cfg.n_layers * seq * cfg.d_model
    mult = 3 + (1 if cfg.remat else 0)
    analytic_per_tok = mult * fwd_per_tok
    mfu = 0.0
    if peak:
        compiled_tok = (flops_per_step / (global_batch * seq)
                        if flops_per_step else 0.0)
        per_tok = max(compiled_tok, analytic_per_tok)
        achieved = per_tok * tok_sec_chip / 1e12
        mfu = achieved / peak
        log(f"bench[transformer]: MFU {mfu * 100:.1f}% "
            f"({achieved:.1f} of {peak:.0f} TFLOP/s/chip; "
            f"{analytic_per_tok / 1e9:.2f} GFLOP/token analytic, "
            f"{compiled_tok / 1e9:.2f} compiled)")
    # The remat tax, decomposed in the artifact itself: `mfu` counts
    # the recompute FLOPs the hardware actually executed (mult =
    # 3+remat — "hardware MFU"); `mfu_model_flops` counts only the
    # model's 3x fwd+bwd ("model MFU" — the number a no-remat run of
    # the same rate would earn). Their gap IS the remat tax; see
    # docs/benchmarks.md "The transformer remat tax".
    emit({
        "metric": "flagship_transformer_tok_sec_per_chip",
        "value": round(tok_sec_chip, 2),
        "unit": "tokens/sec/chip",
        "vs_baseline": 1.0,
        "mfu": round(mfu, 4) if mfu else None,
        "mfu_model_flops": (round(mfu * 3.0 / mult, 4) if mfu
                            else None),
        "remat": {"enabled": bool(cfg.remat),
                  "mode": cfg.remat_mode,
                  "flop_mult": mult},
        "compiled_gflop_per_img": (
            round(flops_per_step / (global_batch * seq) / 1e9, 4)
            if flops_per_step else None),
        "analytic_gflop_per_token": round(analytic_per_tok / 1e9, 4),
        "profile": _profile_block(profile_dir),
        "metrics": _metrics_snapshot(),
        "trace": _trace_digest(),
        "journal": _journal_digest(),
        "health": _health_digest(),
    })


def autotune_main(model: str) -> None:
    """`--autotune`: the parameter manager demonstrated on the real
    bench instead of unit tests (reference: ParameterManager proven
    on workloads, SURVEY §2.1). Runs the EAGER bench as subprocesses
    (each leg needs its own hvd.init with its own knob env):

      leg 1/2 — HOROVOD_AUTOTUNE=1 with hillclimb, then gp; each
        leg's HOROVOD_AUTOTUNE_LOG trajectory is collected verbatim.
      leg 3/4 — the A/B that gates shipped defaults: the tuner's
        best-scoring config (knobs pinned, tuner OFF) vs the shipped
        defaults, same step budget. `defaults_updated` in the
        artifact records the verdict; common/config.py changes iff
        the tuned leg wins the throughput A/B.

    One self-contained artifact lands at BENCH_AUTOTUNE_OUT (default
    benchmarks/AUTOTUNE_<model>_eager_r08.json)."""
    import subprocess
    import tempfile

    from horovod_tpu.common.config import knob_default

    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.environ.get("BENCH_AUTOTUNE_OUT") or os.path.join(
        here, "benchmarks", f"AUTOTUNE_{model}_eager_r08.json")
    steps = int(os.environ.get("BENCH_STEPS", "240"))
    per_sample = os.environ.get(
        "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE", "5")

    def run_leg(extra_env, tag):
        env = {k: v for k, v in os.environ.items()}
        env.update(extra_env)
        env["BENCH_STEPS"] = str(steps)
        cmd = [sys.executable, os.path.join(here, "bench.py"),
               "--eager", "--model", model]
        t0 = time.perf_counter()
        proc = subprocess.run(cmd, env=env, capture_output=True,
                              text=True, timeout=7200)
        wall = time.perf_counter() - t0
        result = None
        for line in reversed(proc.stdout.strip().splitlines()):
            try:
                result = json.loads(line)
                break
            except ValueError:
                continue
        if proc.returncode != 0 or result is None:
            tail = proc.stderr.strip().splitlines()[-8:]
            raise RuntimeError(
                f"autotune leg {tag!r} failed (rc={proc.returncode}): "
                + " | ".join(tail))
        log(f"bench[autotune]: leg {tag}: {result['value']} "
            f"{result['unit']} in {wall:.0f}s")
        return {"wall_s": round(wall, 1),
                "value": result["value"],
                "unit": result["unit"]}

    doc = {"model": model, "steps_per_leg": steps, "modes": {}}
    best = None           # (score, fusion, cycle, quiesce, mode)
    for mode in ("hillclimb", "gp"):
        fd, csv_path = tempfile.mkstemp(suffix=".csv",
                                        prefix=f"autotune_{mode}_")
        os.close(fd)
        leg = run_leg({"HOROVOD_AUTOTUNE": "1",
                       "HOROVOD_AUTOTUNE_MODE": mode,
                       "HOROVOD_AUTOTUNE_LOG": csv_path,
                       "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": per_sample},
                      mode)
        rows = []
        with open(csv_path) as f:
            header = f.readline().strip().split(",")
            for line in f:
                vals = line.strip().split(",")
                if len(vals) == len(header):
                    rows.append({k: float(v) for k, v in
                                 zip(header, vals)})
        os.unlink(csv_path)
        mode_best = max(rows, key=lambda r: r["score_bytes_per_sec"],
                        default=None)
        if mode_best is not None and (
                best is None or
                mode_best["score_bytes_per_sec"] > best[0]):
            best = (mode_best["score_bytes_per_sec"],
                    int(mode_best["fusion_threshold"]),
                    mode_best["cycle_time_ms"],
                    int(mode_best["quiescence"]), mode)
        doc["modes"][mode] = {"bench": leg, "samples": len(rows),
                              "best": mode_best, "trajectory": rows}
        log(f"bench[autotune]: {mode}: {len(rows)} samples, best "
            f"{mode_best}")

    defaults = {"fusion_threshold":
                knob_default("HOROVOD_FUSION_THRESHOLD"),
                "cycle_time_ms": knob_default("HOROVOD_CYCLE_TIME"),
                "quiescence": knob_default("HOROVOD_BATCH_QUIESCENCE")}
    ab = {"default_config": dict(defaults),
          "tuned_best": None, "note":
          "tuner produced no scored samples"}
    if best is not None:
        score, fusion, cycle, quiesce, mode = best
        tuned = {"fusion_threshold": fusion, "cycle_time_ms": cycle,
                 "quiescence": quiesce, "found_by": mode,
                 "score_bytes_per_sec": score}
        a = run_leg({"HOROVOD_AUTOTUNE": ""}, "ab_default")
        b = run_leg({"HOROVOD_AUTOTUNE": "",
                     "HOROVOD_FUSION_THRESHOLD": str(fusion),
                     "HOROVOD_CYCLE_TIME": str(cycle),
                     "HOROVOD_BATCH_QUIESCENCE": str(quiesce)},
                    "ab_tuned")
        delta = (b["value"] / a["value"] - 1) * 100 if a["value"] \
            else 0.0
        ab = {"default_config": {**defaults, **a},
              "tuned_best": {**tuned, **b},
              "delta_pct": round(delta, 2),
              "winner": "tuned" if delta > 0 else "default"}
        log(f"bench[autotune]: A/B default={a['value']} "
            f"tuned={b['value']} ({delta:+.2f}%)")
    doc["ab"] = ab
    doc["defaults_updated"] = False   # flipped by hand iff the tuned
    #                                   config wins reproducibly —
    #                                   see docs/benchmarks.md
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    log(f"bench[autotune]: artifact written to {out_path}")
    emit({
        "metric": f"{model}_eager_autotune_ab_delta_pct",
        "value": ab.get("delta_pct", 0.0),
        "unit": "percent",
        "vs_baseline": 1.0,
    })


def _regen_serving_attribution(here):
    """Regenerate benchmarks/SERVING_ATTRIBUTION_r16.json from the
    COMMITTED trace recording (benchmarks/serving_trace_r16/): a pure
    function of those bytes, so reruns are byte-identical — and
    `doctor serve` on the same directory produces the same bytes as
    its in-dir serving_report.json. Returns the report, or None when
    no recording is committed."""
    from horovod_tpu import journal as hjournal
    from horovod_tpu import serving_trace as hserving_trace

    record_dir = os.environ.get("BENCH_SERVING_RECORD_DIR") \
        or os.path.join(here, "benchmarks", "serving_trace_r16")
    out = os.environ.get("BENCH_SERVING_ATTRIBUTION_OUT") \
        or os.path.join(here, "benchmarks",
                        "SERVING_ATTRIBUTION_r16.json")
    if not (os.path.isdir(record_dir)
            and hjournal.find_journal_files(record_dir)):
        log(f"bench[serving]: no recorded traces under {record_dir}; "
            "skipping attribution regeneration")
        return None
    path, report = hserving_trace.write_serving_report(record_dir)
    with open(path, "rb") as f:
        data = f.read()
    with open(out, "wb") as f:
        f.write(data)
    log(f"bench[serving]: attribution written to {out} "
        f"(and {path})")
    return report


def serving_attribution_main() -> None:
    """`--serving-attribution`: ONLY the deterministic regeneration
    of benchmarks/SERVING_ATTRIBUTION_r16.json from the committed
    trace recording — no measurement legs, so tests can pin the
    bytes cheaply."""
    here = os.path.dirname(os.path.abspath(__file__))
    report = _regen_serving_attribution(here)
    attr = (report or {}).get("attribution") or {}
    emit({
        "metric": "serving_attribution_dominant_share",
        "value": attr.get("dominant_share", 0.0),
        "unit": "fraction", "vs_baseline": 1.0})


def serving_main() -> None:
    """`--serving`: measure the elastic inference frontend
    (horovod_tpu/serving.py) on this host and write
    benchmarks/BENCH_serving_r16.json — p50/p99 request latency vs
    offered QPS, a scale-out curve over pool sizes with its
    per-phase lifecycle decomposition (serving_trace block), an
    autoscale soak, and the chaos retry accounting (an injected
    serving.batch worker death mid-run must lose zero requests).
    With BENCH_SERVING_RECORD=1 the 1- and 2-worker scale-out legs
    journal their request traces into benchmarks/serving_trace_r16/
    (the committed recording behind SERVING_ATTRIBUTION_r16.json);
    every run then regenerates that attribution artifact from the
    committed bytes. The artifact pins the padded-bucket ladder
    digest so a reader can tie the measured numbers to the exact
    executable-shape set they were taken against."""
    from horovod_tpu import faults as hfaults
    from horovod_tpu import journal as hjournal
    from horovod_tpu import serving as hserving

    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.environ.get("BENCH_SERVING_OUT") or os.path.join(
        here, "benchmarks", "BENCH_serving_r16.json")
    record = bool(os.environ.get("BENCH_SERVING_RECORD"))
    record_dir = os.environ.get("BENCH_SERVING_RECORD_DIR") \
        or os.path.join(here, "benchmarks", "serving_trace_r16")

    d_model = int(os.environ.get("BENCH_SERVING_DMODEL", "256"))
    rng = np.random.RandomState(0)
    w1 = jnp.asarray(rng.randn(d_model, 4 * d_model) * 0.05,
                     jnp.float32)
    w2 = jnp.asarray(rng.randn(4 * d_model, d_model) * 0.05,
                     jnp.float32)

    def forward(x):
        return jnp.tanh(x @ w1) @ w2

    senv = dict(os.environ)
    senv.update({
        "HOROVOD_SERVING_MAX_BATCH": senv.get(
            "HOROVOD_SERVING_MAX_BATCH", "8"),
        "HOROVOD_SERVING_LATENCY_BUDGET_MS": senv.get(
            "HOROVOD_SERVING_LATENCY_BUDGET_MS", "5"),
        "HOROVOD_SERVING_MAX_WORKERS": "4",
        "HOROVOD_SERVING_SCALE_INTERVAL_S": "0.05",
        "HOROVOD_SERVING_WORKER_TIMEOUT_S": "5",
    })

    def run_leg(n_requests, qps, workers, autoscale=False,
                fault_spec=None, tag=None, record_to=None):
        if fault_spec:
            hfaults.configure(fault_spec, seed=15)
        env = dict(senv)
        if record_to:
            os.makedirs(record_to, exist_ok=True)
            env["HOROVOD_JOURNAL_DIR"] = record_to
        fe = hserving.ServingFrontend(
            forward, (d_model,), env=env, start_pool=False,
            autoscale=autoscale, trace_tag=tag)
        fe.start_pool(workers)
        gap = (1.0 / qps) if qps else 0.0
        futs = []
        t0 = time.perf_counter()
        for i in range(n_requests):
            futs.append(fe.submit(rng.randn(d_model)))
            if gap:
                time.sleep(gap)
        for f in futs:
            f.result(timeout=60)
        wall = time.perf_counter() - t0
        stats = fe.stats()
        if record_to:
            fe.write_timeline(os.path.join(
                record_to, f"serving-{tag}.trace.json"))
        fe.close()
        if record_to:
            # Detach so the next leg's frontend opens its own role
            # file instead of appending to this leg's journal.
            hjournal.disarm()
        if fault_spec:
            hfaults.configure("", seed=0)
        lats = sorted(1e3 * (f.t_done - f.t_submit) for f in futs)
        return {
            "offered_qps": qps or None,
            "achieved_qps": round(n_requests / wall, 1),
            "p50_ms": round(np.percentile(lats, 50), 3),
            "p99_ms": round(np.percentile(lats, 99), 3),
            "requests": n_requests,
            "wall_s": round(wall, 3),
        }, stats

    # Warm the jit/AOT caches once so leg 1's first batch is not a
    # compile measurement.
    _, warm_stats = run_leg(8, 0, 1)
    ladder = warm_stats["ladder"]

    latency_vs_qps = {}
    for qps in (50, 100, 200):
        leg, _ = run_leg(min(2 * qps, 300), qps, 2)
        latency_vs_qps[f"qps{qps}"] = leg
        log(f"bench[serving]: qps={qps} p50={leg['p50_ms']}ms "
            f"p99={leg['p99_ms']}ms")

    scaleout = {}
    serving_trace = {}
    for w in (1, 2, 4):
        rec = record_dir if (record and w in (1, 2)) else None
        leg, st = run_leg(256, 0, w, tag=f"w{w}", record_to=rec)
        scaleout[f"workers{w}"] = {
            "achieved_qps": leg["achieved_qps"],
            "p99_ms": leg["p99_ms"]}
        if "trace" in st:
            serving_trace[f"workers{w}"] = st["trace"]
        log(f"bench[serving]: workers={w} "
            f"qps={leg['achieved_qps']}")

    auto_leg, auto_stats = run_leg(256, 0, 1, autoscale=True)
    autoscale = {
        "achieved_qps": auto_leg["achieved_qps"],
        "scale_events": auto_stats["scale_events"],
        "final_workers": auto_stats["workers"],
    }

    retry_leg, retry_stats = run_leg(
        64, 200, 2, fault_spec="serving.batch:error:at=3")
    retry = {
        "fault_spec": "serving.batch:error:at=3",
        "completed": retry_stats["completed"],
        "failed": retry_stats["failed"],
        "dropped": retry_stats["dropped"],
        "retries": retry_stats["retries"],
        "duplicates_suppressed": retry_stats["duplicates_suppressed"],
    }
    if retry_stats["dropped"] or retry_stats["retries"] < 1:
        log("bench[serving]: WARNING retry leg did not behave "
            f"({retry})")

    doc = {
        "what": "Elastic inference serving measured on this host "
                "(horovod_tpu/serving.py): request latency vs "
                "offered QPS through the dynamic batcher, scale-out "
                "over pool sizes, an autoscale soak, and the retry "
                "accounting for an injected mid-batch worker death "
                "- zero dropped requests is the acceptance bar.",
        "generated_by": "python bench.py --serving",
        "model": {"kind": "mlp", "d_model": d_model,
                  "dtype": "float32"},
        "ladder": ladder,
        "config": {
            "max_batch": int(senv["HOROVOD_SERVING_MAX_BATCH"]),
            "latency_budget_ms": float(
                senv["HOROVOD_SERVING_LATENCY_BUDGET_MS"]),
        },
        "latency_vs_qps": latency_vs_qps,
        "scaleout": scaleout,
        "serving_trace": serving_trace,
        "autoscale": autoscale,
        "retry": retry,
        "metrics": _metrics_snapshot(),
        "journal": _journal_digest(),
        "health": _health_digest(),
    }
    attribution = _regen_serving_attribution(here)
    if attribution is not None:
        doc["attribution"] = {
            "dominant_phase": attribution["attribution"][
                "dominant_phase"],
            "dominant_share": attribution["attribution"][
                "dominant_share"],
            "source": "benchmarks/SERVING_ATTRIBUTION_r16.json",
        } if attribution.get("attribution") else {}
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    log(f"bench[serving]: written to {out_path}")
    emit({
        "metric": "serving_p99_ms_at_100qps",
        "value": latency_vs_qps["qps100"]["p99_ms"],
        "unit": "ms", "vs_baseline": 1.0})


def _regen_decode_attribution(here):
    """Regenerate benchmarks/SERVING_ATTRIBUTION_r18.json from the
    COMMITTED decode trace recording (benchmarks/serving_decode_r18/)
    — the same pure-function-of-committed-bytes contract as the r16
    artifact: `doctor serve` on that directory and every rerun of
    this function produce identical bytes. Returns the report, or
    None when no recording is committed."""
    from horovod_tpu import journal as hjournal
    from horovod_tpu import serving_trace as hserving_trace

    record_dir = os.environ.get("BENCH_DECODE_RECORD_DIR") \
        or os.path.join(here, "benchmarks", "serving_decode_r18")
    out = os.environ.get("BENCH_DECODE_ATTRIBUTION_OUT") \
        or os.path.join(here, "benchmarks",
                        "SERVING_ATTRIBUTION_r18.json")
    if not (os.path.isdir(record_dir)
            and hjournal.find_journal_files(record_dir)):
        log(f"bench[decode]: no recorded traces under {record_dir}; "
            "skipping decode attribution regeneration")
        return None
    path, report = hserving_trace.write_serving_report(record_dir)
    with open(path, "rb") as f:
        data = f.read()
    with open(out, "wb") as f:
        f.write(data)
    log(f"bench[decode]: attribution written to {out} (and {path})")
    return report


def serving_decode_main() -> None:
    """`--serving-decode`: measure the continuous-batching decode
    plane (horovod_tpu/decoding.py) on this host and write
    benchmarks/BENCH_serving_decode_r18.json — a tokens/s scale-out
    curve over worker counts (the sharded admission plane must keep
    it monotone 1->2->4), goodput vs offered QPS per SLO class
    through the interactive/batch lanes, and the chaos leg: a REAL
    worker process crash (exit 43) mid-sequence, after which every
    in-flight sequence resumes from its KV watermark on a survivor
    process — zero dropped sequences and streams bitwise identical
    to an uninterrupted baseline (the exactly-once token latch means
    no delivered token is ever re-emitted). With
    BENCH_SERVING_RECORD=1 the 1-/2-worker scale-out legs and the
    chaos leg journal per-sequence traces into
    benchmarks/serving_decode_r18/ (the committed recording behind
    SERVING_ATTRIBUTION_r18.json); every run then regenerates that
    attribution artifact from the committed bytes — its
    decode_attribution block is the evidence that the r16 batch_cut
    bottleneck (95.1% of the request-plane scale-out regression)
    does not reappear as admission serialization on the decode
    plane."""
    import subprocess

    from horovod_tpu import decoding as hdecoding
    from horovod_tpu import journal as hjournal

    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.environ.get("BENCH_SERVING_DECODE_OUT") \
        or os.path.join(here, "benchmarks",
                        "BENCH_serving_decode_r18.json")
    record = bool(os.environ.get("BENCH_SERVING_RECORD"))
    record_dir = os.environ.get("BENCH_DECODE_RECORD_DIR") \
        or os.path.join(here, "benchmarks", "serving_decode_r18")

    d_model = int(os.environ.get("BENCH_DECODE_DMODEL", "256"))
    vocab = int(os.environ.get("BENCH_DECODE_VOCAB", "1024"))
    params = hdecoding.make_toy_params(vocab=vocab, d_model=d_model,
                                       seed=18)

    denv = dict(os.environ)
    denv.update({
        "HOROVOD_KV_PAGE_TOKENS": denv.get(
            "HOROVOD_KV_PAGE_TOKENS", "16"),
        "HOROVOD_KV_MAX_CONTEXT": denv.get(
            "HOROVOD_KV_MAX_CONTEXT", "128"),
        "HOROVOD_SERVING_DECODE_SLOTS": denv.get(
            "HOROVOD_SERVING_DECODE_SLOTS", "8"),
        "HOROVOD_SERVING_DECODE_WATERMARK_STRIDE": "8",
        "HOROVOD_SERVING_DECODE_RETRY_BACKOFF_MS": "10",
        "HOROVOD_SERVING_DECODE_LEASE_TIMEOUT_S": "5",
    })
    rng = np.random.RandomState(18)

    def make_prompts(n, hi):
        return [rng.randint(1, hi,
                            size=int(rng.randint(4, 12))).astype(
                                np.int32)
                for _ in range(n)]

    def wait_warm(fe, timeout=120.0):
        # AOT rung warmup runs on the worker threads; wait for every
        # LOCAL engine to pin its rung set so the timed window
        # measures steady-state decode, not compilation.
        nrungs = len(fe.ladder.rungs)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            engines = [t.engine for t in list(fe._threads.values())]
            if not engines or all(e.compiles >= nrungs
                                  for e in engines):
                return
            time.sleep(0.02)

    def run_decode_leg(prompts, workers, max_new=48, qps=0.0,
                       slo_of=None, tag=None, record_to=None):
        env = dict(denv)
        if record_to:
            os.makedirs(record_to, exist_ok=True)
            env["HOROVOD_JOURNAL_DIR"] = record_to
        fe = hdecoding.DecodeFrontend(
            workers=workers, params=params, env=env, trace_tag=tag)
        fe.start_watchdog()
        wait_warm(fe)
        gap = (1.0 / qps) if qps else 0.0
        futs = []
        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            futs.append(fe.submit(
                p, max_new_tokens=max_new,
                slo_ms=(slo_of(i) if slo_of else None), seed=i))
            if gap:
                time.sleep(gap)
        outs = [f.result(timeout=300) for f in futs]
        wall = time.perf_counter() - t0
        stats = fe.stats()
        fe.close()
        if record_to:
            hjournal.disarm()
        delivered = sum(len(o) for o in outs)
        ttfts = sorted((f.t_first_ns - f.t_submit_ns) / 1e6
                       for f in futs if f.t_first_ns)
        leg = {
            "sequences": len(futs),
            "delivered_tokens": delivered,
            "tokens_per_s": round(delivered / wall, 1),
            "wall_s": round(wall, 3),
            "ttft_p50_ms": round(np.percentile(ttfts, 50), 3),
            "ttft_p99_ms": round(np.percentile(ttfts, 99), 3),
        }
        return leg, stats, futs

    # -- scale-out: fixed token workload over 1/2/4 local workers ------
    n_scale = int(os.environ.get("BENCH_DECODE_SEQS", "24"))
    scaleout = {}
    ladder_digest = None
    for w in (1, 2, 4):
        rec = record_dir if (record and w in (1, 2)) else None
        leg, st, _ = run_decode_leg(
            make_prompts(n_scale, vocab), w, max_new=48,
            tag=f"d{w}", record_to=rec)
        ladder_digest = st["ladder"]
        scaleout[f"workers{w}"] = {
            "tokens_per_s": leg["tokens_per_s"],
            "ttft_p99_ms": leg["ttft_p99_ms"],
            "steals": st["steals"],
        }
        log(f"bench[decode]: workers={w} "
            f"tokens/s={leg['tokens_per_s']}")
    t1 = scaleout["workers1"]["tokens_per_s"]
    t2 = scaleout["workers2"]["tokens_per_s"]
    t4 = scaleout["workers4"]["tokens_per_s"]
    if not (t1 <= t2 <= t4):
        log("bench[decode]: WARNING scale-out not monotone "
            f"({t1} -> {t2} -> {t4} tokens/s)")

    # -- goodput vs offered QPS, per SLO class over the two lanes ------
    def slo_of(i):
        return 250.0 if i % 2 == 0 else None

    goodput_vs_qps = {}
    for qps in (20, 40):
        leg, st, futs = run_decode_leg(
            make_prompts(24, vocab), 2, max_new=32, qps=qps,
            slo_of=slo_of)
        by_lane = {}
        for f in futs:
            if f.t_first_ns:
                by_lane.setdefault(f.lane, []).append(
                    (f.t_first_ns - f.t_submit_ns) / 1e6)
        goodput_vs_qps[f"qps{qps}"] = {
            "tokens_per_s": leg["tokens_per_s"],
            "goodput": st["goodput"],
            "ttft_p99_ms_by_lane": {
                lane: round(np.percentile(sorted(v), 99), 3)
                for lane, v in sorted(by_lane.items())},
        }
        log(f"bench[decode]: qps={qps} goodput={st['goodput']}")

    # -- chaos: REAL process crash mid-sequence, survivor resumes ------
    # The remote workers build their engines from env knobs and the
    # module's DEFAULT toy LM, so the uninterrupted baseline below
    # must use the defaults too (bitwise comparability).
    cenv = dict(denv)
    cenv.update({
        "HOROVOD_KV_PAGE_TOKENS": "8",
        "HOROVOD_KV_MAX_CONTEXT": "64",
        "HOROVOD_SERVING_DECODE_SLOTS": "4",
        "HOROVOD_SERVING_DECODE_WATERMARK_STRIDE": "4",
        "HOROVOD_SERVING_DECODE_LEASE_TIMEOUT_S": "2.0",
    })
    cenv.pop("HOROVOD_JOURNAL_DIR", None)
    n_chaos = 6
    cprompts = make_prompts(n_chaos, 32)  # default toy vocab

    fe = hdecoding.DecodeFrontend(workers=1, env=cenv,
                                  trace_tag="dkillbase")
    try:
        futs = [fe.submit(p, max_new_tokens=24, seed=i)
                for i, p in enumerate(cprompts)]
        base = [list(f.result(timeout=300)) for f in futs]
    finally:
        fe.close()

    if jax.devices()[0].platform != "cpu":
        # The chaos leg kills and restarts worker PROCESSES while this
        # process holds the device; a chip belongs to one process.
        raise RuntimeError(
            "bench[decode]: the chaos leg starts worker processes "
            "beside this one; run it with JAX_PLATFORMS=cpu (it "
            "checks recovery, not speed)")
    chaos_env = dict(cenv)
    if record:
        os.makedirs(record_dir, exist_ok=True)
        chaos_env["HOROVOD_JOURNAL_DIR"] = record_dir
    fe2 = hdecoding.DecodeFrontend(workers=0, env=chaos_env,
                                   trace_tag="dkill")
    fe2.start_watchdog()
    port, secret = fe2.decode_endpoint()
    fault_spec = os.environ.get("BENCH_DECODE_CHAOS_FAULTS",
                                "decode.step:crash:at=15")

    def spawn(wid, fault=None):
        env = {k: str(v) for k, v in cenv.items()}
        env.update({
            "DECODE_TEST_ADDR": "127.0.0.1",
            "DECODE_TEST_PORT": str(port),
            "DECODE_TEST_SECRET": secret,
            "DECODE_TEST_WID": wid,
        })
        if fault:
            env["HOROVOD_FAULTS"] = fault
            env["HOROVOD_FAULTS_SEED"] = "18"
        return subprocess.Popen(
            [sys.executable,
             os.path.join(here, "tests", "decode_chaos_worker.py")],
            env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)

    victim = spawn("victim", fault=fault_spec)
    chaos = {"fault_spec": fault_spec, "sequences": n_chaos}
    try:
        futs = [fe2.submit(p, max_new_tokens=24, seed=i)
                for i, p in enumerate(cprompts)]
        rc = victim.wait(timeout=300)
        survivor = spawn("survivor")
        try:
            outs = [list(f.result(timeout=300)) for f in futs]
            st = fe2.stats()
            chaos.update({
                "worker_exit_code": rc,
                "completed": st["completed"],
                "dropped": sum(
                    1 for f in futs
                    if f.outcome not in ("ok", "truncated")),
                "failed": st["failed"],
                "resumed": st["resumed"],
                "duplicate_tokens_suppressed": st["dupes"],
                "streams_match_uninterrupted_baseline":
                    bool(outs == base),
            })
        finally:
            fe2.close()
            survivor.wait(timeout=60)
    finally:
        if victim.poll() is None:
            victim.kill()
        if record:
            hjournal.disarm()
    if (chaos.get("dropped") or chaos.get("failed")
            or not chaos.get("streams_match_uninterrupted_baseline")):
        log(f"bench[decode]: WARNING chaos leg did not behave "
            f"({chaos})")

    doc = {
        "what": "Continuous-batching decode plane measured on this "
                "host (horovod_tpu/decoding.py): tokens/s scale-out "
                "over worker counts through the sharded admission "
                "plane, goodput vs offered QPS per SLO class "
                "through the interactive/batch lanes, and the chaos "
                "accounting for a REAL worker process crash "
                "mid-sequence - zero dropped sequences and streams "
                "bitwise identical to the uninterrupted baseline is "
                "the acceptance bar.",
        "generated_by": "python bench.py --serving-decode",
        "model": {"kind": "toy-lm", "d_model": d_model,
                  "vocab": vocab, "dtype": "float32"},
        "kv_ladder": ladder_digest,
        "config": {
            "slots": int(denv["HOROVOD_SERVING_DECODE_SLOTS"]),
            "page_tokens": int(denv["HOROVOD_KV_PAGE_TOKENS"]),
            "max_context": int(denv["HOROVOD_KV_MAX_CONTEXT"]),
            "watermark_stride": int(
                denv["HOROVOD_SERVING_DECODE_WATERMARK_STRIDE"]),
        },
        "scaleout": scaleout,
        "goodput_vs_qps": goodput_vs_qps,
        "chaos": chaos,
        "metrics": _metrics_snapshot(),
        "journal": _journal_digest(),
        "health": _health_digest(),
    }
    attribution = _regen_decode_attribution(here)
    if attribution is not None:
        dec = attribution.get("decode_attribution")
        doc["decode_attribution"] = {
            "admission_share_base": dec["admission_share_base"],
            "admission_share_scaled": dec["admission_share_scaled"],
            "dominant_phase": dec["dominant_phase"],
            "source": "benchmarks/SERVING_ATTRIBUTION_r18.json",
        } if dec else {}
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    log(f"bench[decode]: written to {out_path}")
    emit({
        "metric": "serving_decode_scaleout4_tokens_per_s",
        "value": scaleout["workers4"]["tokens_per_s"],
        "unit": "tokens/s", "vs_baseline": 1.0})


def weight_swap_main() -> None:
    """`--weight-swap`: measure the train-to-serve live weight
    pipeline (horovod_tpu/weights.py + serving.py adoption) on this
    host and write benchmarks/BENCH_weightswap_r17.json — a rolling
    update under live traffic (per-worker swap latency, request p99
    DURING the swap window vs the SLO budget, the staleness curve,
    and the epoch-fence check over the journaled batch traces: no
    served batch mixes weight versions), a chaos leg (a worker death
    mid-swap via the weights.adopt seam AND a corrupt publication
    that every worker must reject while still serving the previous
    digest, then a clean republish that converges the pool), and a
    verified rollback — zero dropped requests across all of it is
    the acceptance bar."""
    import shutil
    import tempfile

    from horovod_tpu import faults as hfaults
    from horovod_tpu import journal as hjournal
    from horovod_tpu import serving as hserving
    from horovod_tpu import weights as hweights
    from horovod_tpu.metrics import REGISTRY as _REG

    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.environ.get("BENCH_WEIGHTSWAP_OUT") or os.path.join(
        here, "benchmarks", "BENCH_weightswap_r17.json")
    slo_budget_ms = float(os.environ.get(
        "BENCH_WEIGHTSWAP_SLO_MS", "250"))
    d_model = int(os.environ.get("BENCH_WEIGHTSWAP_DMODEL", "128"))
    scratch = tempfile.mkdtemp(prefix="bench-weightswap-")

    def make_params(seed):
        rng = np.random.RandomState(seed)
        return {
            "w1": jnp.asarray(rng.randn(d_model, 2 * d_model) * 0.05,
                              jnp.float32),
            "w2": jnp.asarray(rng.randn(2 * d_model, d_model) * 0.05,
                              jnp.float32),
        }

    def forward(params, x):
        return jnp.tanh(x @ params["w1"]) @ params["w2"]

    senv = dict(os.environ)
    senv.update({
        "HOROVOD_SERVING_MAX_BATCH": "8",
        "HOROVOD_SERVING_LATENCY_BUDGET_MS": "5",
        "HOROVOD_SERVING_MIN_WORKERS": "2",
        "HOROVOD_SERVING_MAX_WORKERS": "4",
        "HOROVOD_SERVING_SCALE_INTERVAL_S": "0.05",
        "HOROVOD_SERVING_WORKER_TIMEOUT_S": "5",
        "HOROVOD_WEIGHTS_POLL_MS": "25",
    })
    rng = np.random.RandomState(0)

    def wait_for(pred, timeout=30.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if pred():
                return True
            time.sleep(0.02)
        return pred()

    def pool_on(fe, digest):
        w = fe.stats()["weights"]["workers"]
        return bool(w) and all(i["digest"] == digest
                               for i in w.values())

    # -- leg 1: rolling update under live traffic -------------------
    wdir = os.path.join(scratch, "rolling")
    jdir = os.path.join(scratch, "rolling-journal")
    os.makedirs(jdir)
    boot = make_params(1)
    pub = hweights.WeightPublisher(wdir, env=senv)
    v1 = pub.publish(boot, step=100)
    env = dict(senv)
    env["HOROVOD_JOURNAL_DIR"] = jdir
    env["HOROVOD_SERVING_TRACE"] = "1"
    fe = hserving.ServingFrontend(
        forward, (d_model,), env=env, autoscale=False,
        trace_tag="weightswap", params=boot, weights=wdir)
    # The bootstrap tree IS v1's content (same digest), so gate on
    # actual adoptions — both workers through their first fence pass
    # — and push a warm burst through so AOT warmup never pollutes
    # the measured window.
    wait_for(lambda: fe.stats()["weights"]["swaps"] >= 2)
    for f in [fe.submit(rng.randn(d_model)) for _ in range(16)]:
        f.result(timeout=60)
    v2 = make_params(2)
    futs = []
    t_pub = None
    t_conv = None
    staleness_curve = []
    n_requests = 400
    for i in range(n_requests):
        futs.append((time.monotonic(),
                     fe.submit(rng.randn(d_model))))
        if i == n_requests // 4:
            t_pub = time.monotonic()
            v2 = pub.publish(v2, step=200)
        if t_pub is not None and t_conv is None and i % 10 == 0:
            w = fe.stats()["weights"]["workers"]
            staleness_curve.append({
                "t_ms": round(1e3 * (time.monotonic() - t_pub), 1),
                "staleness_steps": max(
                    [i_["staleness_steps"] for i_ in w.values()]
                    or [0]),
            })
            if pool_on(fe, v2.digest):
                t_conv = time.monotonic()
        time.sleep(0.002)
    for _, f in futs:
        f.result(timeout=60)
    if t_conv is None:
        wait_for(lambda: pool_on(fe, v2.digest))
        t_conv = time.monotonic()
    staleness_curve.append({
        "t_ms": round(1e3 * (t_conv - t_pub), 1),
        "staleness_steps": 0})
    # p99 over the requests submitted inside the swap window
    swap_lats = sorted(
        1e3 * (f.t_done - f.t_submit) for t, f in futs
        if t_pub <= t <= t_conv)
    all_lats = sorted(1e3 * (f.t_done - f.t_submit)
                      for _, f in futs)
    st = fe.stats()
    fe.close()
    hjournal.disarm()
    events = []
    jpath = os.path.join(jdir, "journal-serving-weightswap.jsonl")
    with open(jpath) as f:
        events = [json.loads(ln) for ln in f if ln.strip()]
    # The epoch fence, witnessed offline: every journaled batch
    # executed under exactly one digest from the published set.
    batch_digests = [e.get("weights", "") for e in events
                     if e["type"] == "batch_trace"]
    known = {v1.digest, v2.digest}
    mixed = sum(1 for d in batch_digests if d not in known)
    swap_ms = [e["ms"] for e in events
               if e["type"] == "weights_adopted"]
    rolling_update = {
        "requests": n_requests,
        "dropped": st["dropped"],
        "failed": st["failed"],
        "swaps": st["weights"]["swaps"],
        "p99_ms": round(np.percentile(all_lats, 99), 3),
        "p99_during_swap_ms": round(
            np.percentile(swap_lats, 99), 3) if swap_lats else None,
        "swap_window_ms": round(1e3 * (t_conv - t_pub), 1),
        "swap_ms": {
            "mean": round(float(np.mean(swap_ms)), 3),
            "max": round(float(np.max(swap_ms)), 3),
        },
        "fence": {
            "batches_traced": len(batch_digests),
            "digests_seen": len(set(batch_digests)),
            "mixed_version_batches": mixed,
        },
    }
    log(f"bench[weight-swap]: rolling update "
        f"p99_during_swap={rolling_update['p99_during_swap_ms']}ms "
        f"swap_mean={rolling_update['swap_ms']['mean']}ms "
        f"mixed={mixed}")

    # -- leg 2: chaos (worker death mid-swap + corrupt publish) -----
    wdir = os.path.join(scratch, "chaos")
    boot = make_params(1)
    pub = hweights.WeightPublisher(wdir, env=senv)
    pub.publish(boot, step=10)
    fe = hserving.ServingFrontend(
        forward, (d_model,), env=dict(senv), autoscale=True,
        params=boot, weights=wdir)
    wait_for(lambda: fe.stats()["weights"]["swaps"] >= 2)
    fired0 = _REG.snapshot().get("hvd_faults_fired_total", {}).get(
        ("weights.adopt", "error"), 0)
    hfaults.configure("weights.adopt:error:at=1", seed=17)
    c2 = pub.publish(make_params(2), step=20)
    futs = [fe.submit(rng.randn(d_model)) for _ in range(64)]
    for f in futs:
        f.result(timeout=60)
    wait_for(lambda: pool_on(fe, c2.digest))
    hfaults.configure("weights.publish:corrupt:at=1", seed=17)
    pub.publish(make_params(3), step=30)
    hfaults.configure("", seed=0)
    wait_for(lambda: fe.stats()["weights"]["rejections"] >= 1)
    still_on_c2 = pool_on(fe, c2.digest)
    c3 = pub.publish(make_params(3), step=31)   # the retry
    wait_for(lambda: pool_on(fe, c3.digest))
    futs = [fe.submit(rng.randn(d_model)) for _ in range(32)]
    for f in futs:
        f.result(timeout=60)
    st = fe.stats()
    deaths = _REG.snapshot().get("hvd_faults_fired_total", {}).get(
        ("weights.adopt", "error"), 0) - fired0
    chaos = {
        "fault_specs": ["weights.adopt:error:at=1",
                        "weights.publish:corrupt:at=1"],
        "dropped": st["dropped"],
        "failed": st["failed"],
        "worker_deaths": int(deaths),
        "corrupt_rejections": st["weights"]["rejections"],
        "kept_previous_digest_while_rejecting": bool(still_on_c2),
        "converged_digest": next(iter(
            st["weights"]["workers"].values()))["digest"],
        "final_digest": c3.digest,
        "final_workers": st["workers"],
    }
    fe.close()
    hjournal.disarm()
    log(f"bench[weight-swap]: chaos deaths={deaths} "
        f"rejections={chaos['corrupt_rejections']} "
        f"dropped={chaos['dropped']}")

    # -- leg 3: verified rollback -----------------------------------
    wdir = os.path.join(scratch, "rollback")
    boot = make_params(1)
    pub = hweights.WeightPublisher(wdir, env=senv)
    r1 = pub.publish(boot, step=1)
    r2 = pub.publish(make_params(2), step=2)
    fe = hserving.ServingFrontend(
        forward, (d_model,), env=dict(senv), autoscale=False,
        params=boot, weights=wdir)
    wait_for(lambda: pool_on(fe, r2.digest))
    rb = pub.rollback()
    wait_for(lambda: pool_on(fe, rb.digest))
    futs = [fe.submit(rng.randn(d_model)) for _ in range(32)]
    for f in futs:
        f.result(timeout=60)
    st = fe.stats()
    rollback = {
        "previous_digest": r1.digest,
        "live_digest_before": r2.digest,
        "restored_digest": next(iter(
            st["weights"]["workers"].values()))["digest"],
        "rollback_seq": rb.seq,
        "dropped": st["dropped"],
        "failed": st["failed"],
    }
    fe.close()
    hjournal.disarm()
    log(f"bench[weight-swap]: rollback restored="
        f"{rollback['restored_digest'] == rollback['previous_digest']}")

    doc = {
        "what": "Train-to-serve live weight pipeline measured on "
                "this host (horovod_tpu/weights.py + serving.py): "
                "a rolling update under live traffic with per-"
                "worker hot-swap latency, request p99 during the "
                "swap window vs the SLO budget, the staleness "
                "curve, and the epoch-fence check (no served batch "
                "mixes weight versions); a chaos leg with a worker "
                "death mid-swap and a corrupt publication rejected "
                "by every worker while still serving the previous "
                "digest; and a verified rollback - zero dropped "
                "requests across all of it is the acceptance bar.",
        "generated_by": "python bench.py --weight-swap",
        "model": {"kind": "mlp", "d_model": d_model,
                  "dtype": "float32"},
        "config": {
            "slo_budget_ms": slo_budget_ms,
            "poll_ms": float(senv["HOROVOD_WEIGHTS_POLL_MS"]),
            "max_batch": int(senv["HOROVOD_SERVING_MAX_BATCH"]),
            "latency_budget_ms": float(
                senv["HOROVOD_SERVING_LATENCY_BUDGET_MS"]),
        },
        "rolling_update": rolling_update,
        "staleness_curve": staleness_curve,
        "chaos": chaos,
        "rollback": rollback,
        "metrics": _metrics_snapshot(),
        "journal": _journal_digest(),
        "health": _health_digest(),
    }
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    log(f"bench[weight-swap]: written to {out_path}")
    emit({
        "metric": "weightswap_p99_during_swap_ms",
        "value": rolling_update["p99_during_swap_ms"],
        "unit": "ms", "vs_baseline": 1.0})


def _regen_health_report(here):
    """Regenerate the health report from the COMMITTED telemetry
    recording (benchmarks/health_r20/) — the same pure-function-of-
    committed-bytes contract as the r16/r18 attribution artifacts:
    `doctor health` on that directory and every rerun of this
    function produce identical bytes. Returns the report, or None
    when no recording is committed."""
    from horovod_tpu import telemetry as htelemetry

    record_dir = os.environ.get("BENCH_HEALTH_RECORD_DIR") \
        or os.path.join(here, "benchmarks", "health_r20")
    out = os.environ.get("BENCH_HEALTH_REPORT_OUT") or None
    if not (os.path.isdir(record_dir)
            and htelemetry.find_telemetry_files(record_dir)):
        log(f"bench[health]: no recorded telemetry under "
            f"{record_dir}; skipping health-report regeneration")
        return None
    path, report = htelemetry.write_health_report(record_dir,
                                                  out=out)
    log(f"bench[health]: report regenerated to {path}")
    return report


def health_report_main() -> None:
    """`--health-report`: regenerate health_report.json from the
    committed benchmarks/health_r20/ recording WITHOUT re-running the
    legs (mirrors --serving-attribution: a pure deterministic
    function of the committed shard bytes; BENCH_HEALTH_REPORT_OUT
    redirects the output for byte-identity checks)."""
    here = os.path.dirname(os.path.abspath(__file__))
    report = _regen_health_report(here)
    if report is None:
        return
    s = report["summary"]
    emit({
        "metric": "health_anomalies", "value": s["anomalies"],
        "unit": "alerts", "vs_baseline": 1.0})


def health_main() -> None:
    """`--health`: exercise the continuous health-telemetry plane
    (horovod_tpu/telemetry.py) end to end on the decode tier and
    write benchmarks/BENCH_health_r20.json — a steady leg (healthy
    single-worker decode drain under tuned-but-plausible detector
    thresholds: ZERO alerts is the acceptance bar) and a chaos leg
    (an injected decode.step hang parks the victim worker past the
    lease timeout; the survivor's continued sampling raises a
    beat_stall health_alert while the watchdog's fault/seq_resumed
    journal anchors attribute it to the recovery window — alerts >= 1
    with ZERO anomalies is the bar). With BENCH_HEALTH_RECORD=1 both
    legs record their telemetry shards and lifecycle journals into
    benchmarks/health_r20/ (the committed recording behind
    health_r20/health_report.json); every run then regenerates that
    report from the committed bytes."""
    import shutil
    import tempfile

    from horovod_tpu import decoding as hdecoding
    from horovod_tpu import faults as hfaults
    from horovod_tpu import journal as hjournal
    from horovod_tpu import telemetry as htelemetry

    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.environ.get("BENCH_HEALTH_OUT") or os.path.join(
        here, "benchmarks", "BENCH_health_r20.json")
    record = bool(os.environ.get("BENCH_HEALTH_RECORD"))
    record_dir = os.environ.get("BENCH_HEALTH_RECORD_DIR") \
        or os.path.join(here, "benchmarks", "health_r20")
    if record:
        # one coherent recording per commit: the report must stay a
        # pure function of exactly these legs' shards
        shutil.rmtree(record_dir, ignore_errors=True)
        rec_to = record_dir
    else:
        rec_to = tempfile.mkdtemp(prefix="bench-health-")
    os.makedirs(rec_to, exist_ok=True)

    denv = dict(os.environ)
    denv.update({
        "HOROVOD_KV_PAGE_TOKENS": "8",
        "HOROVOD_KV_MAX_CONTEXT": "64",
        "HOROVOD_SERVING_DECODE_SLOTS": "4",
        "HOROVOD_SERVING_DECODE_WATERMARK_STRIDE": "4",
        "HOROVOD_SERVING_DECODE_LEASE_TIMEOUT_S": "2.0",
        "HOROVOD_SERVING_DECODE_RETRY_BACKOFF_MS": "5",
        "HOROVOD_JOURNAL_DIR": rec_to,
        "HOROVOD_TELEMETRY_DIR": rec_to,
        "HOROVOD_TELEMETRY_INTERVAL_S": "0",
    })

    def run_leg(tag, workers, n_seqs, max_new, **knobs):
        env = dict(denv)
        env.update({k: str(v) for k, v in knobs.items()})
        fe = hdecoding.DecodeFrontend(workers=workers, env=env,
                                     trace_tag=tag)
        fe.start_watchdog()
        t0 = time.perf_counter()
        try:
            futs = [fe.submit([1, 2, 3], max_new_tokens=max_new,
                              seed=s) for s in range(n_seqs)]
            outs = [list(f.result(timeout=300)) for f in futs]
            st = fe.stats()
        finally:
            fe.close()
            htelemetry.disarm()
            hjournal.disarm()
        wall = time.perf_counter() - t0
        return {
            "name": tag,
            "workers": workers,
            "sequences": n_seqs,
            "delivered_tokens": sum(len(o) for o in outs),
            "wall_s": round(wall, 3),
            "completed": st["completed"],
            "resumed": st["resumed"],
            "failed": st["failed"],
        }

    legs = [run_leg("steady", 1, 4, 24,
                    HOROVOD_TELEMETRY_STEP_MAD_K="30",
                    HOROVOD_TELEMETRY_STALL_FLOOR_S="5.0")]
    log(f"bench[health]: steady leg {legs[-1]}")

    hfaults.configure("decode.step:hang:at=12", seed=0)
    try:
        legs.append(run_leg("chaos", 2, 2, 40,
                            HOROVOD_TELEMETRY_STEP_MAD_K="10",
                            HOROVOD_TELEMETRY_STALL_FLOOR_S="0.5"))
    finally:
        hfaults.configure("", seed=0)
    log(f"bench[health]: chaos leg {legs[-1]}")
    if legs[-1]["resumed"] < 1:
        log("bench[health]: WARNING chaos leg resumed no sequences "
            f"({legs[-1]})")

    path, _ = htelemetry.write_health_report(rec_to)
    log(f"bench[health]: report written to {path}")
    if os.path.abspath(rec_to) != os.path.abspath(record_dir):
        _regen_health_report(here)

    health = _health_digest(rec_to)
    if health.get("anomalies", 0) != 0 or not health.get("alerts"):
        log(f"bench[health]: WARNING unexpected health verdict "
            f"({health})")

    doc = {
        "what": "Continuous health telemetry measured on this host "
                "(horovod_tpu/telemetry.py): a healthy decode drain "
                "that the online detectors must stay silent on, and "
                "an injected mid-decode hang whose beat_stall alert "
                "must be attributed to the journaled recovery window "
                "- alerts with zero unexplained anomalies is the "
                "acceptance bar.",
        "generated_by": "python bench.py --health",
        "config": {
            "slots": 4, "page_tokens": 8, "max_context": 64,
            "watermark_stride": 4, "lease_timeout_s": 2.0,
            "telemetry_interval_s": 0.0,
            "chaos_fault": "decode.step:hang:at=12",
        },
        "legs": legs,
        "health": health,
        "metrics": _metrics_snapshot(),
        "journal": _journal_digest(),
    }
    if not record:
        shutil.rmtree(rec_to, ignore_errors=True)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    log(f"bench[health]: written to {out_path}")
    emit({
        "metric": "health_chaos_anomalies",
        "value": health.get("anomalies", -1),
        "unit": "alerts", "vs_baseline": 1.0})


# The trajectory consolidation is a byte-pinned artifact path:
# hvdlint HVD009 seeds its reachability here and flags wall-clock /
# unsorted-walk / unsorted-json nondeterminism anywhere under it.
DETERMINISTIC_ENTRYPOINTS = ("trajectory_main",)


def trajectory_main() -> None:
    """`--trajectory`: consolidate the committed per-round artifacts
    into one byte-deterministic BENCH_trajectory.json — the headline
    r08->r20 story in a single file. Reads ONLY committed artifacts (no
    clocks, no env), writes with sorted keys — rerunning on the same
    tree reproduces the bytes exactly; this path is on hvdlint
    HVD009's byte-determinism beat via DETERMINISTIC_ENTRYPOINTS."""
    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.environ.get("BENCH_TRAJECTORY_OUT") or os.path.join(
        here, "benchmarks", "BENCH_trajectory.json")

    def read(relpath, *fields, default=None):
        path = os.path.join(here, relpath)
        try:
            with open(path) as f:
                node = json.load(f)
            for k in fields:
                node = node[k]
            return node
        except (OSError, KeyError):
            return default

    doc = {
        "what": "The committed headline-performance trajectory, one "
                "entry per recorded round - every number is read "
                "from its committed artifact (sources inline), and "
                "this file is a pure deterministic function of "
                "them: rerunning --trajectory reproduces it "
                "byte-for-byte.",
        "generated_by": "python bench.py --trajectory",
        "r08_wire_gate_ab": {
            "resnet_delta_pct": read(
                "benchmarks/BENCH_wiregate_ab_r08.json",
                "resnet_stash_ab", "delta_pct"),
            "source": "benchmarks/BENCH_wiregate_ab_r08.json",
        },
        "r15_serving": {
            "p99_ms_at_100qps": read(
                "benchmarks/BENCH_serving_r15.json",
                "latency_vs_qps", "qps100", "p99_ms"),
            "scaleout_4worker_qps": read(
                "benchmarks/BENCH_serving_r15.json",
                "scaleout", "workers4", "achieved_qps"),
            "chaos_dropped_requests": read(
                "benchmarks/BENCH_serving_r15.json",
                "retry", "dropped"),
            "chaos_retries": read(
                "benchmarks/BENCH_serving_r15.json",
                "retry", "retries"),
            "ladder_digest": read(
                "benchmarks/BENCH_serving_r15.json",
                "ladder", "digest"),
            "source": "benchmarks/BENCH_serving_r15.json",
        },
        "r16_serving_attribution": {
            "added_mean_ms_1to2_workers": read(
                "benchmarks/SERVING_ATTRIBUTION_r16.json",
                "attribution", "added_mean_ms"),
            "dominant_phase": read(
                "benchmarks/SERVING_ATTRIBUTION_r16.json",
                "attribution", "dominant_phase"),
            "dominant_share": read(
                "benchmarks/SERVING_ATTRIBUTION_r16.json",
                "attribution", "dominant_share"),
            "top2": read(
                "benchmarks/SERVING_ATTRIBUTION_r16.json",
                "attribution", "top2"),
            "note": "measured per-phase decomposition of the "
                    "1->2-worker scale-out regression from the "
                    "committed trace recording "
                    "(benchmarks/serving_trace_r16/)",
            "source": "benchmarks/SERVING_ATTRIBUTION_r16.json",
        },
        "r17_weightswap": {
            "p99_during_swap_ms": read(
                "benchmarks/BENCH_weightswap_r17.json",
                "rolling_update", "p99_during_swap_ms"),
            "swap_mean_ms": read(
                "benchmarks/BENCH_weightswap_r17.json",
                "rolling_update", "swap_ms", "mean"),
            "mixed_version_batches": read(
                "benchmarks/BENCH_weightswap_r17.json",
                "rolling_update", "fence", "mixed_version_batches"),
            "chaos_dropped_requests": read(
                "benchmarks/BENCH_weightswap_r17.json",
                "chaos", "dropped"),
            "chaos_worker_deaths": read(
                "benchmarks/BENCH_weightswap_r17.json",
                "chaos", "worker_deaths"),
            "note": "zero-downtime rolling weight update: request "
                    "p99 during the swap window, per-worker hot-"
                    "swap latency, and the epoch-fence check (no "
                    "served batch mixes weight versions) under "
                    "injected mid-swap chaos",
            "source": "benchmarks/BENCH_weightswap_r17.json",
        },
        "r18_decode": {
            "scaleout_1worker_tokens_per_s": read(
                "benchmarks/BENCH_serving_decode_r18.json",
                "scaleout", "workers1", "tokens_per_s"),
            "scaleout_2worker_tokens_per_s": read(
                "benchmarks/BENCH_serving_decode_r18.json",
                "scaleout", "workers2", "tokens_per_s"),
            "scaleout_4worker_tokens_per_s": read(
                "benchmarks/BENCH_serving_decode_r18.json",
                "scaleout", "workers4", "tokens_per_s"),
            "chaos_dropped_sequences": read(
                "benchmarks/BENCH_serving_decode_r18.json",
                "chaos", "dropped"),
            "chaos_resumed_sequences": read(
                "benchmarks/BENCH_serving_decode_r18.json",
                "chaos", "resumed"),
            "chaos_streams_match_baseline": read(
                "benchmarks/BENCH_serving_decode_r18.json",
                "chaos", "streams_match_uninterrupted_baseline"),
            "admission_share_base": read(
                "benchmarks/SERVING_ATTRIBUTION_r18.json",
                "decode_attribution", "admission_share_base"),
            "admission_share_scaled": read(
                "benchmarks/SERVING_ATTRIBUTION_r18.json",
                "decode_attribution", "admission_share_scaled"),
            "r16_request_plane_dominant_share": read(
                "benchmarks/SERVING_ATTRIBUTION_r16.json",
                "attribution", "dominant_share"),
            "note": "continuous-batching decode with per-sequence "
                    "exactly-once recovery: monotone tokens/s "
                    "scale-out through the sharded admission plane "
                    "(the r16 batch_cut analog, admission, no "
                    "longer dominates the 1->2-worker delta), and "
                    "a real mid-sequence worker crash resumed from "
                    "the KV watermark with zero dropped sequences "
                    "and zero re-emitted tokens",
            "source": "benchmarks/BENCH_serving_decode_r18.json + "
                      "benchmarks/SERVING_ATTRIBUTION_r18.json",
        },
        "r20_health": {
            "samples": read(
                "benchmarks/BENCH_health_r20.json",
                "health", "samples"),
            "alerts": read(
                "benchmarks/BENCH_health_r20.json",
                "health", "alerts"),
            "attributed_alerts": read(
                "benchmarks/BENCH_health_r20.json",
                "health", "attributed_alerts"),
            "anomalies": read(
                "benchmarks/BENCH_health_r20.json",
                "health", "anomalies"),
            "note": "continuous health telemetry over the decode "
                    "tier: the online detectors stay silent on the "
                    "healthy drain, and the injected mid-decode "
                    "hang's beat_stall alert is fully attributed to "
                    "the journaled recovery window - zero "
                    "unexplained anomalies across the committed "
                    "recording",
            "source": "benchmarks/BENCH_health_r20.json + "
                      "benchmarks/health_r20/health_report.json",
        },
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    log(f"bench[trajectory]: written to {out_path}")
    emit({
        "metric": "trajectory_rounds_recorded",
        "value": 6, "unit": "rounds",
        "vs_baseline": 1.0})


def main(model_name: str = "resnet50"):
    batch_per_chip = int(os.environ.get("BENCH_BATCH", "128"))
    steps = int(os.environ.get("BENCH_STEPS", "200"))
    warmup = int(os.environ.get("BENCH_WARMUP", "5"))
    image = int(os.environ.get(
        "BENCH_IMAGE", "299" if model_name == "inception3" else "224"))
    profile_dir = _profile_requested()

    hvd.init()
    mesh = data_parallel_mesh()
    n_chips = mesh.devices.size
    global_batch = batch_per_chip * n_chips
    log(f"bench: devices={n_chips} platform="
        f"{jax.devices()[0].platform} global_batch={global_batch} "
        f"model={model_name}")

    has_bn = model_name in ("resnet50", "resnet101", "resnet152",
                            "inception3")
    stages = os.environ.get("BENCH_RESNET_STAGES", "")
    if model_name == "inception3":
        # The lead model of the reference's benchmark table
        # (docs/benchmarks.rst: Inception V3 ~90% scaling).
        from horovod_tpu.models.inception import (create_inception_v3,
                                                  init_inception)
        s2d = os.environ.get("BENCH_INCEPTION_S2D", "") == "1"
        if s2d:
            log("bench: inception stem_s2d=1 (space-to-depth stem "
                "experiment — see models/inception.py)")
        model = create_inception_v3(dtype=jnp.bfloat16, stem_s2d=s2d)
        variables = init_inception(model, jax.random.PRNGKey(0), image)
        params, batch_stats = (variables["params"],
                               variables["batch_stats"])
    elif model_name == "vgg16":
        # The reference benchmark trio's comm-bound member: ~138M
        # params = ~276 MB fp16 gradient wire per step (reference:
        # docs/benchmarks.rst VGG-16 at 68% scaling vs ~90%).
        from horovod_tpu.models.vgg import create_vgg16, init_vgg
        model = create_vgg16(dtype=jnp.bfloat16)
        variables = init_vgg(model, jax.random.PRNGKey(0), image)
        params, batch_stats = variables["params"], {}
    elif model_name in ("resnet101", "resnet152"):
        # ResNet-101 is the reference benchmark table's second CNN
        # (docs/benchmarks.rst: ~90% scaling at 128 GPUs). Checked
        # BEFORE the BENCH_RESNET_STAGES override so a leftover
        # reduced-stage env cannot pollute a resnet101/152 metric.
        from horovod_tpu.models.resnet import ResNet101, ResNet152
        cls = ResNet101 if model_name == "resnet101" else ResNet152
        model = cls(dtype=jnp.bfloat16)
        variables = init_resnet(model, jax.random.PRNGKey(0), image)
        params, batch_stats = variables["params"], variables["batch_stats"]
    elif stages:
        model = _make_reduced_resnet(stages)
        variables = init_resnet(model, jax.random.PRNGKey(0), image)
        params, batch_stats = variables["params"], variables["batch_stats"]
    else:
        model = create_resnet50(dtype=jnp.bfloat16)
        variables = init_resnet(model, jax.random.PRNGKey(0), image)
        params, batch_stats = variables["params"], variables["batch_stats"]

    def loss_fn(params, batch):
        if has_bn:
            logits, updates = model.apply(
                {"params": params, "batch_stats": batch["batch_stats"]},
                batch["images"], train=True, mutable=["batch_stats"])
            new_stats = updates["batch_stats"]
        else:
            logits = model.apply({"params": params}, batch["images"],
                                 train=True)
            new_stats = {}
        onehot = jax.nn.one_hot(batch["labels"], logits.shape[-1])
        loss = jnp.mean(
            -jnp.sum(onehot * jax.nn.log_softmax(logits), axis=-1))
        return loss, new_stats

    opt = optax.sgd(0.0125 * n_chips, momentum=0.9)
    opt_state = opt.init(params)

    step = build_train_step(
        loss_fn, opt, mesh,
        batch_spec={"images": P("data"), "labels": P("data"),
                    "batch_stats": P()},
        loss_has_aux=True, donate=True)

    rng = np.random.default_rng(0)
    images = jnp.asarray(
        rng.standard_normal((global_batch, image, image, 3),
                            dtype=np.float32))
    labels = jnp.asarray(rng.integers(0, 1000, global_batch), jnp.int32)
    data_sh = NamedSharding(mesh, P("data"))
    images = jax.device_put(images, data_sh)
    labels = jax.device_put(labels, data_sh)
    rep_sh = NamedSharding(mesh, P())
    batch_stats = jax.device_put(batch_stats, rep_sh)

    step_exec, flops_per_step = aot_compile(
        step, params, opt_state,
        {"images": images, "labels": labels, "batch_stats": batch_stats})

    if os.environ.get("BENCH_COLLECTIVE_STATS") and \
            hasattr(step_exec, "as_text"):
        # Per-step collective accounting from the compiled program:
        # the DP step must contain cross-replica reduces moving (about)
        # one gradient-sized payload (+ BN batch-stat pmeans / loss
        # metrics). Recorded by the multi-process virtual-mesh artifact
        # (benchmarks/MULTIPROC_bench_r03.json).
        try:
            hlo = step_exec.as_text()
            n_ar = hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(")
            grad_bytes = int(sum(
                np.prod(p.shape) * jnp.dtype(p.dtype).itemsize
                for p in jax.tree_util.tree_leaves(params)))
            log(f"bench: compiled collectives: {n_ar} all-reduce ops; "
                f"gradient payload {grad_bytes / 1e6:.1f} MB/step")
        except Exception as e:  # pragma: no cover - backend-dependent
            log(f"bench: collective stats unavailable ({e})")

    def run_step(params, opt_state, batch_stats):
        batch = {"images": images, "labels": labels,
                 "batch_stats": batch_stats}
        params, opt_state, metrics = step_exec(params, opt_state, batch)
        return params, opt_state, metrics["aux"], metrics["loss"]

    t_c0 = time.perf_counter()
    for _ in range(warmup):
        params, opt_state, batch_stats, loss = run_step(
            params, opt_state, batch_stats)
    jax.block_until_ready(loss)
    log(f"bench: warmup ({warmup} steps; compile done in AOT phase) "
        f"{time.perf_counter() - t_c0:.1f}s loss={float(loss):.3f}")

    profiler_cm = (jax.profiler.trace(profile_dir) if profile_dir
                   else None)
    if profiler_cm is not None:
        profiler_cm.__enter__()
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, batch_stats, loss = run_step(
            params, opt_state, batch_stats)
    jax.block_until_ready((params, opt_state, batch_stats, loss))
    dt = time.perf_counter() - t0
    final_loss = float(loss)
    if profiler_cm is not None:
        profiler_cm.__exit__(None, None, None)
        log(f"bench: profiler trace written to {profile_dir}")

    img_sec = global_batch * steps / dt
    img_sec_chip = img_sec / n_chips
    log(f"bench: {steps} steps in {dt:.2f}s -> {img_sec:.1f} img/sec "
        f"({img_sec_chip:.1f} img/sec/chip) loss={final_loss:.3f}")
    peak = device_peak_tflops()
    gflop_per_img = (round(flops_per_step / global_batch / 1e9, 4)
                     if flops_per_step else None)
    mfu = _mfu(img_sec_chip, gflop_per_img, peak)
    if flops_per_step and peak:
        achieved = flops_per_step * steps / dt / n_chips / 1e12
        log(f"bench: MFU {achieved / peak * 100:.1f}% "
            f"({achieved:.1f} of {peak:.0f} TFLOP/s/chip, "
            f"{flops_per_step / global_batch / 1e9:.1f} GFLOP/img "
            f"compiled)")

    metric = f"{model_name}_synthetic_train_img_sec_per_chip"
    doc = {
        "metric": metric,
        "value": round(img_sec_chip, 2),
        "unit": "img/sec/chip",
        "vs_baseline": 1.0,
        "mfu": mfu,
        "compiled_gflop_per_img": gflop_per_img,
        "profile": _profile_block(profile_dir),
        "metrics": _metrics_snapshot(),
        "trace": _trace_digest(),
        "journal": _journal_digest(),
        "health": _health_digest(),
    }
    emit(doc)


if __name__ == "__main__":
    if "--model" in sys.argv:
        chosen = sys.argv[sys.argv.index("--model") + 1:
                          sys.argv.index("--model") + 2]
        if not chosen:
            sys.exit("bench: --model requires a value (resnet50, "
                     "vgg16, inception3, transformer)")
        model = chosen[0]
    else:
        model = "resnet50"
    if "--eager" not in sys.argv and (
            "--eager-hooks" in sys.argv or "--eager-adasum" in sys.argv):
        sys.exit("bench: --eager-hooks/--eager-adasum require --eager "
                 "(without it the jit benchmark would run and the flag "
                 "would be silently ignored)")
    if "--serving-attribution" in sys.argv:
        serving_attribution_main()
    elif "--serving-decode" in sys.argv:
        serving_decode_main()
    elif "--weight-swap" in sys.argv:
        weight_swap_main()
    elif "--health-report" in sys.argv:
        health_report_main()
    elif "--health" in sys.argv:
        health_main()
    elif "--serving" in sys.argv:
        serving_main()
    elif "--trajectory" in sys.argv:
        trajectory_main()
    elif "--autotune" in sys.argv:
        if model not in ("resnet50", "vgg16", "transformer"):
            sys.exit(f"bench: --autotune drives the eager bench "
                     f"(resnet50/vgg16/transformer), got {model!r}")
        autotune_main(model)
    elif "--eager" in sys.argv:
        if model not in ("resnet50", "vgg16", "transformer"):
            sys.exit(f"bench: --eager supports resnet50/vgg16/"
                     f"transformer, got {model!r}")
        eager_main(model)
    elif model == "transformer":
        transformer_main()
    elif model in ("resnet50", "resnet101", "resnet152", "vgg16",
                   "inception3"):
        main(model)
    else:
        sys.exit(f"bench: unknown --model {model!r} (choose "
                 "resnet50, resnet101, resnet152, vgg16, inception3, "
                 "transformer)")
