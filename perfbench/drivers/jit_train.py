"""Driver `jit_train`: the jitted data-parallel step.

`build_train_step` -> `aot_compile`, one process over all the cell's
chips on a `data` mesh, the program at its shipped defaults. A ring of
seeded batches is resident on the device; every step ends in a host
read of its loss, as for a user who logs the loss, and the window's
throughput counts those reads. Order of one run: compile cache,
`hvd.init()`, weights, correctness sample against the plain reference,
optimizer state and ring, compile, warm-up, window, (traced stretch).
"""

from __future__ import annotations

import json
import math
import os
import shutil
import time
from types import SimpleNamespace
from typing import List

WARMUP_STEPS = 3
LOSSES_KEPT = 16
clock = time.perf_counter


def say(phase: str, **kv) -> None:
    """An earlier line: for people, never read by the driver."""
    print(json.dumps({"phase": phase, **kv}), flush=True)


class CompileCounter:
    """Programs handed to the backend compiler (cache hits included)
    while `on` is set."""

    def __init__(self):
        import jax.monitoring
        self.n, self.on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._hit)

    def _hit(self, event, _secs, **_kw):
        if self.on and event == \
                "/jax/core/compile/backend_compile_duration":
            self.n += 1


def device_peak_bytes(device) -> int:
    """Peak of the chip's memory. The TPU runtime keeps two books: live
    buffers (`peak_bytes_in_use`: weights, optimizer state, batches)
    and what it set aside for the temporaries of loaded programs
    (`peak_bytes_reserved`), which `peak_bytes_in_use` leaves out
    although it is half of a training step's need."""
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) + \
        stats.get("peak_bytes_reserved", 0)


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def weights_and_sample(m, mesh, k_init, k_sample):
    """(weights, carried state, correctness sample), each made on the
    device in one jitted call: weights replicated, the sample sharded
    over the chips like a batch."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    params, carry = jax.jit(
        m.init, out_shardings=NamedSharding(mesh, P()))(k_init)
    sample = jax.jit(
        m.sample_batch, static_argnums=1,
        out_shardings=NamedSharding(mesh, P("data")))(
            k_sample, mesh.devices.size)
    return params, carry, sample


def sample_check(m, reference, config, mesh, params, carry, sample):
    """Loss and global gradient norm of the seeded sample: through the
    system (`build_train_step` with an optimizer that changes nothing
    and keeps the norm of the gradient it is handed) and through the
    plain reference on the same weights, each chip's shard apart as
    data parallelism computes it."""
    import jax
    import jax.numpy as jnp
    import optax
    from horovod_tpu.parallel import build_train_step

    def f32_norm(tree):
        return optax.global_norm(
            jax.tree.map(lambda g: g.astype(jnp.float32), tree))

    probe = optax.GradientTransformation(
        lambda p: jnp.zeros((), jnp.float32),
        lambda g, s, p=None: (jax.tree.map(jnp.zeros_like, g),
                              f32_norm(g)))
    step = build_train_step(
        m.loss_fn, probe, mesh, batch_spec=m.batch_spec,
        loss_has_aux=m.has_aux, donate=False, **m.step_kwargs)
    batch = {**sample, m.carry_key: carry} if m.carry_key else sample
    _, norm, metrics = step(params, probe.init(params), batch)
    got = {"loss": float(metrics["loss"]), "grad_norm": float(norm)}

    n = mesh.devices.size
    first = mesh.devices.flat[0]
    on_first = jax.tree.map(
        lambda a: next(s.data for s in a.addressable_shards
                       if s.device == first), (params, carry))
    gathered = jax.device_put(sample, first)

    @jax.jit
    def plain(weights, carry, sample):
        shards = jax.tree.map(
            lambda a: a.reshape(n, -1, *a.shape[1:]), sample)

        def mean_loss(w):
            return jnp.mean(jax.vmap(
                lambda s: reference.loss(config, w, s, carry))(shards))
        loss, grads = jax.value_and_grad(mean_loss)(
            jax.tree.map(lambda a: a.astype(jnp.float32), weights))
        return loss, f32_norm(grads)

    loss, norm = plain(*on_first, gathered)
    want = {"loss": float(loss), "grad_norm": float(norm)}
    error = {k: abs(got[k] - want[k]) / abs(want[k]) for k in want}
    ok = all(math.isfinite(error[k]) and
             error[k] <= reference.TOLERANCE[k] for k in want)
    say("sample", system=got, reference=want, relative_error=error,
        tolerance=reference.TOLERANCE, ok=ok)
    return ok


def run(*, cell, model, reference, devices, seed, seconds, trace,
        process_start, out_dir) -> SimpleNamespace:
    import horovod_tpu as hvd
    from horovod_tpu.common import compile_cache

    cache_dir = compile_cache.enable()
    hvd.init()
    try:
        return _measure(cell, model, reference, devices, seed, seconds,
                        trace, process_start, out_dir, cache_dir)
    finally:
        hvd.shutdown()


def _measure(cell, model, reference, devices, seed, seconds, trace,
             process_start, out_dir, cache_dir) -> SimpleNamespace:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from horovod_tpu.parallel import build_train_step
    from horovod_tpu.parallel.aot import aot_compile
    from horovod_tpu.parallel.mesh import data_parallel_mesh
    from horovod_tpu.parallel.train import last_overlap_info

    spec, n = cell.spec, len(devices)
    first = devices[0]
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": n}
    say("start", cell=cell.name, seed=seed, device=device,
        compile_cache_dir=cache_dir, init_s=clock() - process_start)
    mesh = data_parallel_mesh(devices)
    replicated = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P("data"))
    m = model.build(cell.config, spec, n)
    counter = CompileCounter()
    k_init, k_sample, k_ring = jax.random.split(seed_key(seed), 3)

    params, carry, sample = weights_and_sample(m, mesh, k_init, k_sample)
    sample_ok = sample_check(m, reference, cell.config, mesh, params,
                             carry, sample)
    del sample

    opt_state = jax.jit(m.optimizer.init,
                        out_shardings=replicated)(params)
    make_batch = jax.jit(m.make_batch, static_argnums=1,
                         out_shardings=sharded)
    ring = [make_batch(jax.random.fold_in(k_ring, i),
                       spec["batch_per_chip"] * n)
            for i in range(spec["ring"])]
    shard_devices = {s.device.id for s in
                     jax.tree.leaves(ring[0])[0].addressable_shards}

    def batch_of(i):
        batch = ring[i % len(ring)]
        return {**batch, m.carry_key: carry} if m.carry_key else batch

    step = build_train_step(
        m.loss_fn, m.optimizer, mesh, batch_spec=m.batch_spec,
        loss_has_aux=m.has_aux, donate=True, **m.step_kwargs)
    t = clock()
    executable, _ = aot_compile(step, params, opt_state, batch_of(0))
    compile_s = clock() - t
    overlap = last_overlap_info()
    say("compiled", compile_s=compile_s,
        params_m=sum(p.size for p in jax.tree.leaves(params)) / 1e6,
        buckets=overlap.get("buckets", 0),
        bucket_bytes=overlap.get("bucket_bytes"),
        batch_shard_devices=sorted(shard_devices))

    Span = jax.profiler.TraceAnnotation

    def one_step(i):
        """One training step, ended by the host's read of its loss:
        (loss, seconds of the step, seconds of its dispatch)."""
        nonlocal params, opt_state, carry
        with Span("perfbench.batch_swap"):
            batch = batch_of(i)
        t_a = clock()
        with Span("perfbench.dispatch"):
            params, opt_state, metrics = executable(params, opt_state,
                                                    batch)
        t_b = clock()
        with Span("perfbench.loss_read"):
            loss = float(metrics["loss"])
        t_c = clock()
        carry = metrics.get("aux")
        return loss, t_c - t_a, t_b - t_a

    warmup = [one_step(i)[0] for i in range(WARMUP_STEPS)]

    window: List[tuple] = []
    failed = 0
    counter.on = True
    window_start = clock()
    while clock() - window_start < seconds:
        try:
            window.append(one_step(WARMUP_STEPS + len(window)))
        except Exception as e:  # the buffers were donated: stop here
            failed += 1
            say("step_failed", step=len(window), error=repr(e))
            break
    window_s = clock() - window_start
    counter.on = False
    losses, step_s, dispatch_s = (list(col) for col in zip(*window)) \
        if window else ([], [], [])
    steps = len(window)
    attempted = steps + failed
    failed += sum(not math.isfinite(x) for x in losses)

    traced = None
    if trace and not failed:
        from perfbench import trace_reduce
        trace_dir = os.path.join(out_dir, "trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans come from Span
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            for j in range(spec["traced_steps"]):
                with Span("perfbench.step"):
                    one_step(WARMUP_STEPS + steps + j)
        finally:
            jax.profiler.stop_trace()
        traced = trace_reduce.reduce_file(
            trace_reduce.newest_xplane(trace_dir), n_chips=n)

    ring_n = len(ring)
    falling = steps > ring_n and \
        np.mean(losses[-ring_n:]) < np.mean(losses[:ring_n])
    checks = {
        "sample_agrees_with_reference": bool(sample_ok),
        "no_compilation_in_window": counter.n == 0,
        "losses_finite_and_falling": bool(failed == 0 and falling),
        "every_chip_holds_a_batch_shard": len(shard_devices) == n,
    }
    peak = max(device_peak_bytes(d) for d in devices)
    # a chip's share of the global batch, in the cell's unit of work
    rate = steps * spec["batch_per_chip"] * m.units_per_sample / window_s
    slowest = sorted(range(steps), key=lambda k: -step_s[k])[:5]
    say("window", steps=steps, seconds=window_s, checks=checks,
        compilations_in_window=counter.n, warmup_losses=warmup,
        first_losses=losses[:LOSSES_KEPT],
        step_ms_median=1e3 * float(np.median(step_s)) if steps else None,
        slowest_steps=[{"step": k, "ms": 1e3 * step_s[k],
                        "dispatch_ms": 1e3 * dispatch_s[k]}
                       for k in slowest],
        memory_stats=first.memory_stats())
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, cell.name + ".window.json"),
              "w") as f:
        json.dump({"seed": seed, "warmup_losses": warmup,
                   "losses": losses[:LOSSES_KEPT],
                   "step_ms": [1e3 * x for x in step_s],
                   "dispatch_ms": [1e3 * x for x in dispatch_s]}, f)

    device["memory_peak_bytes"] = peak
    breakdown = None
    if traced:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        breakdown = {"device_ops": traced["device_ops"],
                     "idle_gaps": traced["idle_gaps"]}
    end_to_end = {
        spec["rate_metric"]: rate,
        "step_ms_p95": 1e3 * float(np.percentile(step_s, 95)),
        "peak_hbm_gb": peak / 1e9,
        "setup_s": window_start - process_start,
    } if steps else {}
    context = dict(
        compile_s=compile_s, dispatch_s=dispatch_s, step_s=step_s,
        rate_per_chip=rate, flops_per_unit=m.flops_per_unit,
        device_kind=first.device_kind, n_chips=n, traced=traced)
    return SimpleNamespace(
        correct=all(checks.values()), attempted=attempted, failed=failed,
        end_to_end=end_to_end, device=device, breakdown=breakdown,
        context=context)
