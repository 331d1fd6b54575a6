#!/usr/bin/env python
"""Eager-path response-cache microbenchmark (reference:
horovod/common/response_cache.cc — the cache's point is cheaper
steady-state negotiation). Launches two 2-process jobs — cache
enabled vs HOROVOD_CACHE_CAPACITY=0 — and reports per-op eager
allreduce latency and control-plane bytes for each.

Honest expectation-setting: on CPU loopback the per-op latency is
dominated by the engine cycle time and XLA dispatch, so the p50s come
out equal — what the cache measurably collapses here is steady-state
control TRAFFIC (~6x, approaching the 5-byte-id floor), which is the
term that matters when thousands of tensors negotiate per cycle over
a real DCN hop (the reference's motivation for the cache).

Run:  python benchmarks/eager_cache_latency.py [--iters 300]
"""

import argparse
import json
import os
import statistics
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WARMUP = 20   # compile + cache-fill ops before timing; shared with tests


def worker(iters: int) -> None:
    sys.path.insert(0, REPO)
    import time

    import jax.numpy as jnp

    import horovod_tpu as hvd

    hvd.init()
    x = jnp.ones(1024, jnp.float32)
    for _ in range(WARMUP):                  # warm: compile + cache fill
        hvd.allreduce(x, name="t")
    lat = []
    for _ in range(iters):
        t0 = time.perf_counter()
        hvd.allreduce(x, name="t")
        lat.append(time.perf_counter() - t0)
    from horovod_tpu.common.basics import _require_init
    core = _require_init().engine.controller.core
    bytes_sent = core.control_bytes()
    if hvd.rank() == 1:                       # rank 1 serializes over TCP
        print("RESULT " + json.dumps({
            "p50_us": statistics.median(lat) * 1e6,
            "p99_us": sorted(lat)[int(len(lat) * 0.99)] * 1e6,
            "control_bytes": bytes_sent,
            "iters": iters,
        }), flush=True)
    hvd.shutdown()


def run_job(iters: int, cache_capacity: int) -> dict:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["HOROVOD_CACHE_CAPACITY"] = str(cache_capacity)
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         sys.executable, os.path.abspath(__file__), "--worker",
         "--iters", str(iters)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        raise RuntimeError(r.stdout + r.stderr)
    for line in r.stdout.splitlines():
        if "RESULT " in line:
            return json.loads(line.split("RESULT ", 1)[1])
    raise RuntimeError("no RESULT line:\n" + r.stdout)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=300)
    ap.add_argument("--worker", action="store_true")
    args = ap.parse_args()
    if args.worker:
        worker(args.iters)
        return
    on = run_job(args.iters, cache_capacity=1024)
    off = run_job(args.iters, cache_capacity=0)
    per_op_on = on["control_bytes"] / (on["iters"] + WARMUP)
    per_op_off = off["control_bytes"] / (off["iters"] + WARMUP)
    print(f"cache ON : p50 {on['p50_us']:8.1f} us  "
          f"p99 {on['p99_us']:8.1f} us  "
          f"{per_op_on:6.1f} control bytes/op")
    print(f"cache OFF: p50 {off['p50_us']:8.1f} us  "
          f"p99 {off['p99_us']:8.1f} us  "
          f"{per_op_off:6.1f} control bytes/op")
    print(f"steady-state control traffic: {per_op_off / per_op_on:.1f}x "
          "smaller with the cache")


if __name__ == "__main__":
    main()
