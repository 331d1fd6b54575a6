"""Eager execution engine: handles, ordering, timeline/autotune hooks.

TPU-native rethink of the reference's background-thread core
(reference: horovod/common/operations.cc — BackgroundThreadLoop /
RunLoopOnce / PerformOperation; horovod/common/tensor_queue.cc).

Key design departure, deliberate: the reference needs a background
thread because cudaMemcpy/NCCL calls are synchronous w.r.t. the caller
and must be overlapped manually. XLA dispatch is *already* asynchronous
— a jitted collective returns future-backed jax.Arrays immediately and
executes on the device timeline. So the eager engine dispatches inline
(keeping the caller's program order, which multi-controller SPMD
requires) and gets comm/compute overlap for free; `synchronize()` is
the only blocking point, exactly like the reference's HandleManager
(reference: horovod/torch/handle_manager.cc).

The negotiation/fusion cycle layer (reference: controller.cc) sits on
top of this in ops/controller.py: when enabled it batches pending
tensors into fused groups per cycle with a cross-rank agreed order,
relaxing the same-program-order requirement.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Any, Callable, Dict, Optional

import jax

from .. import tracing as _tracing
from ..common import logging as hlog
from ..metrics import LATENCY_BUCKETS, REGISTRY as _METRICS


class Handle:
    """Async op handle (reference: horovod/torch/handle_manager.cc)."""

    __slots__ = ("id", "result", "error", "_done", "name")

    def __init__(self, hid: int, name: str):
        self.id = hid
        self.name = name
        self.result: Any = None
        self.error: Optional[BaseException] = None
        self._done = threading.Event()

    def set_result(self, result: Any) -> None:
        self.result = result
        self._done.set()

    def set_error(self, err: BaseException) -> None:
        self.error = err
        self._done.set()

    def done(self) -> bool:
        if not self._done.is_set():
            return False
        if self.error is None and self.result is not None:
            return _results_ready(self.result)
        return True

    def wait(self) -> Any:
        """Block until the op is agreed, launched, and delivered;
        framework-level failures (negotiation errors, launch
        exceptions) raise here, exactly like the reference's
        synchronize(). The returned jax.Arrays are ASYNC futures —
        consuming them awaits device completion (XLA-native
        semantics). Deliberately NOT jax.block_until_ready here: a
        per-handle device barrier costs one host<->device round trip
        per tensor and forfeits the async overlap the whole design
        exists for; callers needing a hard device barrier call
        jax.block_until_ready on the result."""
        self._done.wait()
        if self.error is not None:
            raise self.error
        return self.result


def _results_ready(res: Any) -> bool:
    leaves = jax.tree_util.tree_leaves(res)
    for leaf in leaves:
        if isinstance(leaf, jax.Array):
            try:
                if not leaf.is_ready():
                    return False
            except AttributeError:  # older jax without is_ready
                pass
    return True


class Engine:
    """Owns handle bookkeeping, op naming, and the observer hooks; the
    actual collective math lives in ops/dispatch.py."""

    def __init__(self, cfg, topology, pset_table):
        self.cfg = cfg
        self.topology = topology
        self.pset_table = pset_table
        self._handles: Dict[int, Handle] = {}
        self._hid = itertools.count(1)
        self._name_counters: Dict[str, itertools.count] = {}
        self._lock = threading.Lock()
        # Frontends (torch) keep per-handle metadata keyed on the
        # integer id; they register a hook here so their entry dies
        # WITH the engine's handle — releasing via any path (torch
        # synchronize, raw collective_ops.synchronize, future GC
        # sweeps) frees both sides, instead of orphaned metadata
        # accumulating until session end.
        self._release_hooks: list = []
        self.timeline = None
        self.autotuner = None
        self.controller = None      # negotiated-cycle controller (optional)
        self.order_check = None
        if getattr(cfg, "order_check", False):
            from .order_check import OrderCheck
            self.order_check = OrderCheck()
        self._shutdown = False
        # Process-wide metrics. _bytes_processed was a bare unlocked
        # int accumulated from both the caller thread (inline path)
        # and the controller's dispatch worker — a data race; the
        # thread-safe Counter is the fix AND the export. Counters
        # outlive engine instances (process-wide), so the per-engine
        # shutdown log diffs against the value at construction.
        self._bytes_processed = _METRICS.counter(
            "hvd_engine_bytes_total",
            "Payload bytes dispatched through the eager engine.")
        self._ops_processed = _METRICS.counter(
            "hvd_engine_ops_total",
            "Eager ops dispatched through the engine (inline path).")
        self.dispatch_latency = _METRICS.histogram(
            "hvd_dispatch_latency_seconds",
            "Host-side dispatch latency per eager launch (async XLA "
            "dispatch, not device completion).",
            buckets=LATENCY_BUCKETS)
        self._bytes_at_start = self._bytes_processed.value()

    # -- hooks ---------------------------------------------------------------
    def attach_timeline(self, timeline) -> None:
        self.timeline = timeline

    def attach_autotuner(self, autotuner) -> None:
        self.autotuner = autotuner

    # -- naming --------------------------------------------------------------
    def auto_name(self, kind: str) -> str:
        """allreduce.noname.N-style deterministic names
        (reference: horovod/torch/mpi_ops.py name counters)."""
        with self._lock:
            ctr = self._name_counters.setdefault(kind, itertools.count())
            return f"{kind}.noname.{next(ctr)}"

    # -- handle management ---------------------------------------------------
    def new_handle(self, name: str) -> Handle:
        h = Handle(next(self._hid), name)
        with self._lock:
            self._handles[h.id] = h
        return h

    def get_handle(self, hid: int) -> Handle:
        with self._lock:
            return self._handles[hid]

    def add_release_hook(self, fn) -> None:
        """Register `fn(hid)` to run whenever a handle id is
        released (idempotent per function object)."""
        with self._lock:
            if fn not in self._release_hooks:
                self._release_hooks.append(fn)

    def release_handle(self, hid: int) -> None:
        with self._lock:
            self._handles.pop(hid, None)
            hooks = list(self._release_hooks)
        for fn in hooks:
            fn(hid)

    # -- execution -----------------------------------------------------------
    def run(self, name: str, nbytes: int,
            fn: Callable[[], Any]) -> Handle:
        """Dispatch `fn` (a closure over ops.dispatch) inline, recording
        timeline phases and autotune throughput."""
        if self._shutdown:
            raise RuntimeError("horovod_tpu engine is shut down")
        h = self.new_handle(name)
        t0 = time.perf_counter()
        # Inline dispatch gets NO cross-rank sequence id: subset
        # process-set ops run here on member ranks only, so advancing
        # the shared counter would shift the controller's agreed ids
        # differently per rank. seq=-1 marks a local-only span.
        _tracing.record("dispatch", name)
        if self.timeline is not None:
            self.timeline.enqueue(name)
        try:
            # TraceAnnotation names the host-side dispatch span in
            # jax.profiler/XPlane traces so device timelines line up
            # with the per-tensor semantic lanes (SURVEY.md §5.1's
            # "rebuild the semantic layer" guidance). Only built while
            # a profiler session is live — the annotation is invisible
            # outside a capture, but its construction is not free on
            # the per-op hot path.
            cm = (jax.profiler.TraceAnnotation(f"hvd::{name}")
                  if _tracing.profiler_active()
                  else contextlib.nullcontext())
            with cm:
                result = fn()
            h.set_result(result)
        except BaseException as e:
            h.set_error(e)
            _tracing.record("error", name)
            if self.timeline is not None:
                self.timeline.error(name)
            return h
        _tracing.record("dispatched", name,
                        arg=time.perf_counter() - t0)
        if self.timeline is not None:
            self.timeline.dispatched(name)
        if self.order_check is not None:
            self.order_check.record(name)
        self.dispatch_latency.observe(time.perf_counter() - t0)
        self._bytes_processed.inc(nbytes)
        self._ops_processed.inc()
        if self.autotuner is not None:
            # Throughput scoring needs the wall time to completion, not
            # async-dispatch latency, so block only when autotuning.
            jax.block_until_ready(result)
            self.autotuner.record(nbytes, time.perf_counter() - t0)
        return h

    def synchronize(self, h: Handle) -> Any:
        res = h.wait()
        _tracing.record("done", h.name)
        if self.timeline is not None:
            self.timeline.done(h.name)
        self.release_handle(h.id)
        return res

    def shutdown(self) -> None:
        self._shutdown = True
        if self.controller is not None:
            self.controller.shutdown()
            self.controller = None
        hlog.debug("engine shut down; %d bytes processed",
                   int(self._bytes_processed.value()
                       - self._bytes_at_start))
