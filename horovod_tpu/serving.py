"""Elastic inference serving on the existing control plane.

The north star is a system "serving heavy traffic from millions of
users", and after the training-side rounds every ingredient a serving
tier needs already exists in this repo: AOT compilation
(parallel/aot.py), elastic membership with liveness detection
(runner/elastic/driver.py), queue/latency gauges (metrics.py), the
fault grammar (faults.py) and the lifecycle journal (journal.py).
This module composes them — it adds no new distributed primitive.

The serving tier has two planes (round 18):

- **The request/response plane (this module).** One-shot inference:
  a request is one array in, one array out; the unit of scheduling,
  retry and exactly-once delivery is the *batch*, cut by a central
  batcher thread against a latency budget.

- **The decode plane (decoding.py).** Autoregressive decode: a
  sequence lives across hundreds of steps, scheduling is
  iteration-level (continuous batching — sequences join/leave the
  running batch per decode step), the KV cache rides its own pow2
  page ladder (`KVLadder`, the same digest-pin discipline as this
  module's `BucketLadder`), and the unit of exactly-once delivery is
  the *token*: a per-(sequence, epoch) latch generalizing this
  module's per-batch completion latch, with journaled KV watermarks
  so a dead worker's in-flight sequences resume on survivors without
  re-emitting a delivered token.  The r16 attribution pinned the
  scale-out regression on this module's single batcher loop
  (batch_cut 95.1%); the decode plane therefore replaces the central
  batcher with per-worker admission queues plus work-stealing.

Shared between the planes: `BucketLadder`/`_pow2_ladder` shape
discipline, `_pct` percentile rules, the BasicService HMAC wire, the
faults/journal/metrics seams, and `doctor serve` — whose
serving_report folds both planes' journals (`batch_trace` vs
`seq_admitted`/`seq_watermark`/`seq_resumed`/`seq_done`).

Architecture (driver-side `ServingFrontend` + an elastic worker pool):

- **Admission / dynamic batching.** `submit()` enqueues one request;
  a batcher thread cuts a batch when it reaches
  HOROVOD_SERVING_MAX_BATCH or when the oldest queued request has
  waited HOROVOD_SERVING_LATENCY_BUDGET_MS — throughput when traffic
  is heavy, bounded latency when it is not.

- **Padded-bucket shapes.** Batches are padded to a deterministic
  power-of-two `BucketLadder` over the batch axis (and, when
  HOROVOD_SERVING_MAX_LEN > 0, a variable leading sequence axis), so
  every batch hits one of a small, closed set of executable shapes
  that workers AOT-compile at warmup: no request shape ever triggers
  a recompile. Like `OverlapPlan`, the ladder is pinned by a
  canonical digest every process derives identically.

- **Elastic pool.** Workers are in-process threads (`start_pool`,
  one per local device round-robin) and/or remote processes pulling
  batches over the HMAC-signed control-plane wire
  (`serve_endpoint()` / `remote_worker_loop()` — the same
  BasicService idiom as the launcher services). The pool autoscales
  off the queue-depth gauge between HOROVOD_SERVING_MIN_WORKERS and
  HOROVOD_SERVING_MAX_WORKERS, and `on_membership` plugs directly
  into `ElasticDriver.add_membership_listener` so elastic membership
  epochs drive pool size.

- **Exactly-once completion.** A worker that dies mid-batch — the
  `serving.batch` fault seam, a missed per-batch deadline
  (HOROVOD_SERVING_WORKER_TIMEOUT_S, the serving-side heartbeat
  detector), or a real process kill — gets its in-flight batches
  requeued at the head of the dispatch queue (journal record
  `batch_retried`). Each request's future carries a completion latch:
  late results from a revenant worker are suppressed and counted,
  never double-delivered, and a request is failed (visibly — never
  silently dropped) only after HOROVOD_SERVING_RETRY_LIMIT
  re-dispatches.

Observability: the `hvd_serving_*` metric family (request-latency
histogram on the SERVING_LATENCY_BUCKETS ladder, queue depth, pool
size, retries, suppressed duplicates, compile count) plus typed
journal records `batch_admitted` / `batch_retried` / `scale_event`.

Request-lifecycle tracing (round 16, HOROVOD_SERVING_TRACE): every
future carries monotonic-ns phase stamps across its whole life —
enqueue → batch-cut → queue-wait → worker claim → pad → compute →
unpad → complete — with each dispatch attempt recorded as a `_Hop`
(retry hops become linked child spans in `write_timeline()`'s
Chrome-trace lanes). Phase edges ride the PR 5 flight-recorder ring
(`tracing.record`) and a registered postmortem provider, so a
SIGKILLed worker's in-flight request ids and their last completed
phase land in `postmortem-rank{r}.json`; completed batches emit
`batch_trace` journal events that `doctor serve` (serving_trace.py)
folds into the byte-deterministic `serving_report.json`. Aggregates:
`hvd_serving_phase_seconds{phase}`, per-SLO-class
`hvd_serving_goodput_total` / `hvd_serving_slo_miss_total`
(deadline from `submit(x, slo_ms=...)`, defaulting to the latency
budget), and the dispatch-loop health gauges
`hvd_serving_batch_loop_occupancy` / `hvd_serving_latch_wait_seconds`
that say whether the single batcher loop or the completion latch
serializes scale-out. Disarmed, the submit path's trace seam is one
attribute load + compare (the faults.fire discipline).
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import numpy as np

from . import faults as _faults
from . import journal as _journal
from . import telemetry as _telemetry
from . import tracing as _tracing
from . import weights as _weights_mod
from .common import config as _config
from .common import logging as hlog
from .metrics import (COUNT_BUCKETS, REGISTRY as _METRICS,
                      SERVING_LATENCY_BUCKETS,
                      SERVING_PHASE_BUCKETS)
from .parallel.aot import aot_compile

LADDER_SCHEMA = "serving-ladder-v1"

_m_requests = _METRICS.counter(
    "hvd_serving_requests_total",
    "Serving requests by terminal outcome (ok / failed). Zero "
    "dropped requests means submitted == ok + failed at close.",
    ("outcome",))
_m_batches = _METRICS.counter(
    "hvd_serving_batches_total",
    "Dynamic batches admitted, by padded batch-bucket size.",
    ("bucket",))
_m_retries = _METRICS.counter(
    "hvd_serving_retries_total",
    "Batches re-dispatched after a worker died mid-batch, by cause.",
    ("cause",))
_m_latency = _METRICS.histogram(
    "hvd_serving_request_latency_seconds",
    "Submit-to-completion latency per request (queueing + padding + "
    "executable run + any retries).",
    buckets=SERVING_LATENCY_BUCKETS)
_m_batch_size = _METRICS.histogram(
    "hvd_serving_batch_fill",
    "Real (unpadded) requests per admitted batch.",
    buckets=COUNT_BUCKETS)
_m_queue = _METRICS.gauge(
    "hvd_serving_queue_depth",
    "Requests admitted but not yet dispatched to a worker (the "
    "autoscaler's scale-out signal).")
_m_workers = _METRICS.gauge(
    "hvd_serving_workers",
    "Live members of the serving worker pool.")
_m_compiles = _METRICS.counter(
    "hvd_serving_compiles_total",
    "Executable compilations across the pool — bounded by "
    "workers x ladder shapes; growth under traffic means a request "
    "shape escaped the bucket ladder.")
_m_padding = _METRICS.counter(
    "hvd_serving_padding_rows_total",
    "Padding rows executed (bucket size minus real batch fill) — "
    "the throughput cost of the no-recompile pin.")
_m_dupes = _METRICS.counter(
    "hvd_serving_duplicates_suppressed_total",
    "Late completions from revenant workers rejected by the "
    "per-request exactly-once latch.")
_m_phase = _METRICS.histogram(
    "hvd_serving_phase_seconds",
    "Per-request lifecycle decomposition (HOROVOD_SERVING_TRACE): "
    "batch_cut (enqueue to batch admission), queue_wait (admission "
    "to worker claim), pad, compute, unpad, complete (unpad to "
    "latch). The winning dispatch attempt's stamps; retries show up "
    "as inflated queue_wait.",
    ("phase",), buckets=SERVING_PHASE_BUCKETS)
_m_goodput = _METRICS.counter(
    "hvd_serving_goodput_total",
    "Requests completed within their SLO deadline, by SLO class "
    "(the slo_ms= passed to submit(); 'default' = the latency "
    "budget / HOROVOD_SERVING_DEFAULT_SLO_MS).",
    ("slo",))
_m_slo_miss = _METRICS.counter(
    "hvd_serving_slo_miss_total",
    "Requests that missed their SLO deadline, by class and reason: "
    "late = completed past the deadline, failed = never completed "
    "(retry budget exhausted or frontend closed).",
    ("slo", "reason"))
_m_loop_occupancy = _METRICS.gauge(
    "hvd_serving_batch_loop_occupancy",
    "Busy fraction of the single dispatch (batcher) loop over the "
    "window since the previous admission — sustained values near "
    "1.0 mean the loop itself serializes scale-out.")
_m_latch_wait = _METRICS.gauge(
    "hvd_serving_latch_wait_seconds",
    "Wall seconds the most recent completing worker spent inside "
    "_complete_batch (per-request latches + the frontend lock) — "
    "the completion-side serialization cost per batch.")


class ServingError(RuntimeError):
    """A request failed visibly (retry budget exhausted / shutdown)."""


class _WorkerDied(RuntimeError):
    """Internal: the serving.batch seam's 'error' action."""


# ---------------------------------------------------------------------------
# Bucket ladder


class BucketLadder(NamedTuple):
    """Deterministic padded-shape ladder. `digest` is the canonical
    string every process derives identically from the same knobs —
    the cross-process pin (same idiom as OverlapPlan's assignment
    digest): frontends and workers that disagree on it would compile
    different executable sets, and comparing digests catches that
    before any batch is dispatched."""

    batch_buckets: Tuple[int, ...]
    len_buckets: Tuple[int, ...]  # () = fixed-shape requests
    digest: str

    def batch_bucket(self, n: int) -> int:
        for b in self.batch_buckets:
            if b >= n:
                return b
        raise ServingError(
            f"batch of {n} exceeds ladder max {self.batch_buckets[-1]}")

    def len_bucket(self, length: int) -> int:
        for b in self.len_buckets:
            if b >= length:
                return b
        raise ServingError(
            f"request length {length} exceeds ladder max "
            f"{self.len_buckets[-1]}")

    def shapes(self, feature_shape: Sequence[int]
               ) -> List[Tuple[int, ...]]:
        """Every padded executable shape the ladder admits."""
        feats = tuple(feature_shape)
        if not self.len_buckets:
            return [(b,) + feats for b in self.batch_buckets]
        return [(b, l) + feats
                for b in self.batch_buckets for l in self.len_buckets]


def _pow2_ladder(lo: int, hi: int) -> Tuple[int, ...]:
    rungs = []
    b = lo
    while b < hi:
        rungs.append(b)
        b *= 2
    rungs.append(hi)
    return tuple(rungs)


def build_ladder(max_batch: Optional[int] = None,
                 max_len: Optional[int] = None,
                 env: Optional[Dict[str, str]] = None) -> BucketLadder:
    """Build the ladder from the HOROVOD_SERVING_* knobs (or explicit
    overrides): powers of two up to max_batch on the batch axis, and
    — when max_len > 0 — powers of two from 16 up to max_len on the
    variable leading axis."""
    if max_batch is None:
        max_batch = _config.env_value("HOROVOD_SERVING_MAX_BATCH",
                                      env=env)
    if max_len is None:
        max_len = _config.env_value("HOROVOD_SERVING_MAX_LEN", env=env)
    if max_batch < 1:
        raise ValueError(f"HOROVOD_SERVING_MAX_BATCH must be >= 1, "
                         f"got {max_batch}")
    batch = _pow2_ladder(1, max_batch)
    lens: Tuple[int, ...] = ()
    if max_len and max_len > 0:
        lens = ((max_len,) if max_len <= 16
                else _pow2_ladder(16, max_len))
    digest = "{}|b={}|l={}".format(
        LADDER_SCHEMA, ",".join(str(b) for b in batch),
        ",".join(str(l) for l in lens) or "-")
    return BucketLadder(batch, lens, digest)


# ---------------------------------------------------------------------------
# Requests and batches

# Lifecycle phases, in request order. Every completed request's
# latency decomposes exactly into these (stamps from the winning
# dispatch attempt): batch_cut = enqueue to batch admission,
# queue_wait = admission to worker claim (inflated by retries — a
# requeued batch goes back through the dispatch queue), pad = claim
# to executable entry (padding + host→device transfer), compute =
# executable run (for remote members: the pull→push round trip,
# wire included), unpad = output slicing, complete = unpad to the
# exactly-once latch. serving_trace.py carries the same list.
PHASES = ("batch_cut", "queue_wait", "pad", "compute", "unpad",
          "complete")


def _pct(sorted_vals: Sequence[int], q: float) -> int:
    """Nearest-rank percentile over an already-sorted sequence —
    deterministic (no interpolation), shared with serving_trace.py's
    offline aggregation so live digests and doctor-serve reports
    agree bit-for-bit on the same samples."""
    if not sorted_vals:
        return 0
    rank = max(1, int(-(-q * len(sorted_vals) // 1)))  # ceil
    return sorted_vals[min(len(sorted_vals), rank) - 1]


class _Hop:
    """One dispatch attempt of one batch: which worker claimed it and
    the monotonic-ns stamps of its execution edges. The winning hop's
    stamps become the requests' phase decomposition; losing hops keep
    their outcome (`retried:<cause>`) so retry chains reconstruct as
    linked child spans in `write_timeline()` and `doctor serve`."""

    __slots__ = ("worker", "attempt", "t_claim_ns", "t_exec0_ns",
                 "t_exec1_ns", "t_unpad1_ns", "outcome")

    def __init__(self, worker: str, attempt: int):
        self.worker = worker
        self.attempt = attempt
        self.t_claim_ns = time.monotonic_ns()
        self.t_exec0_ns = 0
        self.t_exec1_ns = 0
        self.t_unpad1_ns = 0
        self.outcome = "pending"

    def summary(self) -> List[Any]:
        return [self.worker, self.attempt, self.outcome,
                self.t_claim_ns]


class ServingFuture:
    """One request's handle. `result()` blocks until the request
    completes (the padded row of the executable's output) or fails
    with ServingError. The `_finish` latch is the exactly-once
    guarantee: whichever worker finishes first wins, every later
    completion is suppressed and counted."""

    def __init__(self, req_id: str, payload: np.ndarray,
                 slo_ms: float = 0.0, slo_class: str = "default"):
        self.id = req_id
        self.payload = payload
        self.t_submit = time.monotonic()
        self.t_submit_ns = time.monotonic_ns()
        self.t_done: Optional[float] = None
        self.t_done_ns = 0
        self.slo_ms = slo_ms
        self.slo_class = slo_class
        # Deadline on the same clock as t_submit/t_done; 0 slo means
        # no deadline was derivable (goodput then counts it a hit).
        self.deadline = (self.t_submit + slo_ms / 1e3 if slo_ms > 0
                         else float("inf"))
        self._event = threading.Event()
        self._lock = threading.Lock()
        self._value: Any = None
        self._error: Optional[BaseException] = None

    def _finish(self, value: Any = None,
                error: Optional[BaseException] = None) -> bool:
        with self._lock:
            if self._event.is_set():
                return False
            self._value, self._error = value, error
            self.t_done = time.monotonic()
            self.t_done_ns = time.monotonic_ns()
            self._event.set()
            return True

    @property
    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> Any:
        if not self._event.wait(timeout):
            raise TimeoutError(f"request {self.id} still pending")
        if self._error is not None:
            raise self._error
        return self._value


class _Batch:
    __slots__ = ("id", "requests", "bucket_b", "bucket_len",
                 "attempts", "t_admitted", "t_admit_ns", "hops")

    def __init__(self, bid: str, requests: List[ServingFuture],
                 bucket_b: int, bucket_len: int):
        self.id = bid
        self.requests = requests
        self.bucket_b = bucket_b
        self.bucket_len = bucket_len
        self.attempts = 0
        self.t_admitted = time.monotonic()
        self.t_admit_ns = time.monotonic_ns()
        self.hops: List[_Hop] = []

    @property
    def done(self) -> bool:
        return all(r.done for r in self.requests)


class _RemoteMember:
    """A pool member living in another process, known only through
    its pulls on the wire; liveness is per-batch (the dispatch
    deadline), not per-connection."""

    __slots__ = ("wid", "t_joined")

    def __init__(self, wid: str):
        self.wid = wid
        self.t_joined = time.monotonic()


# ---------------------------------------------------------------------------
# Local (in-process) worker


class _LocalWorker:
    """One pool member: a thread owning a per-shape executable cache,
    AOT-compiled at warmup for every ladder shape (pinned against
    recompiles by `compiles`, which traffic must never grow)."""

    def __init__(self, frontend: "ServingFrontend", wid: str, device):
        self.frontend = frontend
        self.wid = wid
        self.device = device
        self.compiles = 0
        self._compiled: Dict[Tuple[int, ...], Callable] = {}
        # Live weight pipeline: the params this worker serves, the
        # version they came from, and the last version it rejected
        # (a rejected seq is never re-attempted — the publisher's
        # retry bumps the seq, which is how the pool converges).
        self._params = None
        self._w_version: Optional[_weights_mod.WeightVersion] = None
        self._w_digest = frontend._params0_digest
        self._w_rejected_seq = -1
        self._thread = threading.Thread(
            target=self._run, name=f"hvd-serving-{wid}", daemon=True)
        self._thread.start()

    def _get_exec(self, shape: Tuple[int, ...]) -> Callable:
        import jax
        import jax.numpy as jnp
        fn = self._compiled.get(shape)
        if fn is None:
            ex = jnp.zeros(shape, self.frontend._dtype.name)
            if self.device is not None:
                ex = jax.device_put(ex, self.device)
            if self._params is not None:
                # Two-arg (params, x) forward: the executable is
                # specialized on the params' shapes/dtypes only, so
                # it survives hot-swaps (adoption enforces an
                # identical tree) without recompiling.
                fn, _ = aot_compile(self.frontend._jitted,
                                    self._params, ex)
            else:
                fn, _ = aot_compile(self.frontend._jitted, ex)
            self._compiled[shape] = fn
            self.compiles += 1
            _m_compiles.inc()
        return fn

    def _maybe_adopt(self) -> None:
        """Hot-swap to the frontend's adoption target, strictly
        BETWEEN batches — this call site is the epoch fence: a batch
        executes entirely on the params installed here, so no served
        batch ever mixes weight versions. Any failure (digest
        mismatch, torn shard, structure drift) leaves the previous
        version serving; `weights.adopt` faults propagate to the
        caller as a worker death mid-swap."""
        import jax
        fe = self.frontend
        if fe._weights_sub is None:
            return
        with fe._lock:
            tgt = fe._weights_target
        if (tgt is None or tgt.seq == self._w_rejected_seq
                or (self._w_version is not None
                    and tgt.seq <= self._w_version.seq)):
            return
        _faults.fire("weights.adopt", exc=_WorkerDied, tag=self.wid)
        t0 = time.monotonic()
        try:
            tree = fe._load_weights(tgt)
            params = jax.device_put(tree, self.device)
            jax.block_until_ready(params)
        except Exception as e:  # noqa: BLE001 — degrade, keep serving
            self._w_rejected_seq = tgt.seq
            reason = _weights_mod.rejection_reason(e)
            hlog.warning("serving: worker %s rejected weights "
                         "seq=%d digest=%s (%s): %s", self.wid,
                         tgt.seq, tgt.digest, reason, e)
            _weights_mod.note_rejected(self.wid, tgt, reason,
                                       str(e), self._w_digest)
            with fe._lock:
                fe.weight_rejections += 1
            return
        self._params = params
        self._w_version = tgt
        self._w_digest = tgt.digest
        with fe._lock:
            fe.weight_swaps += 1
            latest = fe._weights_target
        _weights_mod.note_adopted(
            self.wid, tgt, time.monotonic() - t0,
            (latest.step - tgt.step) if latest is not None else 0)

    def _run(self) -> None:
        import jax
        fe = self.frontend
        try:
            if fe._params0 is not None:
                # Bootstrap params on this worker's device; the
                # first fence pass below swaps to the published
                # CURRENT version if one exists.
                self._params = jax.device_put(fe._params0,
                                              self.device)
            for shape in fe.ladder.shapes(fe._feature_shape):
                self._get_exec(shape)
        except Exception as e:  # noqa: BLE001 — warmup must not hang pool
            hlog.error("serving: worker %s warmup failed: %s",
                       self.wid, e)
            fe._worker_failed(self.wid, "warmup")
            return
        while True:
            if fe._retired(self.wid):
                return
            try:
                self._maybe_adopt()
            except _WorkerDied:
                # Injected death mid-swap: this member is gone; the
                # pool floor is restored by the autoscaler and its
                # inflight batch (if any) is requeued on survivors.
                fe._worker_failed(self.wid, "weights_fault")
                return
            batch = fe._next_batch(self.wid, timeout=0.05)
            if batch is None:
                if fe._closing:
                    return
                continue
            try:
                act = _faults.fire("serving.batch", exc=_WorkerDied,
                                   tag=self.wid)
            except _WorkerDied:
                # Injected mid-batch death: this member is gone; the
                # frontend requeues the batch on a survivor.
                fe._worker_failed(self.wid, "fault_error")
                return
            if act == "hang":
                # Park holding the batch until well past the dispatch
                # deadline (the watchdog requeues it), then fall
                # through and attempt completion anyway — the revenant
                # path the exactly-once latch must absorb.
                t_end = time.monotonic() + 4 * fe._worker_timeout
                while time.monotonic() < t_end and not fe._closing:
                    time.sleep(0.02)
            try:
                rows = self._execute(batch)
            except Exception as e:  # noqa: BLE001
                hlog.error("serving: worker %s failed batch %s: %s",
                           self.wid, batch.id, e)
                fe._worker_failed(self.wid, "execute_error")
                return
            fe._complete_batch(batch, rows, self.wid,
                               weights=self._w_digest)

    def _execute(self, batch: _Batch) -> List[np.ndarray]:
        import jax
        import jax.numpy as jnp
        fe = self.frontend
        hop = fe._hop_for(batch, self.wid) if fe._trace else None
        arr = fe._pad(batch)
        x = jnp.asarray(arr)
        if self.device is not None:
            x = jax.device_put(x, self.device)
        if hop is not None:
            hop.t_exec0_ns = time.monotonic_ns()
            _tracing.record("serving_exec", batch.id,
                            seq=batch.attempts,
                            arg=float(batch.bucket_b))
        ex = self._get_exec(arr.shape)
        y = np.asarray(ex(self._params, x)
                       if self._params is not None else ex(x))
        if hop is not None:
            hop.t_exec1_ns = time.monotonic_ns()
        rows = fe._unpad(batch, y)
        if hop is not None:
            hop.t_unpad1_ns = time.monotonic_ns()
        return rows


# ---------------------------------------------------------------------------
# Frontend

# Live frontends, for the postmortem provider below: a SIGKILLed (or
# watchdog-dumped) process's postmortem-rank{r}.json must name the
# requests that were in flight and their last completed phase, or a
# death under load silently loses that attribution.
_live_frontends: "weakref.WeakSet" = weakref.WeakSet()


class ServingFrontend:
    """Driver-side request admission, dynamic batching, dispatch,
    retry, and pool management. See the module docstring for the
    architecture; every tunable is a declared HOROVOD_SERVING_* knob
    (env overridable per-instance via ``env=``)."""

    def __init__(self, forward_fn: Callable,
                 feature_shape: Sequence[int],
                 dtype: str = "float32", *,
                 env: Optional[Dict[str, str]] = None,
                 start_pool: bool = True,
                 autoscale: bool = True,
                 trace_tag: Optional[str] = None,
                 params: Optional[Any] = None,
                 weights: Optional[Any] = None):
        import jax
        self._env = env
        self._forward = forward_fn
        self._jitted = jax.jit(forward_fn)
        self._feature_shape = tuple(int(d) for d in feature_shape)
        self._dtype = np.dtype(dtype)
        # Live weight pipeline (weights.py): with ``params`` the
        # forward is two-arg (params, x) and every worker serves a
        # per-device copy; with ``weights`` (a pipeline directory or
        # a WeightSubscriber) the pool additionally tracks the
        # publisher's CURRENT version and hot-swaps between batches.
        self._params0 = params
        self._params0_digest = ""
        self._weights_names = self._weights_treedef = None
        self._weights_sub = None
        self._weights_target: Optional[
            _weights_mod.WeightVersion] = None
        self.weight_swaps = 0
        self.weight_rejections = 0
        if params is not None:
            self._weights_names, self._weights_treedef = \
                _weights_mod.tree_spec(params)
            self._weights_leaf_spec = _weights_mod.leaf_spec(params)
            self._params0_digest = _weights_mod.content_digest(
                _weights_mod.named_leaves(params))
        if weights is not None:
            if params is None:
                raise ValueError(
                    "ServingFrontend(weights=...) needs params=: "
                    "the bootstrap tree defines the structure "
                    "published versions must match (and what the "
                    "pool serves until the first adoption)")
            self._weights_sub = (
                weights if hasattr(weights, "poll")
                else _weights_mod.WeightSubscriber(str(weights),
                                                   env=env))
        self.ladder = build_ladder(env=env)
        ev = lambda name: _config.env_value(name, env=env)  # noqa: E731
        self._max_batch = ev("HOROVOD_SERVING_MAX_BATCH")
        self._budget_s = ev("HOROVOD_SERVING_LATENCY_BUDGET_MS") / 1e3
        self._min_workers = ev("HOROVOD_SERVING_MIN_WORKERS")
        self._max_workers = ev("HOROVOD_SERVING_MAX_WORKERS")
        self._scale_interval = ev("HOROVOD_SERVING_SCALE_INTERVAL_S")
        self._scale_up_queue = ev("HOROVOD_SERVING_SCALE_UP_QUEUE")
        self._scale_down_idle = ev("HOROVOD_SERVING_SCALE_DOWN_IDLE_S")
        self._retry_limit = ev("HOROVOD_SERVING_RETRY_LIMIT")
        self._worker_timeout = ev("HOROVOD_SERVING_WORKER_TIMEOUT_S")
        self._trace = bool(ev("HOROVOD_SERVING_TRACE"))
        self._weights_poll_s = max(
            0.005, ev("HOROVOD_WEIGHTS_POLL_MS") / 1e3)
        default_slo = ev("HOROVOD_SERVING_DEFAULT_SLO_MS")
        self._default_slo_ms = (default_slo if default_slo > 0
                                else self._budget_s * 1e3)
        self._trace_log: deque = deque(
            maxlen=max(1, ev("HOROVOD_SERVING_TRACE_BUFFER")))
        self.trace_tag = trace_tag

        self._lock = threading.RLock()
        self._queue_cond = threading.Condition(self._lock)
        self._dispatch_cond = threading.Condition(self._lock)
        self._queue: deque = deque()          # ServingFuture
        self._ready: deque = deque()          # _Batch
        self._inflight: Dict[str, Tuple[_Batch, str, float]] = {}
        self._batches: Dict[str, _Batch] = {}
        self._workers: Dict[str, Any] = {}
        self._closing = False
        self._draining = False
        self._remote = False
        self._service = None
        self._secret = ""
        self._req_seq = 0
        self._batch_seq = 0
        self._worker_seq = 0
        self._last_nonempty = time.monotonic()
        self.submitted = 0
        self.completed = 0
        self.failed = 0
        self.admitted = 0
        self.retries = 0
        self.dupes = 0
        self.scale_events = 0

        _journal.configure(f"serving-{trace_tag}" if trace_tag
                           else "serving", env=env)
        # Health telemetry rides the same role naming so one record
        # dir collects journal + telemetry shards side by side
        # (disarmed when HOROVOD_TELEMETRY_DIR is unset).
        _telemetry.configure(f"serving-{trace_tag}" if trace_tag
                             else "serving", env=env)
        _journal.record(
            "serving_meta", ladder=self.ladder.digest,
            max_batch=self._max_batch,
            budget_ms=round(self._budget_s * 1e3, 3),
            trace=self._trace,
            default_slo_ms=round(self._default_slo_ms, 3),
            tag=trace_tag or "",
            weights=(self._weights_sub.dir
                     if self._weights_sub is not None else ""))
        _live_frontends.add(self)
        if self._weights_sub is not None:
            self._weights_watcher = threading.Thread(
                target=self._weights_loop,
                name="hvd-serving-weights", daemon=True)
            self._weights_watcher.start()
        self._batcher = threading.Thread(
            target=self._batch_loop, name="hvd-serving-batcher",
            daemon=True)
        self._batcher.start()
        self._watchdog = threading.Thread(
            target=self._watchdog_loop, name="hvd-serving-watchdog",
            daemon=True)
        self._watchdog.start()
        if autoscale:
            self._autoscaler = threading.Thread(
                target=self._autoscale_loop,
                name="hvd-serving-autoscaler", daemon=True)
            self._autoscaler.start()
        if start_pool:
            self.start_pool(self._min_workers)

    # -- pool management ----------------------------------------------------

    def start_pool(self, n: Optional[int] = None,
                   reason: str = "start") -> None:
        """Grow the local pool to ``n`` workers (default the floor),
        round-robin over local devices."""
        target = self._min_workers if n is None else n
        with self._lock:
            cur = len(self._workers)
        if target > cur:
            self._resize(target, reason)

    def _add_local_worker(self) -> None:
        import jax
        devices = jax.local_devices()
        with self._lock:
            wid = f"w{self._worker_seq}"
            self._worker_seq += 1
            dev = (devices[(self._worker_seq - 1) % len(devices)]
                   if len(devices) > 1 else None)
            self._workers[wid] = _LocalWorker(self, wid, dev)
            _m_workers.set(len(self._workers))

    def _resize(self, target: int, reason: str,
                **extra: Any) -> None:
        target = max(self._min_workers,
                     min(self._max_workers, target))
        with self._lock:
            before = len(self._workers)
            qdepth = len(self._ready)
        if target == before:
            return
        while len(self._workers) < target:
            self._add_local_worker()
        with self._lock:
            while len(self._workers) > target:
                # Retire the newest idle-eligible member; its loop
                # observes the membership loss and exits cleanly.
                wid = next(reversed(self._workers))
                self._workers.pop(wid)
            after = len(self._workers)
            _m_workers.set(after)
            self.scale_events += 1
        _journal.record(
            "scale_event",
            direction="up" if after > before else "down",
            workers_from=before, workers_to=after,
            queue_depth=qdepth, reason=reason, **extra)

    def on_membership(self, epoch: int, infos: Sequence[Any]) -> None:
        """ElasticDriver membership listener: size the pool to the
        published world (clamped to the knob floor/ceiling). Register
        with ``driver.add_membership_listener(frontend.on_membership)``."""
        self._resize(len(infos), "membership", epoch=epoch)

    def _retired(self, wid: str) -> bool:
        with self._lock:
            return wid not in self._workers

    def _worker_failed(self, wid: str, cause: str) -> None:
        with self._lock:
            known = self._workers.pop(wid, None)
            _m_workers.set(len(self._workers))
            doomed = [b for b, (bt, owner, _) in
                      list(self._inflight.items()) if owner == wid]
            batches = [self._inflight.pop(bid)[0] for bid in doomed]
            before = len(self._workers) + (1 if known else 0)
            if known is not None:
                self.scale_events += 1
        if known is not None:
            _journal.record("scale_event", direction="down",
                            workers_from=before, workers_to=before - 1,
                            queue_depth=len(self._ready),
                            reason=f"worker_death:{cause}", worker=wid)
        for batch in batches:
            self._retry(batch, cause, wid)

    # -- admission / batching -----------------------------------------------

    def submit(self, x: Any,
               slo_ms: Optional[float] = None) -> ServingFuture:
        """Enqueue one request. ``slo_ms`` sets its completion
        deadline (and goodput class); None means the default class
        (HOROVOD_SERVING_DEFAULT_SLO_MS, falling back to the latency
        budget)."""
        arr = np.asarray(x, dtype=self._dtype)
        if self.ladder.len_buckets:
            want = self._feature_shape
            if arr.ndim != len(want) + 1 or arr.shape[1:] != want:
                raise ValueError(
                    f"request shape {arr.shape} != (L, {want})")
            self.ladder.len_bucket(arr.shape[0])  # validates length
        elif arr.shape != self._feature_shape:
            raise ValueError(
                f"request shape {arr.shape} != {self._feature_shape}")
        if slo_ms is None:
            eff_slo, slo_class = self._default_slo_ms, "default"
        else:
            eff_slo = float(slo_ms)
            slo_class = f"{eff_slo:g}ms"
        with self._lock:
            if self._closing or self._draining:
                raise ServingError("frontend is shutting down")
            self._req_seq += 1
            fut = ServingFuture(f"r{self._req_seq}", arr,
                                slo_ms=eff_slo, slo_class=slo_class)
            self._queue.append(fut)
            self.submitted += 1
            self._last_nonempty = time.monotonic()
            _m_queue.set(self._pending_locked())
            self._queue_cond.notify()
            if self._trace:
                _tracing.record("serving_submit", fut.id,
                                seq=self._req_seq)
        return fut

    def _pending_locked(self) -> int:
        return (len(self._queue)
                + sum(len(b.requests) for b in self._ready))

    def _cut_ready_locked(self) -> bool:
        if not self._queue:
            return False
        if self._draining or len(self._queue) >= self._max_batch:
            return True
        oldest = self._queue[0].t_submit
        return (time.monotonic() - oldest) >= self._budget_s

    def _batch_loop(self) -> None:
        # Occupancy: the busy fraction of this (single) loop since
        # the previous admission — everything that is not blocked in
        # cond.wait(). Sustained ~1.0 under scale-out is the "the
        # batcher loop is the bottleneck" signal ROADMAP item 2 asks
        # tracing to confirm or refute.
        win0_ns = time.monotonic_ns()
        idle_ns = 0
        while True:
            # Telemetry beat at the loop's natural tick: samples (and
            # the stall dual that catches a loop that STOPPED beating)
            # key on it. One load + compare when disarmed.
            _telemetry.beat("serving")
            with self._queue_cond:
                while not self._cut_ready_locked():
                    if self._closing and not self._queue:
                        return
                    wait = None
                    if self._queue:
                        wait = max(0.001, self._budget_s - (
                            time.monotonic()
                            - self._queue[0].t_submit))
                    t0_ns = time.monotonic_ns()
                    self._queue_cond.wait(wait)
                    idle_ns += time.monotonic_ns() - t0_ns
                batch = self._admit_locked()
                self._dispatch_cond.notify_all()
            now_ns = time.monotonic_ns()
            if now_ns > win0_ns:
                _m_loop_occupancy.set(
                    max(0.0, 1.0 - idle_ns / (now_ns - win0_ns)))
            win0_ns, idle_ns = now_ns, 0
            if self._trace:
                _tracing.record("serving_cut", batch.id,
                                seq=batch.attempts,
                                arg=float(len(batch.requests)))
            _journal.record(
                "batch_admitted", batch=batch.id,
                size=len(batch.requests), bucket=batch.bucket_b,
                bucket_len=batch.bucket_len or None,
                queue_depth=len(self._ready),
                wait_ms=round(1e3 * (time.monotonic()
                                     - batch.requests[0].t_submit), 3))

    def _admit_locked(self) -> _Batch:
        take = min(len(self._queue), self._max_batch)
        reqs = [self._queue.popleft() for _ in range(take)]
        bucket_b = self.ladder.batch_bucket(take)
        bucket_len = 0
        if self.ladder.len_buckets:
            bucket_len = max(self.ladder.len_bucket(r.payload.shape[0])
                             for r in reqs)
        self._batch_seq += 1
        batch = _Batch(f"b{self._batch_seq}", reqs, bucket_b,
                       bucket_len)
        self._batches[batch.id] = batch
        self._ready.append(batch)
        self.admitted += 1
        _m_batches.labels(bucket=str(bucket_b)).inc()
        _m_batch_size.observe(float(take))
        _m_padding.inc(float(bucket_b - take))
        _m_queue.set(self._pending_locked())
        return batch

    # -- dispatch / completion ----------------------------------------------

    def _next_batch(self, wid: str,
                    timeout: float) -> Optional[_Batch]:
        deadline = time.monotonic() + timeout
        with self._lock:
            if (self._remote and wid not in self._workers
                    and not self._closing):
                self._workers[wid] = _RemoteMember(wid)
                _m_workers.set(len(self._workers))
        with self._dispatch_cond:
            while not self._ready:
                remaining = deadline - time.monotonic()
                if remaining <= 0 or self._closing:
                    return None
                self._dispatch_cond.wait(remaining)
            batch = self._ready.popleft()
            self._inflight[batch.id] = (
                batch, wid,
                time.monotonic() + self._worker_timeout)
            if self._trace:
                batch.hops.append(_Hop(wid, batch.attempts))
                _tracing.record("serving_claim", batch.id,
                                seq=batch.attempts)
            _m_queue.set(self._pending_locked())
            return batch

    def _hop_for(self, batch: _Batch,
                 wid: str) -> Optional[_Hop]:
        """The newest dispatch attempt `wid` owns (a revenant worker
        matches its own old hop, never the current owner's)."""
        for hop in reversed(batch.hops):
            if hop.worker == wid:
                return hop
        return None

    def _pad(self, batch: _Batch) -> np.ndarray:
        if batch.bucket_len:
            out = np.zeros((batch.bucket_b, batch.bucket_len)
                           + self._feature_shape, dtype=self._dtype)
            for i, r in enumerate(batch.requests):
                out[i, :r.payload.shape[0]] = r.payload
        else:
            out = np.zeros((batch.bucket_b,) + self._feature_shape,
                           dtype=self._dtype)
            for i, r in enumerate(batch.requests):
                out[i] = r.payload
        return out

    def _unpad(self, batch: _Batch, y: np.ndarray) -> List[np.ndarray]:
        rows = []
        for i, r in enumerate(batch.requests):
            row = y[i]
            if (batch.bucket_len and row.ndim >= 1
                    and row.shape[0] == batch.bucket_len):
                # The forward kept the padded length axis: return only
                # the request's true length.
                row = row[:r.payload.shape[0]]
            rows.append(np.asarray(row))
        return rows

    def _complete_batch(self, batch: _Batch,
                        rows: Sequence[np.ndarray],
                        wid: str, weights: str = "") -> int:
        t0_ns = time.monotonic_ns()
        now = time.monotonic()
        won = 0
        dup = 0
        winners: List[ServingFuture] = []
        for req, row in zip(batch.requests, rows):
            if req._finish(value=row):
                won += 1
                winners.append(req)
                _m_requests.labels(outcome="ok").inc()
                _m_latency.observe(now - req.t_submit)
                if req.t_done is not None \
                        and req.t_done <= req.deadline:
                    _m_goodput.labels(slo=req.slo_class).inc()
                else:
                    _m_slo_miss.labels(slo=req.slo_class,
                                       reason="late").inc()
            else:
                dup += 1
                _m_dupes.inc()
        with self._lock:
            self.completed += won
            self.dupes += dup
            ent = self._inflight.get(batch.id)
            if ent is not None and (ent[1] == wid or batch.done):
                self._inflight.pop(batch.id, None)
            if batch.done:
                self._batches.pop(batch.id, None)
                try:
                    self._ready.remove(batch)
                except ValueError:
                    pass
            _m_queue.set(self._pending_locked())
            if not self._queue and not self._ready:
                self._last_nonempty = now
        if self._trace and won:
            self._finalize_traces(batch, winners, wid, weights)
            _tracing.record("serving_done", batch.id,
                            seq=batch.attempts, arg=float(won))
        _m_latch_wait.set((time.monotonic_ns() - t0_ns) / 1e9)
        return won

    def _finalize_traces(self, batch: _Batch,
                         winners: Sequence[ServingFuture],
                         wid: str, weights: str = "") -> None:
        """Fold the winning hop's stamps into per-request trace
        records (ring buffer + phase histograms) and one `batch_trace`
        journal event `doctor serve` aggregates offline."""
        hop = self._hop_for(batch, wid)
        if hop is None:
            return
        hop.outcome = "ok"
        hops = [h.summary() for h in batch.hops]
        recs = []
        for req in winners:
            phases = {
                "batch_cut": batch.t_admit_ns - req.t_submit_ns,
                "queue_wait": hop.t_claim_ns - batch.t_admit_ns,
                "pad": hop.t_exec0_ns - hop.t_claim_ns,
                "compute": hop.t_exec1_ns - hop.t_exec0_ns,
                "unpad": hop.t_unpad1_ns - hop.t_exec1_ns,
                "complete": req.t_done_ns - hop.t_unpad1_ns,
            }
            phases = {p: max(0, int(d)) for p, d in phases.items()}
            rec = {
                "id": req.id, "batch": batch.id, "worker": wid,
                "attempt": batch.attempts,
                "slo": req.slo_class,
                "slo_ms": round(req.slo_ms, 3),
                "outcome": ("ok" if req.t_done is not None
                            and req.t_done <= req.deadline
                            else "late"),
                "t_submit_ns": req.t_submit_ns,
                "t_done_ns": req.t_done_ns,
                "phases_ns": phases,
                "hops": hops,
                # Epoch-fence witness: the single weight-version
                # digest this request's winning batch executed on.
                "weights": weights,
            }
            recs.append(rec)
            for phase, dns in phases.items():
                _m_phase.labels(phase=phase).observe(dns / 1e9)
        with self._lock:
            self._trace_log.extend(recs)
        _journal.record(
            "batch_trace", batch=batch.id, worker=wid,
            attempt=batch.attempts, bucket=batch.bucket_b,
            size=len(winners),
            requests=[r["id"] for r in recs],
            slo=[r["slo"] for r in recs],
            deadline_hit=[r["outcome"] == "ok" for r in recs],
            submit_ns=[r["t_submit_ns"] for r in recs],
            done_ns=[r["t_done_ns"] for r in recs],
            admit_ns=batch.t_admit_ns, claim_ns=hop.t_claim_ns,
            exec0_ns=hop.t_exec0_ns, exec1_ns=hop.t_exec1_ns,
            unpad_ns=hop.t_unpad1_ns, hops=hops, weights=weights)

    def _retry(self, batch: _Batch, cause: str, wid: str) -> None:
        if batch.done:
            return
        if self._trace:
            hop = self._hop_for(batch, wid)
            if hop is not None and hop.outcome == "pending":
                hop.outcome = f"retried:{cause}"
            _tracing.record("serving_retry", batch.id,
                            seq=batch.attempts + 1)
        batch.attempts += 1
        if batch.attempts > self._retry_limit:
            lost = 0
            lost_slo = []
            for req in batch.requests:
                if req._finish(error=ServingError(
                        f"request {req.id} failed after "
                        f"{batch.attempts} dispatch attempts "
                        f"(last cause: {cause})")):
                    lost += 1
                    lost_slo.append(req.slo_class)
                    _m_requests.labels(outcome="failed").inc()
                    _m_slo_miss.labels(slo=req.slo_class,
                                       reason="failed").inc()
            with self._lock:
                self.failed += lost
                self._batches.pop(batch.id, None)
            _journal.record(
                "batch_failed", batch=batch.id,
                attempts=batch.attempts, cause=cause, worker=wid,
                lost=lost, slo=lost_slo,
                hops=[h.summary() for h in batch.hops])
            return
        with self._lock:
            self.retries += 1
        _m_retries.labels(cause=cause).inc()
        _journal.record("batch_retried", batch=batch.id,
                        attempt=batch.attempts, cause=cause,
                        worker=wid,
                        pending=sum(1 for r in batch.requests
                                    if not r.done))
        with self._lock:
            self._ready.appendleft(batch)
            _m_queue.set(self._pending_locked())
            self._dispatch_cond.notify_all()

    def _watchdog_loop(self) -> None:
        while not self._closing:
            time.sleep(min(0.05, self._worker_timeout / 4))
            now = time.monotonic()
            with self._lock:
                expired = sorted({wid for _, (b, wid, dl)
                                  in self._inflight.items()
                                  if dl < now})
            for wid in expired:
                hlog.warning("serving: worker %s missed the batch "
                             "deadline; requeueing its work", wid)
                self._worker_failed(wid, "timeout")

    def _autoscale_loop(self) -> None:
        while not self._closing:
            time.sleep(self._scale_interval)
            if self._remote or self._closing or self._draining:
                continue
            with self._lock:
                qdepth = len(self._ready)
                n = len(self._workers)
                busy = bool(self._inflight or self._queue
                            or self._ready)
                idle_for = time.monotonic() - self._last_nonempty
            if n < self._min_workers:
                # A death took the pool below the floor; restore it.
                self._resize(self._min_workers, "floor")
            elif qdepth > self._scale_up_queue * max(1, n) \
                    and n < self._max_workers:
                self._resize(n + 1, "queue_depth")
            elif (not busy and n > self._min_workers
                    and idle_for > self._scale_down_idle):
                self._resize(n - 1, "idle")

    # -- live weight pipeline -----------------------------------------------

    def _weights_loop(self) -> None:
        """Poll the publisher's CURRENT pointer and expose the
        newest version as the pool's adoption target; workers swap
        at their own between-batches fence. File IO stays outside
        the frontend lock — only the target pointer flips under it."""
        while not self._closing:
            try:
                tgt = self._weights_sub.poll()
            except Exception as e:  # noqa: BLE001 — keep watching
                hlog.warning("serving: weights poll failed: %s", e)
                tgt = None
            if tgt is not None:
                with self._lock:
                    self._weights_target = tgt
                    workers = list(self._workers.values())
                for w in workers:
                    v = getattr(w, "_w_version", None)
                    _weights_mod.set_staleness(
                        w.wid, (tgt.step - v.step) if v is not None
                        else 0)
            t_end = time.monotonic() + self._weights_poll_s
            while time.monotonic() < t_end and not self._closing:
                time.sleep(min(0.02, self._weights_poll_s))

    def _load_weights(self, version) -> Any:
        """Read + verify ``version`` (every shard digested) and
        rebuild it against this frontend's bootstrap tree spec; any
        WeightError here means the caller keeps its old params."""
        named = self._weights_sub.load_named(version)
        return _weights_mod.rebuild(named, self._weights_names,
                                    self._weights_treedef,
                                    self._weights_leaf_spec)

    # -- remote transport ---------------------------------------------------

    def serve_endpoint(self, port: int = 0,
                       secret: Optional[str] = None
                       ) -> Tuple[int, str]:
        """Expose the dispatch queue to remote pool members over the
        HMAC-signed control-plane wire; returns (port, secret) for
        `remote_worker_loop` peers. Pool membership then comes from
        pulls (and `on_membership`), and local autoscaling is off."""
        from .runner import secret as _secret_mod
        from .runner.service import BasicService
        self._secret = (secret if secret is not None
                        else (_secret_mod.from_env()
                              or _secret_mod.make_secret()))
        svc = BasicService("serving", self._secret)
        svc.handle("pull", self._h_pull)
        svc.handle("push", self._h_push)
        with self._lock:
            self._service = svc
            self._remote = True
        return svc.port, self._secret

    def _h_pull(self, req: dict, peer) -> dict:
        wid = str(req.get("worker") or f"{peer[0]}:{peer[1]}")
        if self._closing:
            return {"stop": True}
        batch = self._next_batch(wid, timeout=float(
            req.get("wait", 0.2)))
        if batch is None:
            return {"batch": None, "stop": self._closing}
        arr = self._pad(batch)
        if self._trace:
            # Remote compute is the pull→push round trip, wire
            # included: pad ends (and compute begins) when the padded
            # payload leaves this handler.
            hop = self._hop_for(batch, wid)
            if hop is not None:
                hop.t_exec0_ns = time.monotonic_ns()
        return {"batch": {
            "id": batch.id,
            "shape": list(arr.shape),
            "dtype": self._dtype.name,
            "lens": [int(r.payload.shape[0]) if batch.bucket_len
                     else -1 for r in batch.requests],
            "payload": arr.tolist(),
        }}

    def _h_push(self, req: dict, peer) -> dict:
        wid = str(req.get("worker") or f"{peer[0]}:{peer[1]}")
        bid = str(req.get("batch"))
        batch = self._batches.get(bid)
        if batch is None:
            # Completed and pruned — a revenant's late push.
            with self._lock:
                self.dupes += 1
            _m_dupes.inc()
            return {"ok": 0}
        hop = self._hop_for(batch, wid) if self._trace else None
        if hop is not None and not hop.t_exec1_ns:
            hop.t_exec1_ns = time.monotonic_ns()
        y = np.asarray(req.get("outputs"), dtype=self._dtype)
        rows = self._unpad(batch, y)
        if hop is not None and not hop.t_unpad1_ns:
            hop.t_unpad1_ns = time.monotonic_ns()
        return {"ok": self._complete_batch(
            batch, rows, wid,
            weights=str(req.get("weights") or ""))}

    # -- lifecycle ----------------------------------------------------------

    def drain(self, timeout: float = 30.0) -> bool:
        """Stop admission, flush every queued request through the
        pool; True when nothing is left pending."""
        with self._lock:
            self._draining = True
            self._queue_cond.notify_all()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                if not (self._queue or self._ready or self._inflight
                        or self._batches):
                    return True
            time.sleep(0.01)
        return False

    def close(self, timeout: float = 30.0) -> None:
        drained = self.drain(timeout)
        if not drained:
            hlog.warning("serving: close() draining timed out; "
                         "failing the stragglers")
            with self._lock:
                stuck = list(self._batches.values())
            lost = 0
            for batch in stuck:
                for req in batch.requests:
                    if req._finish(error=ServingError(
                            "frontend closed before completion")):
                        lost += 1
                        _m_requests.labels(outcome="failed").inc()
                        _m_slo_miss.labels(slo=req.slo_class,
                                           reason="failed").inc()
            with self._lock:
                self.failed += lost
        with self._lock:
            self._closing = True
            self._queue_cond.notify_all()
            self._dispatch_cond.notify_all()
            self._workers.clear()
            _m_workers.set(0)
        if self._service is not None:
            # Leave the endpoint answering {"stop": True} briefly so
            # remote members exit cleanly, then close it.
            time.sleep(0.2)
            self._service.close()
        self._batcher.join(timeout=2)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            compiles = sum(getattr(w, "compiles", 0)
                           for w in self._workers.values())
            workers = len(self._workers)
        out = {
            "submitted": self.submitted,
            "completed": self.completed,
            "failed": self.failed,
            "dropped": self.submitted - self.completed - self.failed,
            "batches": self.admitted,
            "retries": self.retries,
            "duplicates_suppressed": self.dupes,
            "scale_events": self.scale_events,
            "workers": workers,
            "compiles": compiles,
            "ladder": {
                "batch_buckets": list(self.ladder.batch_buckets),
                "len_buckets": list(self.ladder.len_buckets),
                "digest": self.ladder.digest,
            },
        }
        if self._weights_sub is not None:
            with self._lock:
                tgt = self._weights_target
                wstates = {
                    wid: getattr(w, "_w_version", None)
                    for wid, w in self._workers.items()}
            out["weights"] = {
                "target_seq": tgt.seq if tgt is not None else 0,
                "target_digest": (tgt.digest if tgt is not None
                                  else ""),
                "target_step": (tgt.step if tgt is not None
                                else -1),
                "swaps": self.weight_swaps,
                "rejections": self.weight_rejections,
                "workers": {
                    wid: {
                        "digest": (v.digest if v is not None
                                   else self._params0_digest),
                        "seq": v.seq if v is not None else 0,
                        "staleness_steps": (
                            max(0, tgt.step - v.step)
                            if tgt is not None and v is not None
                            else 0),
                    } for wid, v in wstates.items()},
            }
        if self._trace:
            out["trace"] = self.trace_digest()
        return out

    # -- trace queries --------------------------------------------------------

    def traces(self) -> List[Dict[str, Any]]:
        """The retained per-request trace records (newest last,
        bounded by HOROVOD_SERVING_TRACE_BUFFER)."""
        with self._lock:
            return list(self._trace_log)

    def trace_digest(self) -> Dict[str, Any]:
        """Per-phase p50/p99/mean decomposition over the retained
        traces, plus goodput-vs-SLO tallies — the live (in-memory)
        view of what `doctor serve` computes offline from journals."""
        recs = self.traces()
        by_phase: Dict[str, List[int]] = {p: [] for p in PHASES}
        goodput: Dict[str, Dict[str, int]] = {}
        for rec in recs:
            cls = goodput.setdefault(
                rec["slo"], {"hit": 0, "late": 0, "failed": 0})
            cls[rec["outcome"] if rec["outcome"] != "ok"
                else "hit"] += 1
            for p, dns in rec["phases_ns"].items():
                if p in by_phase:
                    by_phase[p].append(dns)
        phases = {}
        for p in PHASES:
            vals = sorted(by_phase[p])
            if not vals:
                phases[p] = {"n": 0}
                continue
            phases[p] = {
                "n": len(vals),
                "p50_ms": round(_pct(vals, 0.50) / 1e6, 4),
                "p99_ms": round(_pct(vals, 0.99) / 1e6, 4),
                "mean_ms": round(sum(vals) / len(vals) / 1e6, 4),
            }
        return {"requests": len(recs), "phases": phases,
                "goodput": goodput}

    def write_timeline(self, path: str, rank: int = 0) -> str:
        """Write the retained traces as Chrome-trace lanes
        (timeline.py): one `req/<id>` lane per request with its
        phase spans (retry hops as linked RETRY child spans carrying
        the hop's worker/attempt/outcome args), plus one
        `worker/<wid>` lane of EXEC spans. Returns the file written
        (`Timeline.rank_path(path, rank)`)."""
        from .timeline import Timeline
        recs = self.traces()
        dst = Timeline.rank_path(path, rank)
        tl = Timeline(dst, rank=rank)
        try:
            seen_exec = set()
            for rec in recs:
                lane = f"req/{rec['id']}"
                edge = rec["t_submit_ns"]
                for p in PHASES:
                    dns = rec["phases_ns"].get(p, 0)
                    args = None
                    if p == "batch_cut":
                        args = {"batch": rec["batch"],
                                "worker": rec["worker"],
                                "slo": rec["slo"],
                                "outcome": rec["outcome"]}
                    tl.span(lane, p.upper(), edge, edge + dns,
                            args=args)
                    edge += dns
                hops = rec.get("hops", [])
                for i, (hwid, att, outcome, claim_ns) in \
                        enumerate(hops[:-1]):
                    nxt = hops[i + 1][3]
                    tl.span(lane, "RETRY", claim_ns, nxt,
                            args={"worker": hwid, "attempt": att,
                                  "outcome": outcome,
                                  "batch": rec["batch"]})
                key = (rec["batch"], rec["attempt"])
                if key not in seen_exec:
                    seen_exec.add(key)
                    exec0 = (rec["t_submit_ns"]
                             + rec["phases_ns"].get("batch_cut", 0)
                             + rec["phases_ns"].get("queue_wait", 0)
                             + rec["phases_ns"].get("pad", 0))
                    tl.span(f"worker/{rec['worker']}", "EXEC",
                            exec0,
                            exec0 + rec["phases_ns"].get(
                                "compute", 0),
                            args={"batch": rec["batch"],
                                  "attempt": rec["attempt"]})
        finally:
            tl.close()
        return dst

    def _inflight_table(self) -> Dict[str, Any]:
        # Postmortem provider path: deliberately lock-free (the dump
        # may fire with self._lock held by a dying thread); dict/deque
        # snapshots are GIL-atomic enough for a best-effort table.
        batches = []
        for batch in list(self._batches.values()):
            hops = list(batch.hops)
            last = hops[-1] if hops else None
            if last is None:
                phase = "queued"
            elif last.t_unpad1_ns:
                phase = "complete"
            elif last.t_exec1_ns:
                phase = "unpad"
            elif last.t_exec0_ns:
                phase = "compute"
            else:
                phase = "pad"
            batches.append({
                "batch": batch.id,
                "attempts": batch.attempts,
                "worker": last.worker if last else None,
                "last_phase": phase,
                "requests": [r.id for r in batch.requests],
                "pending": sum(1 for r in batch.requests
                               if not r.done),
            })
        return {
            "tag": self.trace_tag or "",
            "queued": [r.id for r in list(self._queue)],
            "batches": sorted(batches, key=lambda b: b["batch"]),
        }


# ---------------------------------------------------------------------------
# Postmortem provider

# Rides tracing.write_postmortem's provider hook: every postmortem
# dump (watchdog stall, fatal signal) gets a "serving" section with
# each live frontend's queued request ids and in-flight batches with
# their last completed phase — the SIGKILL story the in-memory trace
# log alone cannot tell, because it dies with the process while the
# postmortem file survives it.


def _postmortem_inflight() -> List[Dict[str, Any]]:
    return [fe._inflight_table() for fe in list(_live_frontends)]


_tracing.register_postmortem_provider("serving", _postmortem_inflight)


# ---------------------------------------------------------------------------
# Remote worker loop


# How long a remote pool member keeps pulling from a frontend that
# does not answer before it concludes the frontend is gone.
_FRONTEND_GONE_S = 10.0


def remote_worker_loop(addr: str, port: int,
                       forward_fn: Callable,
                       feature_shape: Sequence[int],
                       dtype: str = "float32",
                       wid: Optional[str] = None,
                       secret: Optional[str] = None,
                       env: Optional[Dict[str, str]] = None,
                       max_batches: int = 0,
                       params: Optional[Any] = None,
                       weights_dir: Optional[str] = None) -> int:
    """Pool-member loop for a separate process: pull padded batches
    from a `ServingFrontend.serve_endpoint()`, execute the
    AOT-compiled forward, push results. Returns the number of batches
    executed; exits when the frontend says stop (or after
    ``max_batches`` > 0, for tests). The `serving.batch` seam fires
    once per pulled batch — `crash` here is a real mid-batch process
    death.

    With ``params`` the forward is two-arg (params, x); with
    ``weights_dir`` this member runs its own `WeightSubscriber` and
    hot-swaps between pulls (the remote epoch fence), stamping every
    push with the digest it executed on. The `weights.adopt` seam
    fires once per adoption attempt — `crash` here is a real process
    death mid-swap."""
    import os

    import jax
    import jax.numpy as jnp

    from .runner import secret as _secret_mod
    from .runner.service import BasicClient

    if wid is None:
        wid = f"pid{os.getpid()}"
    if secret is None:
        secret = _secret_mod.from_env()
    if _journal._journal is None:
        # Don't steal an already-armed journal: under the elastic
        # runner this process journals as its rank, and fault_fired /
        # batch records must stay attributable to that rank.
        _journal.configure(f"serving-{wid}", env=env)
    if _telemetry._recorder is None:
        # Same don't-steal rule: an elastic-rank recorder keeps its
        # shard; a standalone serving worker gets its own.
        _telemetry.configure(f"serving-{wid}", env=env)
    cli = BasicClient(addr, port, secret, timeout=10.0)
    ladder = build_ladder(env=env)
    jitted = jax.jit(forward_fn)
    w_names = w_treedef = None
    w_digest = ""
    w_sub = None
    w_rejected_seq = -1
    if params is not None:
        w_names, w_treedef = _weights_mod.tree_spec(params)
        w_spec = _weights_mod.leaf_spec(params)
        w_digest = _weights_mod.content_digest(
            _weights_mod.named_leaves(params))
        params = jax.device_put(params)
    if weights_dir:
        if params is None:
            raise ValueError("remote_worker_loop(weights_dir=...) "
                             "needs params= (the bootstrap tree)")
        w_sub = _weights_mod.WeightSubscriber(weights_dir, env=env)
    compiled: Dict[Tuple[int, ...], Callable] = {}
    for shape in ladder.shapes(feature_shape):
        if params is not None:
            fn, _ = aot_compile(jitted, params,
                                jnp.zeros(shape, dtype))
        else:
            fn, _ = aot_compile(jitted, jnp.zeros(shape, dtype))
        compiled[shape] = fn
        _m_compiles.inc()
    done = 0
    last_reply = time.monotonic()
    while True:
        if w_sub is not None:
            # Adopt between pulls — the remote member's epoch fence.
            cur = w_sub.poll()
            if cur is not None and cur.seq != w_rejected_seq:
                # Uncaught `error` (and real `crash`) here is a
                # worker death mid-swap; the frontend requeues this
                # member's inflight work on survivors.
                _faults.fire("weights.adopt", exc=_WorkerDied,
                             tag=wid)
                t0 = time.monotonic()
                try:
                    tree = _weights_mod.rebuild(
                        w_sub.load_named(cur), w_names, w_treedef,
                        w_spec)
                    params = jax.device_put(tree)
                    jax.block_until_ready(params)
                except Exception as e:  # noqa: BLE001 — keep serving
                    w_rejected_seq = cur.seq
                    reason = _weights_mod.rejection_reason(e)
                    hlog.warning("serving: remote %s rejected "
                                 "weights seq=%d (%s): %s", wid,
                                 cur.seq, reason, e)
                    _weights_mod.note_rejected(wid, cur, reason,
                                               str(e), w_digest)
                else:
                    w_digest = cur.digest
                    _weights_mod.note_adopted(
                        wid, cur, time.monotonic() - t0, 0)
        reply = cli.try_request({"type": "pull", "worker": wid,
                                 "wait": 0.2}, retries=2)
        if reply is None:
            # A frontend that stays unreachable is gone (closed, or
            # died without saying stop): leave, don't spin forever.
            if time.monotonic() - last_reply > _FRONTEND_GONE_S:
                hlog.warning("serving: remote %s: frontend %s:%d "
                             "unreachable for %.0fs; leaving the pool",
                             wid, addr, port, _FRONTEND_GONE_S)
                return done
            time.sleep(0.05)
            continue
        last_reply = time.monotonic()
        if reply.get("stop"):
            return done
        b = reply.get("batch")
        if not b:
            continue
        _faults.fire("serving.batch", exc=_WorkerDied, tag=wid)
        shape = tuple(b["shape"])
        x = np.asarray(b["payload"], dtype=b["dtype"]).reshape(shape)
        fn = compiled.get(shape)
        if params is not None:
            y = np.asarray(fn(params, jnp.asarray(x))
                           if fn is not None
                           else jitted(params, jnp.asarray(x)))
        else:
            y = np.asarray(fn(jnp.asarray(x)) if fn is not None
                           else jitted(jnp.asarray(x)))
        cli.try_request({"type": "push", "worker": wid,
                         "batch": b["id"], "outputs": y.tolist(),
                         "weights": w_digest},
                        retries=2)
        done += 1
        if max_batches and done >= max_batches:
            return done
