"""Distributed tracing: cross-rank correlated spans, calibrated clock
merge, flight recorder, and straggler attribution.

The reference's flagship debugging tool is the per-rank Timeline
(reference: horovod/common/timeline.cc); this module is the layer that
makes N ranks' timelines ONE artifact and answers the question the
per-rank view cannot: *which rank made everyone wait, and what was it
doing?* Four pieces:

* **Trace context** — every negotiated collective carries a step id
  and a collective sequence id assigned in the controller's agreed
  batch order. The agreed order is identical on every rank by
  construction (that is the controller's core guarantee), so the same
  collective gets the same seq everywhere with zero extra wire bytes.

* **Clock calibration** — per-rank timelines run on
  ``time.monotonic_ns()`` anchored at construction. Rank 0 serves a
  tiny authenticated ``time`` verb (runner/service.py BasicService —
  the existing control-plane wire format); every other rank estimates
  its monotonic offset to rank 0 with NTP-style midpoint sampling
  (min-RTT sample of K probes wins; error is bounded by that RTT) and
  re-estimates periodically. The offsets ride the trace files as
  CLOCK_SYNC records, which is what lets the merge align N files
  recorded on N different clocks.

* **Merge + attribution** — ``hvdrun --timeline-merge`` /
  ``python -m horovod_tpu.runner.doctor trace <dir>`` fuses the
  per-rank files into one Chrome/Perfetto trace (one process track
  per rank) and emits a straggler report: per-collective per-rank
  arrival deltas (negotiate-submit skew on the calibrated clock),
  p50/p99 skew per tensor name, top-K offender ranks. The same
  quantity feeds the runtime ``hvd_collective_skew_seconds``
  histogram, so chronic stragglers are alertable without a trace.

* **Flight recorder** — an always-on bounded ring of the last N span
  events per rank (a tuple append; no file IO when HOROVOD_TIMELINE
  is unset — overhead-guarded like faults.py's disarmed path). Dumped
  on demand (SIGUSR2, the elastic control plane's ``dump`` verb) and
  automatically on HorovodInternalError: thread stacks, the in-flight
  tensor table, controller queue depth, a metrics snapshot and the
  ring tail land in ``postmortem-rank{r}.json`` for the elastic
  driver to collect before it blacklists the host.
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import re
import signal
import sys
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Tuple

from .common import config as _config
from .common import logging as hlog
from .metrics import LATENCY_BUCKETS, REGISTRY as _METRICS

_m_skew = _METRICS.histogram(
    "hvd_collective_skew_seconds",
    "Per-collective arrival lateness of THIS rank vs the earliest "
    "submitting rank (coordinator-measured negotiation span minus "
    "this rank's local wait) — the runtime form of the merged "
    "straggler report.", buckets=LATENCY_BUCKETS)
_m_postmortems = _METRICS.counter(
    "hvd_postmortems_written_total",
    "Flight-recorder postmortem dumps written, by trigger.",
    ("trigger",))


# ---------------------------------------------------------------------------
# flight recorder (always-on ring buffer)
# ---------------------------------------------------------------------------

# One tuple per span event: (mono_ns, kind, name, seq, arg). The deque
# append is the entire enabled hot path — GIL-atomic, no lock, no IO.
# What seq and arg hold is the kind's own (host_span below: ids and
# seconds).
_ring: Optional[collections.deque] = None
_ring_size = 0


def configure_ring(size: int) -> None:
    """(Re)build the flight-recorder ring; size 0 disables it."""
    global _ring, _ring_size
    _ring_size = int(size)
    _ring = (collections.deque(maxlen=_ring_size)
             if _ring_size > 0 else None)


def record(kind: str, name: str, seq: int = -1,
           arg: float = 0.0) -> None:
    """Span-event append on the collective hot path. Ring disabled:
    one module-attribute load + compare (test_tracing.py's overhead
    guard, same contract as faults.fire's disarmed path)."""
    ring = _ring
    if ring is None:
        return
    ring.append((time.monotonic_ns(), kind, name, seq, arg))


def _snapshot_deque(dq) -> list:
    """Copy a deque other threads may be appending to: iteration
    raises RuntimeError on concurrent mutation, so retry a few times
    (appends are rare relative to a copy) and degrade to empty rather
    than ever failing a dump path."""
    for _ in range(8):
        try:
            return list(dq)
        except RuntimeError:
            continue
    return []


def ring_events(limit: Optional[int] = None) -> List[Tuple]:
    """Snapshot of the ring tail, oldest first."""
    ring = _ring
    if ring is None:
        return []
    evs = _snapshot_deque(ring)
    return evs[-limit:] if limit else evs


# ---------------------------------------------------------------------------
# device-trace scopes (names on the jit plane's instructions)
# ---------------------------------------------------------------------------
# The jit plane runs no host code in a step, so what it can give a
# profiler trace is names: a `jax.named_scope` becomes part of every
# instruction's `op_name`, and the device's own timeline then says
# which layer an instruction belongs to, whether it is the backward
# pass (`transpose(` in the name) or a recompute under
# `jax.checkpoint` (`rematted_computation`). Every scope of the program
# starts with `hvd.` and is listed here; nothing else may.

DEVICE_SCOPES: Dict[str, str] = {
    "hvd.embed": "token embedding gather, and its scatter-add in backward",
    "hvd.attn.proj": "attention norm, q/k/v projections, rope (plain "
                     "or YaRN), output projection and its tensor psum; "
                     "where a model has them, the per-head q / k norms, "
                     "the output gate and the sub-layer's post-norm",
    "hvd.attn.core": "attention scores, mask, softmax and PV: the "
                     "fused kernels (forward, dQ, dK/dV), the dense "
                     "path or the ring, with the GQA repeat of K / V "
                     "where the path needs one; in a model that mixes "
                     "layer kinds, the full layers' core",
    "hvd.attn.window": "the same core in a sliding-window layer "
                       "(models/window_moe.py: Trinity's 4,096 keys, "
                       "Mellum 2's 1,024): key blocks older than the "
                       "window are skipped, not masked",
    "hvd.attn.linear": "linear attention with a decay a head "
                       "(parallel/linear_attention.py): the products "
                       "inside a chunk, the state carried from chunk to "
                       "chunk and its cotangent carried back, all passes",
    "hvd.attn.select": "block-sparse attention's selection "
                       "(parallel/sparse_attention.py select_blocks): "
                       "pooled keys, their softmax, the sum over a "
                       "group's heads, the max over a block, top-k; on "
                       "the TPU also the words and walk tables the "
                       "kernels read (fused with it or beside it)",
    "hvd.attn.sparse": "attention over the key blocks each query "
                       "selected: the kernels that walk the table of "
                       "visited blocks (forward, one backward) with "
                       "their token-level mask, or one masked softmax; the "
                       "layer at or under its dense length runs "
                       "hvd.attn.core",
    "hvd.ssm.proj": "a Mamba mixer around its scan (models/jamba.py): "
                    "the norm, in_proj, the causal conv, x_proj, the "
                    "dt / B / C norms, dt_proj and softplus, A, "
                    "out_proj and the residual add",
    "hvd.ssm.scan": "the selective scan (parallel/selective_scan.py): "
                    "the recurrence over chunks with the state in VMEM "
                    "(or jax.numpy chunks), the D skip and the silu(z) "
                    "gate, all passes",
    "hvd.ffn": "dense FFN: norm and SwiGLU, and the sub-layer's "
               "post-norm where a model has one",
    "hvd.moe": "MoE FFN: router, dispatch, experts, combine",
    "hvd.moe.route": "dropless expert layer: norm, router scores "
                     "(sigmoid + bias, or a softmax renormalised), "
                     "top-k, the sort of the (token, expert) pairs held "
                     "here, their rows moved into the dispatch buffer "
                     "and the gated sum back (on the TPU the row "
                     "movers' kernels over the live tiles and landed "
                     "pairs, else gathers over the whole buffer); the "
                     "sub-layer's post-norm where a model has one",
    "hvd.moe.experts": "dropless expert layer: the grouped matmuls "
                       "over the experts held and their SwiGLU (on "
                       "the TPU inside the gate / up kernel pair, "
                       "over the live tiles alone)",
    "hvd.moe.shared": "the shared expert's SwiGLU",
    "hvd.hc": "residual streams (hyper-connections): coefficients, "
              "Sinkhorn, the mix into a sub-layer's input, the "
              "write-back, and the streams' sum at the exit",
    "hvd.mtp": "multi-token module: the two norms, the embedding of "
               "the next token and the 2D x D projection (its block "
               "keeps its own scopes)",
    "hvd.head_loss": "final norm, LM head or classifier, cross-entropy",
    "hvd.conv": "ResNet convolutions",
    "hvd.batchnorm": "BatchNorm statistics and normalisation",
    "hvd.grad_reduce": "the family of the gradient buckets' scopes "
                       "(no instruction carries the bare name): "
                       "hvd.grad_reduce.b<N> is bucket N of "
                       "plan_overlap (pack, cast, all-reduce, unpack)",
    "hvd.optimizer": "optimizer update and its application",
}
# Part of the persistent compile cache's key (common/compile_cache.py):
# JAX's own key leaves names out, so a cache filled before a scope was
# added, renamed or moved hands back executables with the old names.
# Raise it with every such change.
DEVICE_SCOPES_VERSION = 5
_BUCKET_SCOPE = "hvd.grad_reduce.b"
_BUCKET_SCOPE_NAME = re.compile(re.escape(_BUCKET_SCOPE) + "[0-9]+")


def device_scope(name: str):
    """`jax.named_scope(name)` for a name of `DEVICE_SCOPES`, or for a
    bucket's `hvd.grad_reduce.b<N>`. Trace-time only: it adds nothing
    to the lowered program's text and nothing to a step."""
    import jax
    if name not in DEVICE_SCOPES and \
            not _BUCKET_SCOPE_NAME.fullmatch(name):
        raise ValueError(
            f"{name!r} is no registered device scope: add it to "
            "tracing.DEVICE_SCOPES (known: "
            f"{', '.join(DEVICE_SCOPES)}, {_BUCKET_SCOPE}<N>)")
    return jax.named_scope(name)


def bucket_scope(bucket_id: int):
    """The scope of one `plan_overlap` bucket's reduction."""
    return device_scope(f"{_BUCKET_SCOPE}{int(bucket_id)}")


# ---------------------------------------------------------------------------
# host spans (set-up and control code)
# ---------------------------------------------------------------------------
# Set-up is host code the device trace cannot see: `hvd.init()`, the
# lowering and compiling of programs. One `with host_span(name)` is the
# one way to time such a phase: the ring gets its begin and end (a dump
# of a job that hangs in set-up says in which phase), the registry its
# seconds (readable with no capture running), and a live
# profiler capture an `hvd::<name>` annotation on the capture's clock.
# Not for the eager plane's per-collective path: its annotations stay
# gated where they are (ops/engine.py, ops/controller.py).

HOST_SPANS: Dict[str, str] = {
    "init": "the whole of hvd.init(), the phases below and the knob "
            "checks around them",
    "init.distributed": "jax.distributed.initialize() and the exchange "
                        "of process indices (multi-process worlds)",
    "init.topology": "topology.detect: the first look at the devices, "
                     "so the backend's bring-up where nothing touched "
                     "JAX before",
    "init.engine": "process sets, the eager engine, the negotiated "
                   "controller and its native core's load or make, "
                   "the autotuner, the hierarchical factor",
    "init.observability": "timeline, metrics server, tracing.on_init, "
                          "journal, telemetry",
    "aot.lower": "aot_compile: tracing and lowering a step to StableHLO",
    "aot.compile": "aot_compile: the backend compiler, or the load of "
                   "its result from the persistent compile cache",
}
SPAN_BEGIN, SPAN_END = "span_begin", "span_end"

_m_span_seconds = _METRICS.counter(
    "hvd_host_span_seconds_total",
    "Host seconds spent inside tracing.host_span blocks, by span "
    "(a span's own time is this less its children's).", ("span",))

_span_ids = itertools.count()
_open_span = threading.local()   # .id: the thread's innermost open span


class host_span:
    """Time one phase of set-up or control code under a name of
    `HOST_SPANS`. Ring entries: (`SPAN_BEGIN`, name, seq = the span's
    id, arg = the id of the thread's enclosing span or -1) and
    (`SPAN_END`, name, seq = the id, arg = seconds). `seconds` holds
    the duration once the block has ended, raised or not."""

    __slots__ = ("name", "seconds", "_id", "_parent", "_begin_ns",
                 "_annotation")

    def __init__(self, name: str):
        if name not in HOST_SPANS:
            raise ValueError(
                f"{name!r} is no registered host span: add it to "
                f"tracing.HOST_SPANS (known: {', '.join(HOST_SPANS)})")
        self.name = name
        self.seconds = 0.0

    def __enter__(self) -> "host_span":
        self._parent = getattr(_open_span, "id", -1)
        self._id = _open_span.id = next(_span_ids)
        self._annotation = None
        if profiler_active():
            import jax
            self._annotation = jax.profiler.TraceAnnotation(
                "hvd::" + self.name)
            self._annotation.__enter__()
        record(SPAN_BEGIN, self.name, self._id, float(self._parent))
        self._begin_ns = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = (time.monotonic_ns() - self._begin_ns) / 1e9
        record(SPAN_END, self.name, self._id, self.seconds)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        _open_span.id = self._parent
        _m_span_seconds.labels(span=self.name).inc(self.seconds)


# ---------------------------------------------------------------------------
# trace context: step id + agreed collective sequence id
# ---------------------------------------------------------------------------

_ctx_lock = threading.Lock()
_step = 0
_seq = 0


def set_step(step: int) -> None:
    """Pin the training-step id carried on subsequent spans (called
    from the elastic commit boundary; manual loops may call it too)."""
    global _step
    _step = int(step)


def advance_step() -> int:
    global _step
    with _ctx_lock:
        _step += 1
        return _step


def current_step() -> int:
    return _step


def next_seq(n: int = 1) -> int:
    """Reserve `n` consecutive collective sequence ids and return the
    first. The controller calls this once per agreed batch, in batch
    order — the agreed order is identical on every rank, so the ids
    correlate cross-rank with no wire traffic."""
    global _seq
    with _ctx_lock:
        first = _seq
        _seq += n
        return first


def reset_context() -> None:
    """Fresh step/seq numbering (tests)."""
    global _step, _seq
    with _ctx_lock:
        _step = 0
        _seq = 0


def _align_seq_epoch() -> None:
    """Re-base the sequence counter at init so it is identical on
    every rank of the (possibly new) world. Without this, an elastic
    restore breaks the cross-rank invariant: a joiner would start at
    0 while survivors continue from N (and survivors themselves can
    differ by the crashed batch). The elastic epoch — published in
    every rank's rendezvous assignment and refreshed before re-init —
    seeds a fresh non-overlapping id range per world incarnation;
    epoch 0 (non-elastic) keeps plain zero-based ids."""
    global _seq
    epoch = max(_config.env_value("HOROVOD_ELASTIC_EPOCH"), 0)
    with _ctx_lock:
        _seq = epoch << 32


# ---------------------------------------------------------------------------
# runtime skew samples (the straggler report's runtime sibling)
# ---------------------------------------------------------------------------

_skew_samples: collections.deque = collections.deque(maxlen=4096)


def record_skew(seconds: float) -> None:
    _m_skew.observe(seconds)
    _skew_samples.append(float(seconds))


def skew_quantiles() -> Dict[str, float]:
    """Exact p50/p99 over the recent-sample reservoir (bounded)."""
    samples = sorted(_snapshot_deque(_skew_samples))
    if not samples:
        return {"count": 0, "p50_s": 0.0, "p99_s": 0.0, "max_s": 0.0}
    n = len(samples)
    return {"count": n,
            "p50_s": samples[int(0.50 * (n - 1))],
            "p99_s": samples[int(0.99 * (n - 1))],
            "max_s": samples[-1]}


def trace_digest() -> Dict[str, Any]:
    """Compact runtime digest for bench.py's JSON artifact:
    negotiation-skew quantiles + per-phase span totals accumulated
    from the flight-recorder ring."""
    phases: Dict[str, Dict[str, float]] = {}
    for _, kind, _, _, arg in ring_events():
        if kind in (SPAN_BEGIN, SPAN_END):
            # Host spans nest, so one total would count the children
            # twice: hvd_host_span_seconds_total holds them by name.
            continue
        d = phases.setdefault(kind, {"count": 0, "total_s": 0.0})
        d["count"] += 1
        d["total_s"] += float(arg)
    for d in phases.values():
        d["total_s"] = round(d["total_s"], 6)
    return {"negotiation_skew": skew_quantiles(), "spans": phases}


# ---------------------------------------------------------------------------
# clock calibration (NTP-style midpoint against rank 0)
# ---------------------------------------------------------------------------

def estimate_offset(probe: Callable[[], int],
                    probes: int = 8) -> Tuple[int, int]:
    """Estimate the offset mapping the LOCAL monotonic clock onto the
    server's: ``server_mono_ns ~= local_mono_ns + offset_ns``.

    `probe()` returns the server's monotonic_ns. Classic NTP midpoint:
    each round trip yields offset = server - (send + recv)/2, with
    error bounded by half the RTT; the min-RTT sample wins. Returns
    (offset_ns, rtt_ns of the winning sample)."""
    best: Optional[Tuple[int, int]] = None
    for _ in range(max(1, probes)):
        t0 = time.monotonic_ns()
        server = int(probe())
        t1 = time.monotonic_ns()
        rtt = t1 - t0
        off = server - (t0 + t1) // 2
        if best is None or rtt < best[1]:
            best = (off, rtt)
    return best


class TimeService:
    """Rank 0's time oracle: one ``time`` verb on the authenticated
    control-plane wire (runner/service.py), answering with this
    process's monotonic_ns. Handler work is a single clock read, so a
    calibration storm from a large job stays negligible."""

    def __init__(self, secret: str, port: int = 0):
        from .runner.service import BasicService
        self._svc = BasicService("trace-time", secret, port)
        self._svc.handle("time", self._on_time)

    @property
    def port(self) -> int:
        return self._svc.port

    @staticmethod
    def _on_time(req: dict, peer) -> dict:
        return {"mono_ns": time.monotonic_ns()}

    def close(self) -> None:
        self._svc.close()


class ClockCalibrator:
    """Background re-estimation of this rank's offset to rank 0,
    pushed into the timeline as CLOCK_SYNC records (the merge step
    picks the min-RTT record per file)."""

    def __init__(self, host: str, port: int, secret: str, timeline,
                 interval_s: float, probes: int):
        from .runner.service import BasicClient
        self._cli = BasicClient(host, port, secret, timeout=5.0)
        self._timeline = timeline
        self._interval = float(interval_s)
        self._probes = int(probes)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # (offset_ns, rtt_ns) published as ONE tuple: the re-sync
        # thread and the main thread both write, and the pair is only
        # meaningful together (min-RTT pairing) — two separate stores
        # could hand a reader a new offset against a stale rtt.
        self._calibration: Optional[Tuple[int, int]] = None

    @property
    def offset_ns(self) -> Optional[int]:
        cal = self._calibration
        return cal[0] if cal is not None else None

    @property
    def rtt_ns(self) -> Optional[int]:
        cal = self._calibration
        return cal[1] if cal is not None else None

    def _probe(self) -> int:
        reply = self._cli.request({"type": "time"}, retries=2)
        return int(reply["mono_ns"])

    def calibrate_once(self) -> bool:
        try:
            off, rtt = estimate_offset(self._probe, self._probes)
        except Exception as e:  # noqa: BLE001 — observability only
            hlog.debug("tracing: clock calibration failed: %s", e)
            return False
        # hvdlint: disable-next=HVD006 (single GIL-atomic store of an
        # immutable tuple: readers always see a consistent pair)
        self._calibration = (off, rtt)
        tl = self._timeline
        if tl is not None:
            tl.clock_sync(off, rtt)
        return True

    def start(self) -> None:
        self.calibrate_once()
        if self._interval > 0:
            self._thread = threading.Thread(
                target=self._loop, name="hvd-clock-sync", daemon=True)
            self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(self._interval):
            self.calibrate_once()

    def stop(self) -> None:
        self._stop.set()


_time_service: Optional[TimeService] = None
_calibrator: Optional[ClockCalibrator] = None


def current_calibration() -> Optional[Tuple[int, int]]:
    """This rank's live (offset_ns, rtt_ns) estimate against rank 0,
    or None when no calibrator is running (rank 0 itself, single
    process, or no timeline at init). journal.py persists it so the
    incident merge aligns journals across hosts on the same clock the
    trace merge uses."""
    cal = _calibrator
    return cal._calibration if cal is not None else None


def _start_clock_sync(cfg, topo, timeline) -> None:
    """Wire the calibration plane up at init: rank 0 binds the time
    verb, its address rides an object broadcast (the negotiation plane
    is already up), every other rank calibrates now and periodically.
    Best-effort: tracing must never kill training."""
    global _time_service, _calibrator
    from .runner import secret as _secret
    secret = _secret.from_env()
    payload = None
    if topo.rank == 0:
        # Rank-0 setup failures (port exhaustion, bind EACCES) must
        # NOT skip the broadcast below: every other rank enters it
        # unconditionally, so skipping would hang their init. A None
        # payload tells them to run uncalibrated instead.
        try:
            _time_service = TimeService(secret)
            host = (cfg.coordinator_addr.rsplit(":", 1)[0]
                    if cfg.coordinator_addr else "127.0.0.1")
            payload = (host, _time_service.port)
        except Exception as e:  # noqa: BLE001 — observability only
            hlog.warning("tracing: time service unavailable (%s); "
                         "traces will merge uncalibrated", e)
    from .optim.functions import broadcast_object
    addr = broadcast_object(payload, root_rank=0,
                            name="hvd.tracing.time_addr")
    if topo.rank == 0 or addr is None:
        return
    _calibrator = ClockCalibrator(
        addr[0], addr[1], secret, timeline,
        interval_s=cfg.trace_clock_sync_interval,
        probes=cfg.trace_clock_probes)
    _calibrator.start()


# ---------------------------------------------------------------------------
# profiler session detection (the TraceAnnotation gate)
# ---------------------------------------------------------------------------

def _resolve_profiler_probe():
    """Bind the profiler-session probe ONCE: it runs on the
    per-dispatch hot path, so a raised-and-caught exception per
    collective would cost more than the TraceAnnotation the gate
    exists to avoid. The C++-side ``TraceMe.is_enabled`` is the
    source of truth for BOTH programmatic traces and on-demand
    profiler-server captures (a python-side session check misses the
    latter — the standard production capture path). Unknown jax
    layout => always True (keep annotating, the pre-gate
    behavior)."""
    try:
        import jax
        # TraceAnnotation is a TraceMe: the public name outlives the
        # moves of the extension module under it.
        probe = jax.profiler.TraceAnnotation.is_enabled
        probe()  # must be callable without args
        return probe
    except Exception:  # noqa: BLE001 — unknown jax layout
        return lambda: True


_profiler_probe = _resolve_profiler_probe()


def profiler_active() -> bool:
    """True while any profiler capture (programmatic jax.profiler
    trace OR an on-demand profiler-server session) is live — the
    gate for engine-side TraceAnnotation spans, so the disabled path
    pays no per-dispatch context-manager construction."""
    return _profiler_probe()


# ---------------------------------------------------------------------------
# postmortem (flight-recorder dump)
# ---------------------------------------------------------------------------

_dumping = threading.Lock()

# Config snapshot installed by on_init so init(config_overrides=...)
# reaches knobs read at dump time too; env fallback pre-init.
_cfg = None


def _knob(name: str):
    cfg = _cfg
    if cfg is not None:
        try:
            return cfg[name]
        except KeyError:  # pragma: no cover - defensive
            pass
    return _config.env_value(name)


def _my_rank() -> int:
    """The initialized topology rank when available (multi-controller
    pods derive it from jax.process_index(), NOT the launcher env);
    the launcher env only as the pre-init fallback — otherwise every
    rank of a platform-launched pod would label its postmortem
    rank 0 and clobber its peers' dumps in a shared directory."""
    try:
        from .common import basics
        st = basics.state()
        if st.initialized and st.topology is not None:
            return st.topology.rank
    except Exception:  # noqa: BLE001 — dump paths must not raise
        pass
    return max(_config.env_value("HOROVOD_RANK"), 0)


def postmortem_dir() -> str:
    """HOROVOD_TRACE_POSTMORTEM_DIR, else the timeline's directory,
    else cwd — so traces and postmortems land side by side."""
    d = _knob("HOROVOD_TRACE_POSTMORTEM_DIR")
    if d:
        return d
    tl = _knob("HOROVOD_TIMELINE")
    if tl:
        return os.path.dirname(os.path.abspath(tl))
    return os.getcwd()


def _thread_stacks() -> Dict[str, List[str]]:
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    for tid, frame in sys._current_frames().items():
        label = f"{names.get(tid, '?')}-{tid}"
        out[label] = traceback.format_stack(frame)
    return out


def _runtime_tables() -> Dict[str, Any]:
    """In-flight tensor table + controller queue depths, read without
    taking runtime locks (a postmortem may fire while they are
    held)."""
    out: Dict[str, Any] = {}
    try:
        from .common import basics
        st = basics.state()
        eng = st.engine
        if eng is not None:
            out["in_flight_handles"] = [
                {"id": h.id, "name": h.name, "done": h.done()}
                for h in list(eng._handles.values())]
            ctl = eng.controller
            if ctl is not None:
                now = time.monotonic()
                out["controller_pending"] = [
                    {"name": n, "age_s": round(now - p.submitted, 4)}
                    for n, p in list(ctl._pending.items())]
                out["controller_queue_depth"] = \
                    len(out["controller_pending"])
                out["controller_exec_counts"] = dict(ctl.exec_counts)
    except Exception as e:  # noqa: BLE001 — best effort
        out["error"] = str(e)
    return out


# Subsystems with their own in-flight state (the serving frontend's
# request table) register a named section here; every dump calls each
# provider best-effort so a SIGKILLed worker's batch still leaves a
# per-request trace in postmortem-rank{r}.json. Registration is
# idempotent by name (module re-imports replace, never duplicate).
_pm_providers: Dict[str, Callable[[], Any]] = {}


def register_postmortem_provider(name: str,
                                 fn: Callable[[], Any]) -> None:
    """Add a `name` section to every postmortem dump, produced by
    `fn()` at dump time. Providers must not take runtime locks — a
    dump may fire while they are held."""
    _pm_providers[name] = fn


def write_postmortem(reason: str, trigger: str = "manual",
                     path: Optional[str] = None) -> Optional[str]:
    """Dump the flight recorder + runtime introspection to
    ``postmortem-rank{r}.json``. NEVER raises (crash handlers call
    this); returns the path or None."""
    if not _dumping.acquire(blocking=False):
        return None  # a dump is already in flight (signal re-entry)
    try:
        rank = _my_rank()
        if path is None:
            path = os.path.join(postmortem_dir(),
                                f"postmortem-rank{rank}.json")
        doc = {
            "rank": rank,
            "reason": reason,
            "trigger": trigger,
            "unix_time": time.time(),
            "mono_ns": time.monotonic_ns(),
            "step": current_step(),
            "seq": _seq,
            "thread_stacks": _thread_stacks(),
            "runtime": _runtime_tables(),
            "metrics": _metrics_snapshot(),
            "skew": skew_quantiles(),
            "ring": [[ts, kind, name, seq, arg] for
                     (ts, kind, name, seq, arg) in ring_events()],
        }
        for pname, provider in sorted(_pm_providers.items()):
            try:
                doc[pname] = provider()
            except Exception as e:  # noqa: BLE001 — dump never fails
                doc[pname] = {"error": str(e)}
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True, default=str)
        os.replace(tmp, path)
        _m_postmortems.labels(trigger=trigger).inc()
        # Postmortems are first-class journal events: `doctor
        # incident` links each recovery to the dumps its dead workers
        # left behind (basename only — the report must stay
        # byte-deterministic across checkouts).
        from . import journal as _journal
        _journal.record("postmortem_written",
                        file=os.path.basename(path),
                        reason=str(reason)[:200], trigger=trigger,
                        step=current_step())
        hlog.warning("tracing: postmortem written to %s (%s)",
                     path, reason)
        return path
    except Exception as e:  # noqa: BLE001 — must never re-raise
        try:
            hlog.error("tracing: postmortem dump failed: %s", e)
        except Exception:
            pass
        return None
    finally:
        _dumping.release()


def _metrics_snapshot() -> Dict[str, Any]:
    try:
        snap = _METRICS.snapshot()
        return {name: {",".join(k): v for k, v in series.items()}
                for name, series in snap.items()}
    except Exception as e:  # noqa: BLE001
        return {"error": str(e)}


_sigusr2_installed = False


def install_signal_handler() -> bool:
    """SIGUSR2 -> postmortem dump (idempotent; main thread only — a
    worker initialized off the main thread skips it silently, the
    control-plane dump verb still works there). A user-installed
    SIGUSR2 handler (checkpoint-on-preemption patterns) is NEVER
    replaced — tracing cedes the signal and says so."""
    global _sigusr2_installed
    if _sigusr2_installed:
        return True
    if not hasattr(signal, "SIGUSR2"):  # pragma: no cover - windows
        return False
    try:
        existing = signal.getsignal(signal.SIGUSR2)
    except (ValueError, OSError):  # pragma: no cover - exotic host
        existing = None
    if existing not in (signal.SIG_DFL, signal.SIG_IGN, None,
                        signal.default_int_handler):
        hlog.info("tracing: SIGUSR2 already has a handler; leaving "
                  "it in place (use the elastic 'dump' verb for "
                  "postmortems)")
        return False

    def _handler(signum, frame):
        # The dump runs on a SEPARATE thread: the handler interrupts
        # arbitrary main-thread code, which may hold the very
        # (non-reentrant) metric/logging locks the dump needs —
        # dumping inline would deadlock the process exactly when the
        # operator is inspecting a busy rank.
        threading.Thread(
            target=write_postmortem, args=("SIGUSR2",),
            kwargs={"trigger": "sigusr2"},
            name="hvd-postmortem", daemon=True).start()

    try:
        signal.signal(signal.SIGUSR2, _handler)
    except ValueError:  # not the main thread
        return False
    _sigusr2_installed = True
    return True


# ---------------------------------------------------------------------------
# init/shutdown wiring (called from common/basics.py)
# ---------------------------------------------------------------------------

def on_init(cfg, state) -> None:
    """Post-init hook: honor the Config snapshot (so
    init(config_overrides=...) reaches every tracing knob, not just
    the env), then signal handler + clock calibration. Best effort —
    observability failures warn, never raise."""
    global _cfg
    _cfg = cfg
    # Local wiring first, in its OWN guard: a per-rank failure here
    # (ring resize, signal handler on a non-main thread) must not
    # skip the clock-sync broadcast below — every other rank enters
    # that broadcast unconditionally, so skipping it on one rank
    # would hang their init (hvdlint HVD005 found the original
    # single-try shape).
    try:
        _align_seq_epoch()
        if cfg.trace_ring_size != _ring_size:
            configure_ring(cfg.trace_ring_size)
        if cfg.trace_sigusr2:
            install_signal_handler()
    except Exception as e:  # noqa: BLE001 — observability only
        hlog.warning("tracing: init wiring failed (%s); continuing",
                     e)
    if cfg.timeline_path and state.topology.size > 1:
        try:
            # hvdlint: disable-next=HVD005 (rank-0 pre-broadcast
            # failures are handled inside _start_clock_sync so every
            # rank still reaches the broadcast; a failure of the
            # broadcast itself is a control-plane error surfaced by
            # wire timeouts on the peers, not a silent hang)
            _start_clock_sync(cfg, state.topology, state.timeline)
        except Exception as e:  # noqa: BLE001 — observability only
            hlog.warning("tracing: clock calibration unavailable "
                         "(%s); traces will merge uncalibrated", e)


def rebind_timeline(timeline) -> None:
    """Point the running calibrator at a NEW timeline (runtime
    hvd.start_timeline / stop_timeline): the fresh file gets an
    immediate CLOCK_SYNC record instead of the calibrator writing
    into the closed old one forever. No-op without a calibrator —
    calibration machinery only comes up when HOROVOD_TIMELINE was set
    at init (a runtime-started trace cannot safely run the address
    broadcast mid-training); merge() warns when calibration records
    are missing."""
    cal = _calibrator
    if cal is None:
        return
    cal._timeline = timeline
    if timeline is not None:
        cal.calibrate_once()


def on_shutdown() -> None:
    global _time_service, _calibrator, _cfg
    _cfg = None
    if _calibrator is not None:
        _calibrator.stop()
        _calibrator = None
    if _time_service is not None:
        _time_service.close()
        _time_service = None


# ---------------------------------------------------------------------------
# merge + straggler attribution (offline; doctor / hvdrun)
# ---------------------------------------------------------------------------

def find_trace_files(target: str) -> List[str]:
    """Per-rank trace files for a merge target: a directory (every
    file that sniffs as a Chrome-trace array — HOROVOD_TIMELINE needs
    no .json extension, so rank 0's file may be extensionless) or one
    rank's file (its ``.rankN`` siblings are picked up)."""
    import glob as _glob
    if os.path.isdir(target):
        cand = sorted(_glob.glob(os.path.join(target, "*")))
    else:
        root, ext = os.path.splitext(target)
        cand = sorted(set(
            [target] + _glob.glob(f"{root}.rank*{ext or '.json'}")))
    out = []
    for p in cand:
        base = os.path.basename(p)
        if base.startswith(("postmortem-", "timeline.merged",
                            "straggler_report")):
            continue
        if not os.path.isfile(p):
            continue
        try:
            with open(p, "rb") as f:
                head = f.read(64).lstrip()
        except OSError:
            continue
        if head.startswith(b"["):  # Chrome-trace event array
            out.append(p)
    return out


def _parse_event_array(raw: str) -> Optional[list]:
    """Parse a (possibly damaged) Chrome-trace array. A killed rank
    leaves an unterminated array; a SIGKILL landing mid-write leaves
    a PARTIAL last event. The writer emits one event per line, so
    after the cheap close-the-array attempts, drop damaged tail
    lines (bounded — damage is at most the last flush) until it
    parses: the killed rank's thousands of intact events are usually
    exactly the interesting ones."""
    for attempt in (raw, raw.rstrip().rstrip(",") + "\n]"):
        try:
            events = json.loads(attempt)
            if isinstance(events, list):
                return events
        except ValueError:
            pass
    lines = raw.splitlines()
    for _ in range(16):
        if not lines:
            return None
        lines.pop()
        cand = "\n".join(lines).rstrip().rstrip(",") + "\n]"
        try:
            events = json.loads(cand)
            if isinstance(events, list):
                return events
        except ValueError:
            continue
    return None


def load_trace(path: str) -> Tuple[Optional[dict], List[dict]]:
    """Parse one per-rank trace, tolerating the unterminated array a
    killed rank leaves behind. Returns (meta_args, events)."""
    with open(path) as f:
        raw = f.read()
    events = _parse_event_array(raw)
    if events is None:
        raise ValueError(f"{path}: not a Chrome-trace event array")
    meta = None
    for e in events:
        if e.get("name") == "hvd_trace_meta" and e.get("ph") == "M":
            meta = e.get("args", {})
            break
    return meta, events


def _best_clock_offset(events: List[dict]) -> int:
    """Min-RTT CLOCK_SYNC record wins; 0 when none (single host, or
    rank 0 itself)."""
    best = None
    for e in events:
        if e.get("name") != "CLOCK_SYNC":
            continue
        args = e.get("args", {})
        rtt = int(args.get("rtt_ns", 1 << 62))
        if best is None or rtt < best[1]:
            best = (int(args.get("offset_ns", 0)), rtt)
    return best[0] if best else 0


def merge(target: str, out: Optional[str] = None,
          top_k: int = 3) -> Tuple[str, Dict[str, Any]]:
    """Fuse per-rank traces into one clock-aligned Chrome trace and
    compute the straggler report.

    Writes ``timeline.merged.json`` (one Chrome process per rank) and
    ``straggler_report.json`` next to the inputs (or to `out`).
    Returns (merged_path, report). Byte-deterministic for identical
    inputs (sorted keys, stable event order) so goldens can diff."""
    paths = find_trace_files(target)
    ranks: Dict[int, Tuple[dict, List[dict], str]] = {}
    for p in paths:
        try:
            meta, events = load_trace(p)
        except (OSError, ValueError) as e:
            hlog.warning("tracing: skipping unreadable trace %s (%s)",
                         p, e)
            continue
        if meta is None or "rank" not in meta:
            continue  # not one of ours (no correlation metadata)
        ranks[int(meta["rank"])] = (meta, events, p)
    if not ranks:
        raise ValueError(
            f"no per-rank traces with hvd_trace_meta under {target!r} "
            "(produced by runs with HOROVOD_TIMELINE set)")
    if 0 not in ranks:
        # align against the lowest present rank instead
        base_rank = min(ranks)
        hlog.warning("tracing: rank 0 trace missing; aligning against "
                     "rank %d", base_rank)
    else:
        base_rank = 0
    anchor0 = int(ranks[base_rank][0]["anchor_mono_ns"])
    # Every CLOCK_SYNC offset maps a LOCAL clock onto rank 0's; when
    # the base rank is not rank 0 (its trace is missing), aligning
    # onto the base clock needs off_r - off_base, not off_r alone —
    # otherwise the base rank itself sits displaced by its own offset.
    base_offset = _best_clock_offset(ranks[base_rank][1])

    merged: List[dict] = []
    arrivals: Dict[int, Dict[int, Tuple[str, float]]] = {}
    for rank in sorted(ranks):
        meta, events, _ = ranks[rank]
        anchor = int(meta["anchor_mono_ns"])
        offset = (0 if rank == base_rank
                  else _best_clock_offset(events) - base_offset)
        if rank != base_rank and not any(
                e.get("name") == "CLOCK_SYNC" for e in events):
            hlog.warning(
                "tracing: rank %d trace has no clock-calibration "
                "records; aligning on raw monotonic anchors — only "
                "valid if it was recorded on the same host as rank "
                "%d (calibration requires HOROVOD_TIMELINE set at "
                "init, not a runtime start_timeline)", rank,
                base_rank)
        # local ts_us -> the base rank's monotonic timeline, in us.
        shift_us = (anchor + offset - anchor0) / 1e3
        merged.append({"name": "process_name", "ph": "M", "pid": rank,
                       "tid": 0, "args": {"name": f"rank {rank}"}})
        for e in events:
            ev = dict(e)
            ev["pid"] = rank
            if "ts" in ev:
                ev["ts"] = round(float(ev["ts"]) + shift_us, 3)
            merged.append(ev)
            args = e.get("args") or {}
            if (e.get("name") == "NEGOTIATE" and e.get("ph") == "E"
                    and "seq" in args and "arrival_us" in args):
                arr = float(args["arrival_us"]) + shift_us
                arrivals.setdefault(int(args["seq"]), {})[rank] = \
                    (str(args.get("tensor", "")), arr)
    # stable order: (ts, pid, insertion index); metadata (no ts) first.
    merged = [ev for _, _, ev in sorted(
        ((ev.get("ts", -1.0), ev.get("pid", 0), i), i, ev)
        for i, ev in enumerate(merged))]

    report = straggler_report(arrivals, sorted(ranks), top_k=top_k)

    out_dir = (out if out and os.path.isdir(out)
               else (target if os.path.isdir(target)
                     else os.path.dirname(os.path.abspath(target))))
    merged_path = (out if out and not os.path.isdir(out)
                   else os.path.join(out_dir, "timeline.merged.json"))
    with open(merged_path, "w") as f:
        json.dump({"traceEvents": merged,
                   "displayTimeUnit": "ms",
                   "metadata": {"tool": "horovod_tpu tracing merge",
                                "ranks": sorted(ranks)}},
                  f, sort_keys=True, separators=(",", ":"))
        f.write("\n")
    report_path = os.path.join(os.path.dirname(merged_path),
                               "straggler_report.json")
    with open(report_path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    report["merged_trace"] = merged_path
    report["report_path"] = report_path
    return merged_path, report


def straggler_report(arrivals: Dict[int, Dict[int, Tuple[str, float]]],
                     ranks: List[int],
                     top_k: int = 3) -> Dict[str, Any]:
    """Attribution from per-(seq, rank) calibrated arrival times:
    delta_r = arrival_r - min(arrivals of that collective)."""
    per_rank: Dict[int, List[float]] = {r: [] for r in ranks}
    per_tensor: Dict[str, List[Tuple[float, int]]] = {}
    n_shared = 0
    for seq, by_rank in sorted(arrivals.items()):
        if len(by_rank) < 2:
            continue
        n_shared += 1
        first = min(arr for _, arr in by_rank.values())
        for rank, (name, arr) in by_rank.items():
            delta = (arr - first) / 1e6  # us -> s
            per_rank[rank].append(delta)
            per_tensor.setdefault(name, []).append((delta, rank))

    def _q(sorted_vals: List[float], q: float) -> float:
        return (sorted_vals[int(q * (len(sorted_vals) - 1))]
                if sorted_vals else 0.0)

    rank_stats = {}
    for r in ranks:
        ds = sorted(per_rank[r])
        rank_stats[str(r)] = {
            "collectives": len(ds),
            "mean_delta_s": round(sum(ds) / len(ds), 6) if ds else 0.0,
            "p99_delta_s": round(_q(ds, 0.99), 6),
            "max_delta_s": round(ds[-1], 6) if ds else 0.0,
        }
    tensor_stats = {}
    for name, pairs in sorted(per_tensor.items()):
        ds = sorted(d for d, _ in pairs)
        worst = max(pairs)
        tensor_stats[name] = {
            "samples": len(ds),
            "p50_skew_s": round(_q(ds, 0.50), 6),
            "p99_skew_s": round(_q(ds, 0.99), 6),
            "max_skew_s": round(worst[0], 6),
            "worst_rank": worst[1],
        }
    offenders = sorted(
        ((r, rank_stats[str(r)]["mean_delta_s"]) for r in ranks),
        key=lambda kv: -kv[1])[:max(1, top_k)]
    return {
        "ranks": ranks,
        "correlated_collectives": n_shared,
        "per_rank": rank_stats,
        "per_tensor": tensor_stats,
        "offenders": [[r, m] for r, m in offenders],
    }


def render_report(report: Dict[str, Any]) -> str:
    """Human-readable straggler report for the doctor CLI."""
    lines = [
        "merged trace: " + report.get("merged_trace", "<not written>"),
        f"ranks: {report['ranks']}  correlated collectives: "
        f"{report['correlated_collectives']}",
        "",
        "top offender ranks (mean arrival delta behind the earliest "
        "rank):",
    ]
    for r, mean in report["offenders"]:
        st = report["per_rank"][str(r)]
        lines.append(
            f"  rank {r}: mean {mean * 1e3:8.3f} ms   "
            f"p99 {st['p99_delta_s'] * 1e3:8.3f} ms   "
            f"max {st['max_delta_s'] * 1e3:8.3f} ms   "
            f"over {st['collectives']} collectives")
    worst = sorted(report["per_tensor"].items(),
                   key=lambda kv: -kv[1]["p99_skew_s"])[:10]
    if worst:
        lines += ["", "worst tensors by p99 skew:"]
        for name, st in worst:
            lines.append(
                f"  {name}: p50 {st['p50_skew_s'] * 1e3:.3f} ms  "
                f"p99 {st['p99_skew_s'] * 1e3:.3f} ms  "
                f"max {st['max_skew_s'] * 1e3:.3f} ms "
                f"(rank {st['worst_rank']})")
    return "\n".join(lines)


# Ring armed from the environment at import (workers inherit the knob
# through the forwarded env), mirroring faults.configure_from_env().
configure_ring(_config.env_value("HOROVOD_TRACE_RING_SIZE"))
