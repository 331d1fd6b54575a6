"""Process-wide runtime metrics: registry, Prometheus exposition,
opt-in HTTP scrape endpoint, rank-0 periodic summary.

The reference ships a Chrome-trace timeline (timeline.cc) and a stall
inspector (stall_inspector.cc) whose findings die in log lines —
nothing a dashboard or alerting system can consume. This module is the
machine-readable counterpart: a dependency-free, thread-safe registry
of Counters, Gauges and log-scale-bucket Histograms, rendered in the
Prometheus text exposition format (the de-facto fleet scrape wire
format) and served from a background ThreadingHTTPServer when
HOROVOD_METRICS_PORT is set (same serving idiom as the elastic
rendezvous server, runner/elastic/rendezvous.py).

The registry is process-wide and always on: instrumentation seams in
the engine/controller/dispatch/elastic/autotune layers record into it
unconditionally (a dict lookup + a lock'd add — nanoseconds against a
collective dispatch), and `hvd.metrics()` snapshots it in-process.
Serving, like the timeline, is opt-in.

Endpoint is deliberately unauthenticated (read-only, standard
Prometheus scrape contract — scrapers don't sign requests); it exposes
aggregate counters only, never tensor data.
"""

from __future__ import annotations

import bisect
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .common import logging as hlog

# Fixed log-scale bucket ladders. Latencies span profiler-visible
# dispatch (~µs) to stall territory (~minutes); byte sizes span a
# scalar tensor to a fusion bucket far past HOROVOD_FUSION_THRESHOLD.
LATENCY_BUCKETS = (1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 0.1, 1.0, 10.0, 100.0)
BYTES_BUCKETS = (1024.0, 8192.0, 65536.0, 524288.0, 4194304.0,
                 33554432.0, 268435456.0, 2147483648.0)
COUNT_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0)
# Request-serving latencies live in a narrower band than the dispatch
# ladder above: SLO-relevant edges from sub-millisecond (cache-warm
# forward on an idle pool) through the ~10 ms admission budget out to
# multi-second queue-collapse territory, 1-2.5-5 spaced so p50/p99
# interpolation is stable where serving actually operates.
SERVING_LATENCY_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                           0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)
# Serving lifecycle PHASES are one decade finer than the end-to-end
# request ladder: pad/unpad run in tens of microseconds and the
# batch-cut wait tops out at the admission budget, so the request
# ladder's 0.5 ms floor would fold every sub-budget phase into one
# bucket and the p50/p99 decomposition (serving.py's
# hvd_serving_phase_seconds) could not attribute anything.
SERVING_PHASE_BUCKETS = (1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4,
                         1e-3, 2.5e-3, 5e-3, 0.01, 0.025, 0.05, 0.1,
                         0.25, 1.0, 10.0)
# Recovery phases span a sub-second in-process restore to a
# multi-minute blacklist-then-respawn on a starved pool (journal.py's
# hvd_recovery_seconds{phase} SLO histograms).
RECOVERY_BUCKETS = (0.1, 0.5, 1.0, 2.0, 5.0, 15.0, 60.0, 300.0,
                    1800.0)
# Live weight pipeline (weights.py): publish (host trees -> digested
# shards on disk) and per-worker hot-swap (shard read + verify +
# device_put) both move MB-to-GB states through file IO — slower
# than the serving phase ladder, far faster than a recovery — and
# the swap side bounds how long a worker sits out of the pool, so
# the ladder needs resolution from a millisecond toy state out to a
# multi-second flagship publish.
WEIGHT_SWAP_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                       0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0, 60.0)
# Continuous-batching decode (decoding.py): one iteration of the
# running batch — a single AOT-compiled token step plus host-side
# emission — sits in the tens-of-microseconds-to-milliseconds band on
# a toy model and stretches toward a second on a flagship; the ladder
# needs resolution inside a single step, not across a request, which
# is why it starts an order of magnitude below SERVING_PHASE_BUCKETS'
# useful range.
DECODE_STEP_BUCKETS = (1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4,
                       1e-3, 2.5e-3, 5e-3, 0.01, 0.025, 0.05, 0.1,
                       0.25, 1.0)


def _fmt(v: float) -> str:
    """Prometheus sample value: integral floats render as ints."""
    f = float(v)
    if f.is_integer() and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _escape_label(v: str) -> str:
    return (str(v).replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _escape_help(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace("\n", "\\n")


class _Metric:
    """Base: one named metric with 0+ label dimensions; per-label-set
    series live in `_series` behind one lock (metrics are touched at
    collective-dispatch rate, not per-element — one uncontended lock
    is cheaper than sharding)."""

    kind = "untyped"

    def __init__(self, name: str, doc: str,
                 labels: Sequence[str] = ()):
        self.name = name
        self.doc = doc
        self.label_names = tuple(labels)
        self._lock = threading.Lock()
        self._series: Dict[Tuple[str, ...], Any] = {}
        if not self.label_names:
            with self._lock:
                self._series[()] = self._new_series()

    def _new_series(self):
        raise NotImplementedError

    def _key(self, labelkw: Dict[str, str]) -> Tuple[str, ...]:
        if set(labelkw) != set(self.label_names):
            raise ValueError(
                f"{self.name} takes labels {self.label_names}, "
                f"got {tuple(labelkw)}")
        return tuple(str(labelkw[n]) for n in self.label_names)

    def labels(self, **labelkw) -> "_Bound":
        return _Bound(self, self._key(labelkw))

    def _check_unlabeled(self) -> None:
        if self.label_names:
            raise ValueError(
                f"{self.name} has labels {self.label_names}; use "
                ".labels(...)")


class _Bound:
    """A metric bound to one label set; forwards the mutators."""

    __slots__ = ("_m", "_k")

    def __init__(self, metric: "_Metric", key: Tuple[str, ...]):
        self._m = metric
        self._k = key

    def inc(self, amount: float = 1.0) -> None:
        self._m._inc(self._k, amount)

    def dec(self, amount: float = 1.0) -> None:
        self._m._inc(self._k, -amount)

    def set(self, value: float) -> None:
        self._m._set(self._k, value)

    def observe(self, value: float) -> None:
        self._m._observe(self._k, value)

    def value(self):
        return self._m._value(self._k)


class Counter(_Metric):
    """Monotonic counter (Prometheus counter semantics: inc-only)."""

    kind = "counter"

    def _new_series(self) -> float:
        return 0.0

    def _inc(self, key: Tuple[str, ...], amount: float) -> None:
        if amount < 0:
            raise ValueError(
                f"counter {self.name} cannot decrease (inc {amount})")
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + amount

    def _value(self, key: Tuple[str, ...]) -> float:
        with self._lock:
            return self._series.get(key, 0.0)

    def inc(self, amount: float = 1.0) -> None:
        self._check_unlabeled()
        self._inc((), amount)

    def value(self) -> float:
        self._check_unlabeled()
        return self._value(())


class Gauge(_Metric):
    """Settable value (current knob positions, stalled-tensor count)."""

    kind = "gauge"

    def _new_series(self) -> float:
        return 0.0

    def _inc(self, key: Tuple[str, ...], amount: float) -> None:
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + amount

    def _set(self, key: Tuple[str, ...], value: float) -> None:
        with self._lock:
            self._series[key] = float(value)

    def _value(self, key: Tuple[str, ...]) -> float:
        with self._lock:
            return self._series.get(key, 0.0)

    def set(self, value: float) -> None:
        self._check_unlabeled()
        self._set((), value)

    def inc(self, amount: float = 1.0) -> None:
        self._check_unlabeled()
        self._inc((), amount)

    def dec(self, amount: float = 1.0) -> None:
        self._check_unlabeled()
        self._inc((), -amount)

    def value(self) -> float:
        self._check_unlabeled()
        return self._value(())


class Histogram(_Metric):
    """Histogram with fixed (log-scale by default) buckets. Series
    state is [per-bucket counts (+overflow slot), sum, count]; the
    cumulative `le` view Prometheus wants is computed at render."""

    kind = "histogram"

    def __init__(self, name: str, doc: str,
                 labels: Sequence[str] = (),
                 buckets: Sequence[float] = LATENCY_BUCKETS):
        bs = tuple(sorted(float(b) for b in buckets))
        if not bs:
            raise ValueError(f"histogram {name} needs >= 1 bucket")
        self.buckets = bs
        super().__init__(name, doc, labels)

    def _new_series(self) -> List[Any]:
        return [[0] * (len(self.buckets) + 1), 0.0, 0]

    def _observe(self, key: Tuple[str, ...], value: float) -> None:
        v = float(value)
        idx = bisect.bisect_left(self.buckets, v)
        with self._lock:
            st = self._series.get(key)
            if st is None:
                st = self._series[key] = self._new_series()
            st[0][idx] += 1
            st[1] += v
            st[2] += 1

    def _value(self, key: Tuple[str, ...]) -> Dict[str, Any]:
        with self._lock:
            st = self._series.get(key)
            if st is None:
                st = self._new_series()
            counts, total, n = list(st[0]), st[1], st[2]
        cum, acc = [], 0
        for b, c in zip(self.buckets, counts):
            acc += c
            cum.append((b, acc))
        cum.append((float("inf"), n))
        return {"count": n, "sum": total, "buckets": tuple(cum)}

    def observe(self, value: float) -> None:
        self._check_unlabeled()
        self._observe((), value)

    def value(self) -> Dict[str, Any]:
        self._check_unlabeled()
        return self._value(())


class MetricsRegistry:
    """Named metric table with idempotent registration (a second
    registration of the same name/type/labels returns the existing
    metric, so instrumentation seams need no import-order choreography)
    and Prometheus text rendering."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, _Metric] = {}

    def _register(self, cls, name: str, doc: str,
                  labels: Sequence[str], **kw) -> _Metric:
        labels = tuple(labels)
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if type(m) is not cls or m.label_names != labels:
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{type(m).__name__}{m.label_names}, wanted "
                        f"{cls.__name__}{labels}")
                return m
            m = cls(name, doc, labels, **kw)
            self._metrics[name] = m
            return m

    def counter(self, name: str, doc: str,
                labels: Sequence[str] = ()) -> Counter:
        return self._register(Counter, name, doc, labels)

    def gauge(self, name: str, doc: str,
              labels: Sequence[str] = ()) -> Gauge:
        return self._register(Gauge, name, doc, labels)

    def histogram(self, name: str, doc: str,
                  labels: Sequence[str] = (),
                  buckets: Sequence[float] = LATENCY_BUCKETS
                  ) -> Histogram:
        return self._register(Histogram, name, doc, labels,
                              buckets=buckets)

    def get(self, name: str) -> Optional[_Metric]:
        with self._lock:
            return self._metrics.get(name)

    def snapshot(self) -> Dict[str, Dict[Tuple[str, ...], Any]]:
        """{name: {label_values_tuple: value}}; counters/gauges map to
        floats, histograms to {'count','sum','buckets'} dicts. The
        unlabeled series key is ()."""
        with self._lock:
            metrics = list(self._metrics.values())
        out: Dict[str, Dict[Tuple[str, ...], Any]] = {}
        for m in metrics:
            with m._lock:
                keys = list(m._series)
            out[m.name] = {k: m._value(k) for k in sorted(keys)}
        return out

    def generate_text(self) -> str:
        """Prometheus text exposition (format version 0.0.4)."""
        with self._lock:
            metrics = list(self._metrics.values())
        lines: List[str] = []
        for m in metrics:
            lines.append(f"# HELP {m.name} {_escape_help(m.doc)}")
            lines.append(f"# TYPE {m.name} {m.kind}")
            with m._lock:
                keys = sorted(m._series)
            for key in keys:
                val = m._value(key)
                pairs = [f'{n}="{_escape_label(v)}"'
                         for n, v in zip(m.label_names, key)]
                if isinstance(m, Histogram):
                    for le, cum in val["buckets"]:
                        ps = pairs + [
                            'le="+Inf"' if le == float("inf")
                            else f'le="{_fmt(le)}"']
                        lines.append(
                            f"{m.name}_bucket{{{','.join(ps)}}} "
                            f"{_fmt(cum)}")
                    lbl = f"{{{','.join(pairs)}}}" if pairs else ""
                    lines.append(
                        f"{m.name}_sum{lbl} {_fmt(val['sum'])}")
                    lines.append(
                        f"{m.name}_count{lbl} {_fmt(val['count'])}")
                else:
                    lbl = f"{{{','.join(pairs)}}}" if pairs else ""
                    lines.append(f"{m.name}{lbl} {_fmt(val)}")
        return "\n".join(lines) + ("\n" if lines else "")


# The process-wide registry every subsystem instruments against.
REGISTRY = MetricsRegistry()


def snapshot() -> Dict[str, Dict[Tuple[str, ...], Any]]:
    """Snapshot of the process-wide registry (hvd.metrics())."""
    return REGISTRY.snapshot()


def generate_text() -> str:
    return REGISTRY.generate_text()


# -- hot-path helper for the dispatch layer ---------------------------------
# Bound children cached per (kind, pset) so the data plane pays one
# dict lookup + one lock'd add per collective, no registry traffic.

_collective_cache: Dict[Tuple[str, str], Tuple[_Bound, _Bound]] = {}


def record_collective(kind: str, pset_id, nbytes: int,
                      tensors: int = 1) -> None:
    """Per-collective-kind and per-process-set accounting (called by
    ops/dispatch.py entry points)."""
    key = (kind, str(pset_id))
    pair = _collective_cache.get(key)
    if pair is None:
        b = REGISTRY.counter(
            f"hvd_{kind}_bytes_total",
            f"Raw payload bytes submitted to {kind} (pre-compression), "
            "by process set.", ("pset",)).labels(pset=key[1])
        o = REGISTRY.counter(
            "hvd_collective_tensors_total",
            "Tensors dispatched, by collective kind and process set.",
            ("kind", "pset")).labels(kind=kind, pset=key[1])
        pair = _collective_cache[key] = (b, o)
    pair[0].inc(nbytes)
    pair[1].inc(tensors)


_wire_cache: Dict[str, Tuple[_Bound, _Bound, _Bound]] = {}


def record_wire(compression: str, raw_bytes: int,
                wire_bytes: int) -> None:
    """Gradient wire-byte accounting by compression tag ("none",
    "fp16", "bf16"). Called once per submission on the
    eager plane and once per COMPILE on the jit plane (where the wire
    is static per program — the trace-time record states what each
    step of that program will move). `raw_bytes` is the uncompressed
    payload, `wire_bytes` what actually hits the interconnect; the
    saved-bytes counter and achieved-ratio gauge are derived here so
    dashboards don't have to."""
    trio = _wire_cache.get(compression)
    if trio is None:
        w = REGISTRY.counter(
            "hvd_wire_bytes_total",
            "Bytes actually moved on the gradient wire (post-"
            "compression), by compression tag.",
            ("compression",)).labels(compression=compression)
        s = REGISTRY.counter(
            "hvd_wire_bytes_saved_total",
            "Raw-minus-wire gradient bytes elided by compression, "
            "by compression tag.",
            ("compression",)).labels(compression=compression)
        r = REGISTRY.gauge(
            "hvd_compression_ratio",
            "Achieved raw/wire compression ratio of the most recent "
            "submission, by compression tag.",
            ("compression",)).labels(compression=compression)
        trio = _wire_cache[compression] = (w, s, r)
    trio[0].inc(wire_bytes)
    trio[1].inc(max(0, raw_bytes - wire_bytes))
    trio[2].set(raw_bytes / wire_bytes if wire_bytes else 0.0)


# -- scrape endpoint --------------------------------------------------------

class _Handler(BaseHTTPRequestHandler):
    registry: MetricsRegistry = None  # injected

    def log_message(self, *args):  # silence default stderr spam
        pass

    def do_GET(self):
        path = self.path.split("?", 1)[0]
        if path in ("/metrics", "/"):
            body = self.registry.generate_text().encode()
            self.send_response(200)
            self.send_header(
                "Content-Type",
                "text/plain; version=0.0.4; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        else:
            body = b"not found\n"
            self.send_response(404)
            self.send_header("Content-Type", "text/plain")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)


class MetricsServer:
    """Background Prometheus scrape endpoint (ThreadingHTTPServer, the
    rendezvous-server idiom). port=0 binds an ephemeral port; the
    bound port is `self.port`."""

    def __init__(self, port: int = 0,
                 registry: Optional[MetricsRegistry] = None):
        handler = type("Handler", (_Handler,),
                       {"registry": registry or REGISTRY})
        self._httpd = ThreadingHTTPServer(("0.0.0.0", port), handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="hvd-metrics",
            daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()


def serve(port: int = 0,
          registry: Optional[MetricsRegistry] = None) -> MetricsServer:
    return MetricsServer(port, registry)


# -- rank-0 periodic summary ------------------------------------------------

class SummaryLogger:
    """Periodic INFO line with the registry's nonzero counters/gauges
    (histograms contribute their _count) — the greppable heartbeat for
    runs without a scraper attached."""

    MAX_FIELDS = 40

    def __init__(self, interval_s: float,
                 registry: Optional[MetricsRegistry] = None):
        self.registry = registry or REGISTRY
        self.interval_s = float(interval_s)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="hvd-metrics-summary", daemon=True)
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            line = self.summary_line()
            if line:
                hlog.info("metrics: %s", line)

    def summary_line(self) -> str:
        parts = []
        for name, series in self.registry.snapshot().items():
            for key, v in series.items():
                out_name = name
                if isinstance(v, dict):
                    v = v["count"]
                    out_name = name + "_count"
                if not v:
                    continue
                if key:
                    lbl = ",".join(key)
                    parts.append(f"{out_name}{{{lbl}}}={_fmt(v)}")
                else:
                    parts.append(f"{out_name}={_fmt(v)}")
        return " ".join(parts[:self.MAX_FIELDS])

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2)
