"""Metrics subsystem tests: registry semantics, Prometheus text
exposition, live scrape endpoint, stall gauges, instrumentation seams,
and the timeline durability/error-marker fixes that rode along
(reference gap being closed: the reference's timeline.cc /
stall_inspector.cc findings die in log lines — nothing scrapeable)."""

import json
import os
import re
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.metrics import (BYTES_BUCKETS, LATENCY_BUCKETS,
                                 Counter, Gauge, Histogram,
                                 MetricsRegistry, MetricsServer)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# One metric sample line: name{labels} value
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? "
    r"(-?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?|[+-]Inf|NaN)$")


def assert_prometheus_text(text: str) -> None:
    """Every non-comment, non-blank line must be a valid sample."""
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert _SAMPLE_RE.match(line), f"bad exposition line: {line!r}"


class TestRegistry:
    def test_concurrent_counter(self):
        """8 threads x 2000 increments land exactly — the unlocked
        += data race the engine's _bytes_processed had would lose
        updates here."""
        reg = MetricsRegistry()
        c = reg.counter("t_total", "t")

        def worker():
            for _ in range(2000):
                c.inc(3)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value() == 8 * 2000 * 3

    def test_counter_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("t_total", "t")
        with pytest.raises(ValueError, match="cannot decrease"):
            c.inc(-1)

    def test_gauge_set_inc_dec(self):
        reg = MetricsRegistry()
        g = reg.gauge("t_gauge", "t")
        g.set(5)
        g.inc(2)
        g.dec(3)
        assert g.value() == 4

    def test_histogram_bucketing(self):
        """Log-scale buckets with Prometheus le semantics (v <= bound
        counts, including exact boundary hits) and a cumulative view."""
        reg = MetricsRegistry()
        h = reg.histogram("t_seconds", "t", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.01, 0.05, 0.5, 2.0):
            h.observe(v)
        val = h.value()
        assert val["count"] == 5
        assert abs(val["sum"] - 2.565) < 1e-9
        cum = dict(val["buckets"])
        assert cum[0.01] == 2          # 0.005 and the boundary 0.01
        assert cum[0.1] == 3
        assert cum[1.0] == 4
        assert cum[float("inf")] == 5

    def test_labels_required_and_checked(self):
        reg = MetricsRegistry()
        c = reg.counter("t_total", "t", ("kind",))
        with pytest.raises(ValueError, match="labels"):
            c.inc()  # labeled metric needs .labels(...)
        with pytest.raises(ValueError, match="labels"):
            c.labels(wrong="x")
        c.labels(kind="a").inc(2)
        assert c.labels(kind="a").value() == 2
        assert c.labels(kind="b").value() == 0

    def test_idempotent_registration(self):
        reg = MetricsRegistry()
        a = reg.counter("t_total", "t", ("k",))
        assert reg.counter("t_total", "t", ("k",)) is a
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("t_total", "t", ("k",))
        with pytest.raises(ValueError, match="already registered"):
            reg.counter("t_total", "t", ("other",))

    def test_label_escaping(self):
        reg = MetricsRegistry()
        c = reg.counter("t_total", "t", ("name",))
        c.labels(name='we"ird\\path\nline').inc()
        text = reg.generate_text()
        assert r'name="we\"ird\\path\nline"' in text
        assert_prometheus_text(text)

    def test_prometheus_golden(self):
        """Exact text-exposition golden: format drift breaks real
        scrapers, so pin it byte for byte."""
        reg = MetricsRegistry()
        c = reg.counter("test_total", "A counter.", ("kind",))
        c.labels(kind="a").inc(3)
        g = reg.gauge("test_gauge", "A gauge.")
        g.set(2.5)
        h = reg.histogram("test_seconds", "A histogram.",
                          buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(5.0)
        expected = (
            '# HELP test_total A counter.\n'
            '# TYPE test_total counter\n'
            'test_total{kind="a"} 3\n'
            '# HELP test_gauge A gauge.\n'
            '# TYPE test_gauge gauge\n'
            'test_gauge 2.5\n'
            '# HELP test_seconds A histogram.\n'
            '# TYPE test_seconds histogram\n'
            'test_seconds_bucket{le="0.1"} 1\n'
            'test_seconds_bucket{le="1"} 1\n'
            'test_seconds_bucket{le="+Inf"} 2\n'
            'test_seconds_sum 5.05\n'
            'test_seconds_count 2\n')
        assert reg.generate_text() == expected

    def test_snapshot_shape(self):
        reg = MetricsRegistry()
        reg.counter("c_total", "c", ("k",)).labels(k="x").inc(7)
        reg.gauge("g", "g").set(1.5)
        snap = reg.snapshot()
        assert snap["c_total"][("x",)] == 7
        assert snap["g"][()] == 1.5


def test_registry_fast_path_overhead():
    """Tier-1 perf guard: with no scrape server running, the
    registry-only fast path (one dict access + one lock'd add per
    record) must stay far below per-op dispatch cost. The bound is
    generous (100 µs/record on a loaded CI host vs sub-µs typical) —
    it catches pathological regressions (I/O, rendering, or lock
    convoys on the hot path), not micro-drift."""
    reg = MetricsRegistry()
    c = reg.counter("hot_total", "hot", ("pset",)).labels(pset="0")
    h = reg.histogram("hot_seconds", "hot", buckets=LATENCY_BUCKETS)
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        c.inc(4096)
        h.observe(1e-4)
    per_record = (time.perf_counter() - t0) / (2 * n)
    assert per_record < 100e-6, f"{per_record * 1e6:.1f} us/record"


class TestScrapeServer:
    def test_live_scrape_and_404(self):
        reg = MetricsRegistry()
        reg.counter("up_total", "u").inc(2)
        srv = MetricsServer(0, reg)
        try:
            url = f"http://127.0.0.1:{srv.port}/metrics"
            with urllib.request.urlopen(url, timeout=5) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith(
                    "text/plain")
                text = resp.read().decode()
            assert "up_total 2" in text
            assert_prometheus_text(text)
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/nope", timeout=5)
            assert ei.value.code == 404
        finally:
            srv.stop()

    def test_init_knob_serves_and_shutdown_stops(self):
        """HOROVOD_METRICS_PORT through the full hvd lifecycle."""
        import horovod_tpu as hvd
        from horovod_tpu.common.basics import state
        port = _free_port_base(1)
        hvd.init(config_overrides={"HOROVOD_METRICS_PORT": port})
        try:
            assert state().metrics_server is not None
            assert state().metrics_server.port == port
            hvd.allreduce(jnp.ones(16), name="scrape0")
            text = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics",
                timeout=5).read().decode()
            assert "hvd_allreduce_bytes_total" in text
            assert "hvd_dispatch_latency_seconds_bucket" in text
            assert_prometheus_text(text)
        finally:
            hvd.shutdown()
        with pytest.raises(OSError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=2)


def test_stall_gauge_rises_and_clears():
    """Forced stall: a pending collective older than
    HOROVOD_STALL_CHECK_TIME_SECONDS must raise hvd_stalled_tensors
    (and a nonzero max age), and the gauges must clear once the
    pending drains — the alertable form of the stall inspector's
    log-only warning."""
    import horovod_tpu as hvd
    from horovod_tpu.common.basics import state
    from horovod_tpu.ops.compression import NoneCompressor
    from horovod_tpu.ops.controller import _PendingAllreduce
    hvd.init(config_overrides={
        "HOROVOD_CONTROLLER": "python",
        "HOROVOD_STALL_CHECK_TIME_SECONDS": 0.05})
    try:
        st = state()
        ctl = st.engine.controller
        pset = st.process_set_table.global_set
        h = st.engine.new_handle("stuck")
        # A pending entry the core never agrees on (submitted directly
        # into the registry, bypassing core.submit) — what a missing
        # peer looks like from this rank.
        with ctl._mu:
            ctl._pending["stuck"] = _PendingAllreduce(
                [jnp.ones(2)], NoneCompressor, pset, 0, 1.0, 1.0, h,
                False)
        deadline = time.time() + 10
        while time.time() < deadline:
            if hvd.metrics()["hvd_stalled_tensors"][()] >= 1:
                break
            time.sleep(0.02)
        snap = hvd.metrics()
        assert snap["hvd_stalled_tensors"][()] >= 1
        assert snap["hvd_stall_max_age_seconds"][()] >= 0.05
        with ctl._mu:
            ctl._pending.pop("stuck")
        h.set_error(RuntimeError("test cleanup"))
        deadline = time.time() + 10
        while time.time() < deadline:
            if hvd.metrics()["hvd_stalled_tensors"][()] == 0:
                break
            time.sleep(0.02)
        assert hvd.metrics()["hvd_stalled_tensors"][()] == 0
        assert hvd.metrics()["hvd_stall_max_age_seconds"][()] == 0
    finally:
        hvd.shutdown()


class TestInstrumentationSeams:
    def test_engine_bytes_and_latency(self, hvd_single):
        """Inline-path ops land in the engine counters and the
        dispatch-latency histogram; hvd_allreduce_bytes_total tracks
        raw payload bytes by process set."""
        before = hvd_single.metrics()

        def val(snap, name, key=()):
            return snap.get(name, {}).get(key, 0)

        hvd_single.allreduce(jnp.ones(1024, jnp.float32), name="im0")
        after = hvd_single.metrics()
        assert (val(after, "hvd_engine_bytes_total")
                - val(before, "hvd_engine_bytes_total")) == 4096
        assert (val(after, "hvd_engine_ops_total")
                - val(before, "hvd_engine_ops_total")) == 1
        assert (val(after, "hvd_allreduce_bytes_total", ("0",))
                - val(before, "hvd_allreduce_bytes_total",
                      ("0",))) == 4096
        dl_b = before.get("hvd_dispatch_latency_seconds",
                          {}).get((), {"count": 0})["count"]
        dl_a = after["hvd_dispatch_latency_seconds"][()]["count"]
        assert dl_a - dl_b >= 1

    def test_controller_fusion_and_program_cache_metrics(self):
        """The negotiated path scores batches/entries, the fusion
        histograms, negotiation latency, and the composition
        (compiled-program) cache hit/miss pair."""
        import horovod_tpu as hvd
        hvd.init(config_overrides={"HOROVOD_CONTROLLER": "python"})
        try:
            before = hvd.metrics()

            def val(snap, name, key=()):
                return snap.get(name, {}).get(key, 0)

            for i in range(3):
                hvd.allreduce(jnp.ones(64), name=f"fc{i}")
            after = hvd.metrics()
            assert (val(after, "hvd_fused_batches_total", ("ar",))
                    - val(before, "hvd_fused_batches_total",
                          ("ar",))) >= 3
            hits = (val(after, "hvd_fused_program_cache_hits_total")
                    - val(before,
                          "hvd_fused_program_cache_hits_total"))
            misses = (val(after,
                          "hvd_fused_program_cache_misses_total")
                      - val(before,
                            "hvd_fused_program_cache_misses_total"))
            batches = (val(after, "hvd_fused_batches_total", ("ar",))
                       - val(before, "hvd_fused_batches_total",
                             ("ar",)))
            # same composition 3x: >= 1 miss (first), the rest hits;
            # every allreduce batch scores exactly one of the two
            assert misses >= 1
            assert hits + misses == batches, (hits, misses, batches)
            neg = after["hvd_negotiation_latency_seconds"][()]
            neg0 = before.get("hvd_negotiation_latency_seconds",
                              {}).get((), {"count": 0})
            assert neg["count"] - neg0["count"] >= 3
            fb = after["hvd_fusion_batch_bytes"][()]
            assert fb["count"] >= 3
        finally:
            hvd.shutdown()

    def test_autotune_knob_gauges(self):
        from horovod_tpu.autotune import Autotuner
        from horovod_tpu.common.config import Config
        from horovod_tpu.metrics import REGISTRY
        t = Autotuner(Config({"HOROVOD_AUTOTUNE": True,
                              "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": 0,
                              "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": 1},
                             env={}))
        g = REGISTRY.get("hvd_autotune_fusion_threshold_bytes")
        assert g.value() == t.fusion_threshold
        t.record(1000, 0.001)  # one sample -> knob step + republish
        assert g.value() == t.fusion_threshold
        assert REGISTRY.get(
            "hvd_autotune_cycle_time_ms").value() == t.cycle_time_ms

    def test_elastic_state_counters(self):
        from horovod_tpu.elastic.state import ObjectState
        from horovod_tpu.metrics import REGISTRY
        commits = REGISTRY.get("hvd_elastic_commits_total")
        restores = REGISTRY.get("hvd_elastic_restores_total")
        c0, r0 = commits.value(), restores.value()
        st = ObjectState(bcast_object=lambda obj, root_rank=0: obj,
                         epoch=1)
        st.commit()
        st.epoch = 99
        st.restore()
        assert commits.value() - c0 == 1
        assert restores.value() - r0 == 1
        assert st.epoch == 1


class TestLogRank0Only:
    def teardown_method(self, _):
        from horovod_tpu.common import logging as hlog
        hlog.set_rank0_only(False)

    def collect(self, rank, emit):
        import logging
        from horovod_tpu.common import logging as hlog
        records = []

        class Grab(logging.Handler):
            def emit(self, record):
                records.append(record)

        old_rank = hlog._rank_filter.rank
        old_level = hlog.logger.level
        g = Grab(level=logging.DEBUG)
        g.addFilter(hlog._rank_filter)
        hlog.logger.addHandler(g)
        hlog.logger.setLevel(logging.DEBUG)
        hlog.set_rank(rank)
        try:
            emit()
        finally:
            hlog.logger.removeHandler(g)
            hlog.logger.setLevel(old_level)
            hlog._rank_filter.rank = old_rank
        return [r.getMessage() for r in records]

    def test_nonzero_rank_suppresses_info_keeps_warning(self):
        from horovod_tpu.common import logging as hlog
        hlog.set_rank0_only(True)

        def emit():
            hlog.info("info-msg")
            hlog.debug("debug-msg")
            hlog.warning("warn-msg")

        msgs = self.collect(3, emit)
        assert "info-msg" not in msgs and "debug-msg" not in msgs
        assert "warn-msg" in msgs
        # rank 0 keeps everything
        msgs0 = self.collect(0, emit)
        assert "info-msg" in msgs0 and "warn-msg" in msgs0


class TestTimelineFixes:
    def test_done_error_emits_marker_inside_span(self, tmp_path):
        """Timeline.done(name, error=True) must emit the ERROR instant
        BEFORE closing the DISPATCH span (the error flag was silently
        ignored), and the trace must stay balanced."""
        from horovod_tpu.timeline import Timeline
        path = str(tmp_path / "tl.json")
        tl = Timeline(path)
        tl.enqueue("t")
        tl.dispatched("t")
        tl.done("t", error=True)
        tl.close()
        events = json.load(open(path))
        errors = [e for e in events if e["name"] == "ERROR"]
        assert len(errors) == 1 and errors[0]["ph"] == "i"
        d_end = [e for e in events
                 if e["name"] == "DISPATCH" and e["ph"] == "E"][0]
        assert errors[0]["ts"] <= d_end["ts"]
        assert errors[0]["tid"] == d_end["tid"]
        opens = {}
        for e in events:
            key = (e.get("tid"), e["name"])
            if e["ph"] == "B":
                opens[key] = opens.get(key, 0) + 1
            elif e["ph"] == "E":
                opens[key] = opens.get(key, 0) - 1
        assert all(v == 0 for v in opens.values()), opens

    def test_writer_flushes_without_close(self, tmp_path):
        """Durability: events must reach the file shortly after the
        writer drains the queue, WITHOUT close() — a SIGKILLed rank
        keeps its trace up to the last quiet moment (the writer never
        flushed before, so a killed rank lost everything)."""
        from horovod_tpu.timeline import Timeline
        path = str(tmp_path / "tl.json")
        tl = Timeline(path)
        tl.enqueue("persist_me")
        tl.dispatched("persist_me")
        deadline = time.time() + 5
        content = ""
        while time.time() < deadline:
            content = open(path).read()
            if "persist_me" in content and "DISPATCH" in content:
                break
            time.sleep(0.02)
        assert "persist_me" in content, "no flush before close()"
        assert "DISPATCH" in content
        tl.close()


def _free_port_base(n: int = 2) -> int:
    """A base port with n consecutive free ports (rank i binds
    base + local_rank i)."""
    import random
    for _ in range(64):
        base = random.randint(20000, 45000)
        socks = []
        try:
            for i in range(n):
                s = socket.socket()
                s.bind(("127.0.0.1", base + i))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no free port pair found")


@pytest.mark.integration
def test_metrics_scrape_two_ranks():
    """Acceptance path: a live /metrics scrape during a 2-rank
    multiprocess run returns valid Prometheus text with the allreduce
    byte counter, the dispatch-latency histogram, and the stall gauge
    — and hvd.metrics() agrees with the scraped numbers in-process
    (asserted inside the worker)."""
    base = _free_port_base(2)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["HOROVOD_METRICS_PORT"] = str(base)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         sys.executable, os.path.join("tests", "mp_worker_metrics.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240)
    if "Multiprocess computations aren't implemented" in (
            r.stdout + r.stderr):
        pytest.skip("this jaxlib's CPU backend cannot run cross-"
                    "process collectives (affects every multiprocess "
                    "integration test)")
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert r.stdout.count("METRICS ALL OK") == 2
