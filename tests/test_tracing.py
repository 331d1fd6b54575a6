"""Distributed-tracing subsystem tests (tracing.py + the timeline.py
surgery): flight-recorder ring bounds, the NTP-style clock-offset
estimator on synthetic skew, merge byte-stability + straggler
attribution on synthetic per-rank files, SIGUSR2/postmortem dumps,
the always-on hot-path overhead guard (same style as faults.py's
disarmed guard), and a 2-rank integration run behind the multiproc
capability probe."""

import json
import os
import random
import signal
import subprocess
import sys
import time

import pytest

from horovod_tpu import tracing
from horovod_tpu.common import config as hconfig
from horovod_tpu.timeline import Timeline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def default_ring():
    """Restore the environment-configured ring after tests that
    resize/disable it."""
    yield
    tracing.configure_ring(hconfig.env_value("HOROVOD_TRACE_RING_SIZE"))


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_is_bounded(self, default_ring):
        tracing.configure_ring(8)
        for i in range(50):
            tracing.record("dispatch", f"t{i}", i)
        evs = tracing.ring_events()
        assert len(evs) == 8
        # oldest events fell off; the tail is the newest
        assert [e[2] for e in evs] == [f"t{i}" for i in range(42, 50)]
        assert evs[-1][3] == 49
        assert tracing.ring_events(limit=3) == evs[-3:]

    def test_ring_disabled_is_noop(self, default_ring):
        tracing.configure_ring(0)
        tracing.record("dispatch", "nope")
        assert tracing.ring_events() == []

    def test_hot_path_overhead(self, default_ring):
        """Tier-1 perf guard (same shape as faults.py's disarmed
        guard): the always-on ring append — the ONLY per-span cost
        with HOROVOD_TIMELINE unset — and the fully-disabled path
        both stay bounded. Generous bound for a loaded CI host."""
        n = 50000
        tracing.configure_ring(4096)           # the always-on default
        t0 = time.perf_counter()
        for _ in range(n):
            tracing.record("dispatch", "guard")
        per_call_on = (time.perf_counter() - t0) / n
        tracing.configure_ring(0)              # ring disabled
        t0 = time.perf_counter()
        for _ in range(n):
            tracing.record("dispatch", "guard")
        per_call_off = (time.perf_counter() - t0) / n
        assert per_call_on < 20e-6, f"{per_call_on * 1e6:.2f} us/call"
        assert per_call_off < 20e-6, f"{per_call_off * 1e6:.2f} us/call"


# ---------------------------------------------------------------------------
# trace context
# ---------------------------------------------------------------------------

class TestTraceContext:
    def test_seq_reservation_and_step(self):
        tracing.reset_context()
        assert tracing.next_seq(3) == 0
        assert tracing.next_seq() == 3
        tracing.set_step(7)
        assert tracing.current_step() == 7
        assert tracing.advance_step() == 8
        tracing.reset_context()
        assert tracing.next_seq() == 0


# ---------------------------------------------------------------------------
# clock-offset estimation
# ---------------------------------------------------------------------------

class TestClockOffset:
    def test_estimator_recovers_synthetic_skew(self):
        """A fake rank-0 clock 7.5 s ahead, probed through jittery
        round trips: the min-RTT midpoint estimate must recover the
        skew within its own RTT bound (the NTP guarantee: the server
        read falls inside [send, recv], so |error| <= rtt/2)."""
        skew_ns = 7_500_000_000
        rng = random.Random(3)

        def probe():
            time.sleep(rng.random() * 0.002)
            return time.monotonic_ns() + skew_ns

        off, rtt = tracing.estimate_offset(probe, probes=8)
        assert abs(off - skew_ns) <= rtt
        assert abs(off - skew_ns) < 5_000_000  # < 5 ms in practice

    def test_estimator_zero_skew(self):
        off, rtt = tracing.estimate_offset(time.monotonic_ns,
                                           probes=4)
        assert abs(off) <= max(rtt, 1_000_000)

    def test_time_service_roundtrip(self):
        """The real wire: a TimeService probed through the
        authenticated BasicClient; same process => same clock, so the
        estimate must be within the RTT bound of zero."""
        from horovod_tpu.runner.service import BasicClient
        svc = tracing.TimeService("s3cr3t-trace")
        try:
            cli = BasicClient("127.0.0.1", svc.port, "s3cr3t-trace",
                              timeout=5.0)

            def probe():
                return int(cli.request({"type": "time"})["mono_ns"])

            off, rtt = tracing.estimate_offset(probe, probes=4)
            assert abs(off) <= rtt
        finally:
            svc.close()


# ---------------------------------------------------------------------------
# timeline anchor + per-rank paths
# ---------------------------------------------------------------------------

class TestTimelineAnchor:
    def test_meta_record_and_monotonic_anchor(self, tmp_path):
        path = str(tmp_path / "tl.json")
        tl = Timeline(path, rank=3)
        tl.enqueue("t1")
        tl.dispatched("t1")
        tl.done("t1")
        tl.clock_sync(-123456, 789)
        tl.close()
        events = json.load(open(path))
        meta = [e for e in events if e["name"] == "hvd_trace_meta"]
        assert len(meta) == 1
        args = meta[0]["args"]
        assert args["rank"] == 3
        assert args["anchor_mono_ns"] > 0
        assert args["anchor_unix_ns"] > 0
        sync = [e for e in events if e["name"] == "CLOCK_SYNC"]
        assert sync and sync[0]["args"]["offset_ns"] == -123456
        # span timestamps are monotonic-since-anchor, small positive us
        spans = [e for e in events if "ts" in e]
        assert all(0 <= e["ts"] < 60e6 for e in spans)

    def test_rank_path(self):
        assert Timeline.rank_path("tl.json", 0) == "tl.json"
        assert Timeline.rank_path("tl.json", 2) == "tl.rank2.json"
        assert Timeline.rank_path("/a/b/trace", 1) == "/a/b/trace.rank1.json"

    def test_negotiate_end_carries_trace_context(self, tmp_path):
        path = str(tmp_path / "tl.json")
        tl = Timeline(path)
        tl.negotiate_start("g0")
        tl.negotiate_end("g0", negotiate_us=1500, seq=12, step=4,
                         arrival_us=123.456)
        tl.close()
        events = json.load(open(path))
        neg = [e for e in events
               if e["name"] == "NEGOTIATE" and e["ph"] == "E"]
        args = neg[0]["args"]
        assert args["seq"] == 12 and args["step"] == 4
        assert args["tensor"] == "g0"
        assert args["arrival_us"] == 123.456
        assert args["coordinator_negotiate_us"] == 1500


# ---------------------------------------------------------------------------
# merge + straggler attribution (synthetic per-rank files)
# ---------------------------------------------------------------------------

def _write_rank_trace(path, rank, anchor_mono_ns, events,
                      clock_syncs=(), truncate=False):
    evs = [{"name": "hvd_trace_meta", "ph": "M", "pid": 0, "tid": 0,
            "args": {"rank": rank, "anchor_mono_ns": anchor_mono_ns,
                     "anchor_unix_ns": 1_700_000_000_000_000_000,
                     "version": 1}}]
    for off, rtt in clock_syncs:
        evs.append({"name": "CLOCK_SYNC", "ph": "M", "pid": 0,
                    "tid": 0, "args": {"offset_ns": off,
                                       "rtt_ns": rtt}})
    evs += events
    body = json.dumps(evs)
    if truncate:
        # what a SIGKILLed rank leaves behind: an unterminated array
        body = body[:-1].rstrip() + ","
    with open(path, "w") as f:
        f.write(body)


def _neg_end(tensor, seq, arrival_us, ts_us, tid=1):
    return {"name": "NEGOTIATE", "ph": "E", "pid": 0, "tid": tid,
            "ts": ts_us, "args": {"seq": seq, "step": 0,
                                  "tensor": tensor,
                                  "arrival_us": arrival_us}}


def _make_two_rank_dir(d):
    """Rank 1 runs on a clock anchored 1 s later with a known
    calibration offset; it arrives 42 ms late at grads_0 and on time
    at grads_1."""
    # rank 0: anchor 1e9; arrivals at 600_000 us and 700_000 us.
    _write_rank_trace(
        os.path.join(d, "tl.json"), 0, 1_000_000_000,
        [{"name": "QUEUE", "ph": "B", "pid": 0, "tid": 1,
          "ts": 500.0},
         {"name": "QUEUE", "ph": "E", "pid": 0, "tid": 1,
          "ts": 900.0},
         _neg_end("grads_0", 0, 600_000.0, 650_000.0),
         _neg_end("grads_1", 1, 700_000.0, 750_000.0)])
    # rank 1: anchor 2e9, offset -0.5e9 => shift = +500_000 us on
    # rank 0's axis. grads_0 local arrival 142_000 -> global 642_000
    # (42 ms late); grads_1 local 200_000 -> global 700_000 (on time).
    _write_rank_trace(
        os.path.join(d, "tl.rank1.json"), 1, 2_000_000_000,
        [_neg_end("grads_0", 0, 142_000.0, 160_000.0),
         _neg_end("grads_1", 1, 200_000.0, 255_000.0)],
        clock_syncs=[(-500_000_000, 40_000), (-400_000_000, 900_000)])


class TestMergeAndAttribution:
    def test_merge_aligns_clocks_and_names_straggler(self, tmp_path):
        d = str(tmp_path)
        _make_two_rank_dir(d)
        merged_path, report = tracing.merge(d)
        doc = json.load(open(merged_path))
        evs = doc["traceEvents"]
        assert {e.get("pid") for e in evs if "ts" in e} == {0, 1}
        # one process_name track per rank
        pnames = {e["pid"]: e["args"]["name"] for e in evs
                  if e.get("name") == "process_name"}
        assert pnames == {0: "rank 0", 1: "rank 1"}
        # rank 1 timestamps shifted onto rank 0's axis with the
        # MIN-RTT calibration record (-0.5 s, not the noisier -0.4 s)
        r1_neg = [e for e in evs
                  if e.get("pid") == 1 and e.get("name") == "NEGOTIATE"]
        assert r1_neg[0]["ts"] == pytest.approx(660_000.0)
        # attribution: rank 1 is the offender, 42 ms late at grads_0
        assert report["correlated_collectives"] == 2
        assert report["offenders"][0][0] == 1
        t0 = report["per_tensor"]["grads_0"]
        assert t0["worst_rank"] == 1
        assert t0["max_skew_s"] == pytest.approx(0.042, abs=1e-6)
        assert report["per_rank"]["1"]["mean_delta_s"] == \
            pytest.approx(0.021, abs=1e-6)
        assert report["per_rank"]["0"]["mean_delta_s"] == 0.0

    def test_merge_is_byte_stable(self, tmp_path):
        """Identical inputs => byte-identical merged trace and report
        (golden-file property: a re-run must not churn diffs)."""
        da, db = tmp_path / "a", tmp_path / "b"
        da.mkdir(), db.mkdir()
        _make_two_rank_dir(str(da))
        _make_two_rank_dir(str(db))
        pa, _ = tracing.merge(str(da))
        pb, _ = tracing.merge(str(db))
        assert open(pa, "rb").read() == open(pb, "rb").read()
        ra = open(os.path.join(str(da), "straggler_report.json"),
                  "rb").read()
        rb = open(os.path.join(str(db), "straggler_report.json"),
                  "rb").read()
        assert ra == rb

    def test_merge_tolerates_truncated_trace(self, tmp_path):
        """A SIGKILLed rank leaves an unterminated JSON array; the
        loader repairs it instead of dropping the rank."""
        d = str(tmp_path)
        _write_rank_trace(os.path.join(d, "tl.json"), 0, 1_000,
                          [_neg_end("g", 0, 100.0, 200.0)])
        _write_rank_trace(os.path.join(d, "tl.rank1.json"), 1, 1_000,
                          [_neg_end("g", 0, 150.0, 260.0)],
                          truncate=True)
        _, report = tracing.merge(d)
        assert report["ranks"] == [0, 1]
        assert report["correlated_collectives"] == 1

    def test_merge_missing_rank0_aligns_relative_to_base(self,
                                                         tmp_path):
        """Rank 0's trace lost: the fallback base (lowest present
        rank) must subtract ITS OWN rank-0 offset from everyone —
        otherwise the base sits displaced by its offset and dominates
        the straggler report."""
        d = str(tmp_path)
        # rank 1 (base): offset to rank 0 = +3 s.
        _write_rank_trace(
            os.path.join(d, "tl.rank1.json"), 1, 1_000_000_000,
            [_neg_end("g", 0, 100_000.0, 150_000.0)],
            clock_syncs=[(3_000_000_000, 10_000)])
        # rank 2: offset +3.005 s, same anchor; arrives 5 ms late.
        _write_rank_trace(
            os.path.join(d, "tl.rank2.json"), 2, 1_000_000_000,
            [_neg_end("g", 0, 100_000.0, 160_000.0)],
            clock_syncs=[(3_005_000_000, 10_000)])
        _, report = tracing.merge(d)
        assert report["ranks"] == [1, 2]
        t = report["per_tensor"]["g"]
        assert t["worst_rank"] == 2
        assert t["max_skew_s"] == pytest.approx(0.005, abs=1e-6)

    def test_merge_tolerates_mid_event_truncation(self, tmp_path):
        """A SIGKILL landing mid `f.write` leaves a PARTIAL last
        event (not just a missing ']'); the loader drops the damaged
        tail line and keeps the intact events."""
        d = str(tmp_path)
        _write_rank_trace(os.path.join(d, "tl.json"), 0, 1_000,
                          [_neg_end("g", 0, 100.0, 200.0)])
        meta = json.dumps(
            {"name": "hvd_trace_meta", "ph": "M", "pid": 0, "tid": 0,
             "args": {"rank": 1, "anchor_mono_ns": 1_000,
                      "anchor_unix_ns": 1, "version": 1}})
        ev = json.dumps(_neg_end("g", 0, 150.0, 260.0))
        raw = "[\n" + meta + ",\n" + ev + ',\n{"name": "NEGO'
        with open(os.path.join(d, "tl.rank1.json"), "w") as f:
            f.write(raw)
        _, report = tracing.merge(d)
        assert report["ranks"] == [0, 1]
        assert report["correlated_collectives"] == 1

    def test_merge_dir_finds_extensionless_rank0(self, tmp_path):
        """HOROVOD_TIMELINE needs no .json extension: directory-mode
        discovery must still find rank 0's extensionless file next to
        the .rankN.json siblings."""
        d = str(tmp_path)
        _write_rank_trace(os.path.join(d, "trace"), 0, 1_000,
                          [_neg_end("g", 0, 100.0, 200.0)])
        _write_rank_trace(os.path.join(d, "trace.rank1.json"), 1,
                          1_000, [_neg_end("g", 0, 150.0, 260.0)])
        _, report = tracing.merge(d)
        assert report["ranks"] == [0, 1]
        assert report["correlated_collectives"] == 1

    def test_merge_without_traces_raises(self, tmp_path):
        with pytest.raises(ValueError, match="no per-rank traces"):
            tracing.merge(str(tmp_path))

    def test_doctor_cli_renders_report(self, tmp_path, capsys):
        from horovod_tpu.runner.doctor import main as doctor_main
        d = str(tmp_path)
        _make_two_rank_dir(d)
        assert doctor_main(["trace", d]) == 0
        out = capsys.readouterr().out
        assert "rank 1" in out and "grads_0" in out
        assert doctor_main(["trace", str(tmp_path / "empty")]) == 1


# ---------------------------------------------------------------------------
# postmortem / flight-recorder dumps
# ---------------------------------------------------------------------------

class TestPostmortem:
    def test_write_postmortem_contents(self, tmp_path, monkeypatch,
                                       default_ring):
        monkeypatch.setenv("HOROVOD_TRACE_POSTMORTEM_DIR",
                           str(tmp_path))
        tracing.configure_ring(16)
        tracing.record("dispatch", "pm_op", 5)
        path = tracing.write_postmortem("unit test", trigger="manual")
        assert path == str(tmp_path / "postmortem-rank0.json")
        doc = json.load(open(path))
        assert doc["reason"] == "unit test"
        assert doc["trigger"] == "manual"
        assert any(ev[2] == "pm_op" for ev in doc["ring"])
        # thread stacks include at least this (main) thread
        assert doc["thread_stacks"]
        assert "metrics" in doc and "runtime" in doc

    def test_sigusr2_dump(self, tmp_path, monkeypatch, default_ring):
        monkeypatch.setenv("HOROVOD_TRACE_POSTMORTEM_DIR",
                           str(tmp_path))
        tracing.configure_ring(16)
        tracing.record("dispatch", "sig_op")
        assert tracing.install_signal_handler()
        os.kill(os.getpid(), signal.SIGUSR2)
        deadline = time.time() + 10
        path = tmp_path / "postmortem-rank0.json"
        while not path.exists() and time.time() < deadline:
            time.sleep(0.05)
        assert path.exists()
        doc = json.load(open(str(path)))
        assert doc["trigger"] == "sigusr2"

    def test_init_survives_unwritable_timeline_dir(self, tmp_path,
                                                   default_ring):
        """A host where the trace directory is missing loses its
        trace with a warning — hvd.init() must not die for an
        observability feature. Piggybacks the config_overrides
        plumbing check: trace knobs set via init(config_overrides=)
        (not env) must reach the ring and the signal handler."""
        import horovod_tpu as hvd
        from horovod_tpu.common.basics import state
        hvd.init(config_overrides={
            "HOROVOD_TIMELINE": str(tmp_path / "nope" / "tl.json"),
            "HOROVOD_TRACE_RING_SIZE": 8,
            "HOROVOD_TRACE_POSTMORTEM_DIR": str(tmp_path)})
        try:
            assert state().timeline is None
            for i in range(20):
                tracing.record("dispatch", f"o{i}")
            assert len(tracing.ring_events()) == 8
            assert tracing.postmortem_dir() == str(tmp_path)
        finally:
            hvd.shutdown()
            tracing._cfg = None

    def test_sigusr2_respects_user_handler(self):
        """A user-installed SIGUSR2 handler (checkpoint-on-preemption
        patterns) must never be replaced."""
        was_installed = tracing._sigusr2_installed
        tracing._sigusr2_installed = False

        def user_handler(sig, frm):
            pass

        old = signal.signal(signal.SIGUSR2, user_handler)
        try:
            assert tracing.install_signal_handler() is False
            assert signal.getsignal(signal.SIGUSR2) is user_handler
        finally:
            signal.signal(signal.SIGUSR2, old)
            tracing._sigusr2_installed = was_installed

    def test_dump_verb_over_the_wire(self, tmp_path, monkeypatch):
        """The elastic control plane's dump verb: a BasicClient with
        the job secret asks a live worker for its postmortem."""
        monkeypatch.setenv("HOROVOD_TRACE_POSTMORTEM_DIR",
                           str(tmp_path))
        monkeypatch.setenv("HOROVOD_SECRET", "dump-secret")
        from horovod_tpu.elastic.worker import NotificationListener
        from horovod_tpu.runner.service import BasicClient
        lst = NotificationListener()
        try:
            cli = BasicClient("127.0.0.1", lst.port, "dump-secret",
                              timeout=5.0)
            reply = cli.request({"type": "dump"})
            assert reply["ok"] is True
            assert os.path.exists(reply["path"])
            doc = json.load(open(reply["path"]))
            assert doc["trigger"] == "dump_verb"
        finally:
            lst.stop()


# ---------------------------------------------------------------------------
# 2-rank integration: merged trace + straggler attribution
# ---------------------------------------------------------------------------

@pytest.mark.integration
def test_two_rank_merged_trace_names_slow_rank(tmp_path):
    """Acceptance path: a 2-rank run with HOROVOD_TIMELINE set and a
    rank 1 that computes 150 ms longer before every submit produces
    per-rank traces that merge into one clock-aligned Chrome trace
    containing both ranks with SHARED collective sequence ids, and
    the straggler report names the slow rank."""
    tl_path = str(tmp_path / "tl.json")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["HOROVOD_TIMELINE"] = tl_path
    # Rank 1 sleeps 150 ms before every submit, so negotiation waits
    # on it — the classic straggler. (A delay at dispatch entry does
    # not make one: a real cross-process collective holds the fast
    # rank until the slow one joins, and both submit the next step
    # together.)
    env["TRACING_WORKER_SLOW_RANK"] = "1"
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         sys.executable, os.path.join("tests", "mp_worker_tracing.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240)
    if "Multiprocess computations aren't implemented" in (
            r.stdout + r.stderr):
        pytest.skip("this jaxlib's CPU backend cannot run cross-"
                    "process collectives (affects every multiprocess "
                    "integration test)")
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert r.stdout.count("TRACING WORKER OK") == 2

    merged_path, report = tracing.merge(tl_path)
    doc = json.load(open(merged_path))
    evs = doc["traceEvents"]
    assert {0, 1} <= {e.get("pid") for e in evs}

    # shared collective sequence ids: the same named collective got
    # the SAME seq on both ranks (assigned from the agreed order)
    by_name = {}
    for e in evs:
        args = e.get("args") or {}
        if e.get("name") == "NEGOTIATE" and e.get("ph") == "E" \
                and "seq" in args:
            by_name.setdefault(args["tensor"], {})[e["pid"]] = \
                args["seq"]
    shared = {n: v for n, v in by_name.items() if len(v) == 2}
    assert shared, by_name
    assert all(len(set(v.values())) == 1 for v in shared.values()), \
        shared
    assert any(n.startswith("grads_") for n in shared)

    # straggler attribution: the delayed rank is the top offender,
    # and its measured lateness is in the injected-delay ballpark
    assert report["offenders"][0][0] == 1, report
    assert report["per_rank"]["1"]["mean_delta_s"] > 0.03, report
    assert report["per_rank"]["1"]["mean_delta_s"] > \
        report["per_rank"]["0"]["mean_delta_s"]
    worst = {name: st for name, st in report["per_tensor"].items()
             if st["worst_rank"] == 1 and st["max_skew_s"] > 0.05}
    assert worst, report["per_tensor"]
