"""Journal crash-semantics + incident-analyzer tests: truncated-tail
repair, merge byte-determinism, MTTR decomposition on synthetic event
streams, the committed-step watermark across a simulated restart, the
committed chaos artifact's regeneration pin, and (behind the
multiproc probe) a live 2-rank chaos run whose incident report must
name the injected-fault rank."""

import json
import os
import subprocess
import sys
import time

import pytest

from horovod_tpu import journal

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARTIFACT_DIR = os.path.join(REPO, "benchmarks", "incident_chaos_r11")
ARTIFACT = os.path.join(REPO, "benchmarks", "INCIDENT_chaos_r11.json")


@pytest.fixture
def jdir(tmp_path, monkeypatch):
    """Armed journal in a tmp dir; module state restored after."""
    d = str(tmp_path / "journal")
    monkeypatch.setenv("HOROVOD_JOURNAL_DIR", d)
    yield d
    if journal._journal is not None:
        journal._journal.close()
    journal._journal = None
    journal._first_commit_pending = None


def _reset_module():
    if journal._journal is not None:
        journal._journal.close()
    journal._journal = None
    journal._first_commit_pending = None


class TestWriter:
    def test_roundtrip_and_meta(self, jdir):
        j = journal.configure("worker", 3)
        j.event("commit", step=7, epoch=2, durable=True)
        j.event("fault_fired", point="elastic.step", action="crash")
        events, dropped = journal.read_journal(j.path)
        assert dropped == 0
        assert [e["type"] for e in events] == [
            "journal_meta", "commit", "fault_fired"]
        meta = events[0]
        assert meta["schema"] == journal.SCHEMA
        assert meta["role"] == "worker" and meta["rank"] == 3
        assert "anchor_mono_ns" in meta and "anchor_unix" in meta
        c = events[1]
        assert c["step"] == 7 and c["durable"] is True
        # per-segment sequence + derived wall clock on every record
        assert [e["n"] for e in events] == [0, 1, 2]
        assert events[1]["t"] <= events[2]["t"]
        _reset_module()

    def test_truncated_tail_repair(self, jdir):
        """A SIGKILL mid-write leaves a torn last line; every intact
        record before it must survive the read."""
        j = journal.configure("worker", 0)
        for s in range(5):
            j.event("commit", step=s, epoch=1)
        _reset_module()
        path = os.path.join(jdir, "journal-rank0.jsonl")
        with open(path, "a") as f:
            f.write('{"type":"commit","step":99,"t":1.0,"ro')  # torn
        events, dropped = journal.read_journal(path)
        assert dropped == 1
        steps = [e["step"] for e in events if e["type"] == "commit"]
        assert steps == [0, 1, 2, 3, 4]
        # the torn step-99 record is GONE, not half-parsed
        assert 99 not in steps

    def test_rotation_keeps_two_segments(self, tmp_path, monkeypatch):
        d = str(tmp_path / "rot")
        monkeypatch.setenv("HOROVOD_JOURNAL_DIR", d)
        monkeypatch.setenv("HOROVOD_JOURNAL_ROTATE_MB", "1")
        j = journal.configure("worker", 0)
        j._rotate_bytes = 2048  # tiny cap for the test
        for s in range(64):
            j.event("commit", step=s, epoch=1)
        _reset_module()
        live = os.path.join(d, "journal-rank0.jsonl")
        rotated = live + ".1"
        assert os.path.exists(rotated), "no rotation happened"
        # both segments parse; the fresh one re-opens with a meta and
        # the merge reads rotated-then-live in write order
        ev_r, _ = journal.read_journal(rotated)
        ev_l, _ = journal.read_journal(live)
        assert ev_l[0]["type"] == "journal_meta"
        files = journal.find_journal_files(d)
        assert [os.path.basename(p) for p in files] == [
            "journal-rank0.jsonl.1", "journal-rank0.jsonl"]
        all_steps = [e["step"] for e in ev_r + ev_l
                     if e["type"] == "commit"]
        # two-segment bound by design: the oldest history is dropped,
        # but what remains is contiguous and ends at the newest step
        assert all_steps == list(range(all_steps[0], 64))
        assert len(all_steps) >= 16

    def test_disarmed_record_is_cheap_and_inert(self, tmp_path):
        _reset_module()
        assert not journal.enabled()
        t0 = time.perf_counter()
        for _ in range(100_000):
            journal.record("commit", step=1)
        dt = time.perf_counter() - t0
        # same contract as faults.fire disarmed: well under 1 us/call
        assert dt < 1.0, f"disarmed record too slow: {dt:.3f}s/100k"
        assert not list((tmp_path).glob("journal-*"))


class TestWatermark:
    def test_durable_commits_win(self, jdir):
        """A non-snapshot-writing rank running a step ahead must not
        inflate the watermark a restarted gang is held to."""
        os.makedirs(jdir, exist_ok=True)
        with open(os.path.join(jdir, "journal-rank0.jsonl"), "w") as f:
            for s in (1, 2, 3):
                f.write(json.dumps({"type": "commit", "step": s,
                                    "durable": True, "t": float(s),
                                    "role": "worker", "rank": 0,
                                    "n": s}) + "\n")
        with open(os.path.join(jdir, "journal-rank1.jsonl"), "w") as f:
            for s in (1, 2, 3, 4, 5):  # ahead, but nothing durable
                f.write(json.dumps({"type": "commit", "step": s,
                                    "t": float(s), "role": "worker",
                                    "rank": 1, "n": s}) + "\n")
        assert journal.watermark(jdir) == 3

    def test_plain_max_without_durable_flags(self, jdir):
        os.makedirs(jdir, exist_ok=True)
        with open(os.path.join(jdir, "journal-rank0.jsonl"), "w") as f:
            for s in (1, 2):
                f.write(json.dumps({"type": "commit", "step": s,
                                    "t": float(s), "role": "worker",
                                    "rank": 0, "n": s}) + "\n")
        assert journal.watermark(jdir) == 2
        assert journal.watermark(str(jdir) + "-nonexistent") == -1

    def test_note_sync_measures_loss_across_restart(self, jdir):
        """Simulated restart: incarnation 1 journals durable commits
        to step 5; the 'restarted' process resumes at 3 — note_sync
        must measure the 2-step loss and bump the SLO counter."""
        from horovod_tpu.metrics import REGISTRY
        j = journal.configure("worker", 0)
        for s in range(1, 6):
            j.event("commit", step=s, epoch=1, durable=True)
        # simulate the respawn: same dir, fresh journal module state
        _reset_module()
        journal.configure("worker", 0)
        before = REGISTRY.get(
            "hvd_committed_step_loss_total").value()
        journal.note_sync(3)
        after = REGISTRY.get("hvd_committed_step_loss_total").value()
        assert after - before == 2
        # the check itself is journaled, and a recovery is now
        # pending so the next commit closes first_commit
        events, _ = journal.read_journal(
            os.path.join(jdir, "journal-rank0.jsonl"))
        wm = [e for e in events if e["type"] == "watermark"]
        assert wm and wm[-1]["watermark"] == 5 \
            and wm[-1]["resumed"] == 3 and wm[-1]["loss"] == 2
        journal.note_commit(4, durable=True)
        events, _ = journal.read_journal(
            os.path.join(jdir, "journal-rank0.jsonl"))
        assert any(e["type"] == "first_commit" for e in events)
        _reset_module()

    def test_fresh_job_has_no_loss(self, jdir):
        from horovod_tpu.metrics import REGISTRY
        journal.configure("worker", 0)
        before = REGISTRY.get(
            "hvd_committed_step_loss_total").value()
        journal.note_sync(0)  # no prior commits anywhere
        assert REGISTRY.get(
            "hvd_committed_step_loss_total").value() == before
        _reset_module()


def _write_synthetic(dir_):
    """A synthetic crash recovery: rank 1 dies at t=10 inside an
    injected crash, detected at t=10.5, teardown to t=12, epoch 2
    published at t=12.25, respawned at t=12.5, both ranks synced by
    t=14, first epoch-2 commit at t=14.5."""
    os.makedirs(dir_, exist_ok=True)

    def w(name, recs):
        with open(os.path.join(dir_, name), "w") as f:
            for i, r in enumerate(recs):
                r.setdefault("n", i)
                f.write(json.dumps(r, sort_keys=True) + "\n")

    def ev(t, role, rank, type_, **kw):
        return dict(kw, t=t, role=role, rank=rank, type=type_)

    w("journal-driver.jsonl", [
        ev(0.0, "driver", -1, "journal_meta", schema=journal.SCHEMA,
           faults="elastic.step:crash:at=4,rank=1", faults_seed=7),
        ev(0.1, "driver", -1, "epoch_published", epoch=1, size=2,
           hosts={"0": "hostA", "1": "hostB"}),
        ev(0.2, "driver", -1, "spawn", exit_rank=0, host="hostA"),
        ev(0.2, "driver", -1, "spawn", exit_rank=1, host="hostB"),
        ev(0.3, "driver", -1, "respawn_done", epoch=1, ranks=2),
        ev(10.5, "driver", -1, "worker_exit", exit_rank=1,
           host="hostB", code=43),
        ev(10.5, "driver", -1, "detect", cause="crash", exit_rank=1,
           host="hostB", code=43, reset=1),
        ev(10.6, "driver", -1, "postmortem", exit_rank=1, code=43,
           file="postmortem-rank1.json", reason="crash", step=3),
        ev(10.7, "driver", -1, "blacklist", host="hostB",
           window_s=60.0, failures=1),
        ev(10.8, "driver", -1, "gang_restart_begin", reset=1,
           epoch=1),
        ev(12.0, "driver", -1, "teardown_done", reset=1),
        ev(12.25, "driver", -1, "epoch_published", epoch=2, size=2,
           hosts={"0": "hostA", "1": "hostA"}),
        ev(12.4, "driver", -1, "spawn", exit_rank=0, host="hostA"),
        ev(12.4, "driver", -1, "spawn", exit_rank=1, host="hostA"),
        ev(12.5, "driver", -1, "respawn_done", epoch=2, ranks=2),
        ev(20.0, "driver", -1, "job_done", code=0),
    ])
    w("journal-rank0.jsonl", [
        ev(0.5, "worker", 0, "journal_meta", schema=journal.SCHEMA),
        ev(0.6, "worker", 0, "init_done", epoch=1, world_size=2),
        ev(1.0, "worker", 0, "commit", step=1, epoch=1, durable=True),
        ev(5.0, "worker", 0, "commit", step=2, epoch=1, durable=True),
        ev(9.0, "worker", 0, "commit", step=3, epoch=1, durable=True),
        ev(13.0, "worker", 0, "init_done", epoch=2, world_size=2),
        ev(13.5, "worker", 0, "snapshot_loaded", step=3),
        ev(14.0, "worker", 0, "sync_done", step=3, epoch=2),
        ev(14.0, "worker", 0, "watermark", watermark=3, resumed=3,
           loss=0),
        ev(14.5, "worker", 0, "commit", step=4, epoch=2,
           durable=True),
    ])
    w("journal-rank1.jsonl", [
        ev(0.5, "worker", 1, "journal_meta", schema=journal.SCHEMA),
        ev(0.6, "worker", 1, "init_done", epoch=1, world_size=2),
        ev(1.0, "worker", 1, "commit", step=1, epoch=1),
        ev(5.0, "worker", 1, "commit", step=2, epoch=1),
        ev(9.0, "worker", 1, "commit", step=3, epoch=1),
        ev(10.0, "worker", 1, "fault_fired", point="elastic.step",
           action="crash", hit=4),
        ev(13.1, "worker", 1, "init_done", epoch=2, world_size=2),
        ev(13.9, "worker", 1, "sync_done", step=3, epoch=2),
        ev(14.6, "worker", 1, "commit", step=4, epoch=2),
    ])


class TestIncidentAnalyzer:
    def test_mttr_decomposition_synthetic(self, tmp_path):
        d = str(tmp_path / "synth")
        _write_synthetic(d)
        report = journal.incident_report(d)
        assert report["schema"] == journal.REPORT_SCHEMA
        assert report["summary"]["recoveries"] == 1
        (rec,) = report["recoveries"]
        assert rec["complete"] is True
        # cause attribution: rank, host, exit code, injected seam
        assert rec["cause"] == {
            "kind": "crash", "rank": 1, "host": "hostB",
            "exit_code": 43, "seam": "elastic.step:crash"}
        # phase decomposition against the synthetic timestamps
        # (t_fail = rank 1's last breath, the fault_fired at t=10)
        ph = rec["phases"]
        assert ph["detect"] == pytest.approx(0.5)
        assert ph["teardown"] == pytest.approx(1.5)
        assert ph["rendezvous"] == pytest.approx(0.25)
        assert ph["respawn"] == pytest.approx(0.25)
        assert ph["restore"] == pytest.approx(1.5)   # -> t=14.0
        assert ph["first_commit"] == pytest.approx(0.5)
        assert rec["mttr_s"] == pytest.approx(4.5)
        # step accounting: durable watermark 3, resumed 3, loss 0
        assert rec["steps"] == {"watermark": 3, "resumed": 3,
                                "committed_step_loss": 0}
        assert rec["postmortems"] == [
            {"rank": 1, "file": "postmortem-rank1.json",
             "reason": "crash", "step": 3}]
        assert rec["blacklisted"] == [
            {"host": "hostB", "window_s": 60.0, "failures": 1}]
        # epochs: 1 = start, 2 = recovery
        assert [(e["epoch"], e["kind"]) for e in report["epochs"]] \
            == [(1, "start"), (2, "recovery")]
        assert report["source"]["faults"] == [
            {"spec": "elastic.step:crash:at=4,rank=1", "seed": 7}]

    def test_merge_byte_determinism_golden(self, tmp_path):
        """Identical journal bytes -> identical report bytes, across
        repeated runs and an unrelated-cwd invocation."""
        d = str(tmp_path / "synth")
        _write_synthetic(d)
        p1, _ = journal.write_incident_report(
            d, out=str(tmp_path / "r1.json"))
        p2, _ = journal.write_incident_report(
            d, out=str(tmp_path / "r2.json"))
        b1 = open(p1, "rb").read()
        assert b1 == open(p2, "rb").read()
        # no environment-dependent content
        raw = b1.decode()
        assert str(tmp_path) not in raw
        assert "unix_time" not in raw

    def test_hung_worker_cause(self, tmp_path):
        """A liveness-detector kill is attributed as 'hung' with the
        stale heartbeat age, not as a crash with exit -9."""
        d = str(tmp_path / "hung")
        os.makedirs(d)

        def line(**kw):
            return json.dumps(kw, sort_keys=True) + "\n"

        with open(os.path.join(d, "journal-driver.jsonl"), "w") as f:
            f.write(line(t=1.0, n=0, role="driver", rank=-1,
                         type="epoch_published", epoch=1, size=1,
                         hosts={"0": "h"}))
            f.write(line(t=14.0, n=1, role="driver", rank=-1,
                         type="hung_worker", exit_rank=0, host="h",
                         age_s=4.0, timeout_s=4.0))
            f.write(line(t=14.1, n=2, role="driver", rank=-1,
                         type="detect", cause="hung", exit_rank=0,
                         host="h", code=-9, age_s=4.0, reset=1))
            f.write(line(t=14.2, n=3, role="driver", rank=-1,
                         type="gang_restart_begin", reset=1))
            f.write(line(t=15.0, n=4, role="driver", rank=-1,
                         type="teardown_done", reset=1))
            f.write(line(t=15.1, n=5, role="driver", rank=-1,
                         type="epoch_published", epoch=2, size=1,
                         hosts={"0": "h"}))
            f.write(line(t=15.2, n=6, role="driver", rank=-1,
                         type="respawn_done", epoch=2, ranks=1))
        with open(os.path.join(d, "journal-rank0.jsonl"), "w") as f:
            f.write(line(t=2.0, n=0, role="worker", rank=0,
                         type="commit", step=1, epoch=1,
                         durable=True))
            f.write(line(t=10.0, n=1, role="worker", rank=0,
                         type="fault_fired", point="elastic.step",
                         action="hang", hit=2))
            f.write(line(t=16.0, n=2, role="worker", rank=0,
                         type="sync_done", step=1, epoch=2))
            f.write(line(t=16.5, n=3, role="worker", rank=0,
                         type="commit", step=2, epoch=2,
                         durable=True))
        report = journal.incident_report(d)
        (rec,) = report["recoveries"]
        assert rec["cause"]["kind"] == "hung"
        assert rec["cause"]["heartbeat_stale_age_s"] == 4.0
        assert rec["cause"]["seam"] == "elastic.step:hang"
        # t_fail is the hang's firing; detect spans hang -> verdict
        assert rec["phases"]["detect"] == pytest.approx(4.1)
        assert rec["steps"]["committed_step_loss"] == 0

    def test_render_is_stringy(self, tmp_path):
        d = str(tmp_path / "synth")
        _write_synthetic(d)
        text = journal.render_incident_report(
            journal.incident_report(d))
        assert "crash on hostB" in text
        assert "teardown" in text and "first_commit" in text
        assert "watermark 3 -> resumed 3" in text


class TestCommittedArtifact:
    """The acceptance pin: the committed seeded-chaos artifact holds
    >= 2 recoveries (crash + hung) with complete decompositions and
    zero committed-step loss, and regenerates byte-identically from
    the committed journals."""

    def test_regenerates_byte_identically(self, tmp_path):
        out = str(tmp_path / "regen.json")
        journal.write_incident_report(ARTIFACT_DIR, out=out)
        assert open(out, "rb").read() == open(ARTIFACT, "rb").read()
        # the in-dir copy is the same bytes too
        assert open(os.path.join(
            ARTIFACT_DIR, "incident_report.json"), "rb").read() == \
            open(ARTIFACT, "rb").read()

    def test_acceptance_invariants(self):
        report = json.load(open(ARTIFACT))
        s = report["summary"]
        assert s["recoveries"] >= 2
        assert s["by_cause"].get("crash", 0) >= 1
        assert s["by_cause"].get("hung", 0) >= 1
        assert s["complete_decompositions"] == s["recoveries"]
        assert s["committed_step_loss_total"] == 0
        for rec in report["recoveries"]:
            for ph in ("detect", "teardown", "rendezvous", "respawn",
                       "restore", "first_commit"):
                assert rec["phases"][ph] is not None, (ph, rec)
            assert rec["cause"]["host"] and \
                rec["cause"]["rank"] is not None
            assert rec["cause"]["seam"] is not None
            assert rec["steps"]["committed_step_loss"] == 0
        # the fault schedule that produced it is carried in-band
        assert report["source"]["faults"][0]["seed"] == 11
        assert "elastic.step:crash" in \
            report["source"]["faults"][0]["spec"]


class TestEventSchemas:
    """The declared EVENT_SCHEMAS registry (hvdlint HVD008's source
    of truth): internal consistency, the strict-mode runtime
    companion, validation of every committed journal artifact, and
    the generated user_guide table's lockstep pin."""

    def test_registry_shape(self):
        names = [s.name for s in journal.EVENT_SCHEMAS]
        assert len(names) == len(set(names)), "duplicate event decls"
        for s in journal.EVENT_SCHEMAS:
            assert s.name and s.name == s.name.lower()
            assert s.writer and s.doc
            overlap = (set(s.required) | set(s.optional)) \
                & journal.BASE_FIELDS
            assert not overlap, (s.name, overlap)
            assert not set(s.required) & set(s.optional), s.name

    def test_critical_events_derived_from_registry(self):
        assert journal.CRITICAL_EVENTS <= journal.EVENT_NAMES
        assert journal.CRITICAL_EVENTS == {
            s.name for s in journal.EVENT_SCHEMAS if s.critical}
        # the load-bearing recovery edges stay critical
        for name in ("commit", "detect", "fault_fired",
                     "epoch_published", "first_commit"):
            assert name in journal.CRITICAL_EVENTS, name

    def test_schema_problems_round_trip(self):
        ok = journal.schema_problems(
            "commit", {"epoch": 2, "durable": True, "step": 7})
        assert ok == []
        assert any("undeclared event" in p for p in
                   journal.schema_problems("comitted", {"step": 1}))
        assert any("missing required" in p for p in
                   journal.schema_problems("commit", {"step": 1}))
        assert any("undeclared field" in p for p in
                   journal.schema_problems(
                       "commit", {"epoch": 1, "stepp": 7}))

    def test_strict_mode_warns_once_per_type(self, jdir,
                                             monkeypatch):
        monkeypatch.setenv("HOROVOD_JOURNAL_STRICT", "1")
        seen = []
        real = journal.hlog.warning
        monkeypatch.setattr(
            journal.hlog, "warning",
            lambda msg, *a: seen.append(msg % a if a else msg))
        j = journal.configure("worker", 0)
        j.event("fx_not_a_real_event", x=1)  # never raises
        j.event("fx_not_a_real_event", x=2)
        j.event("commit", epoch=1, durable=True, step=3)
        monkeypatch.setattr(journal.hlog, "warning", real)
        warns = [m for m in seen
                 if "HOROVOD_JOURNAL_STRICT" in m]
        assert len(warns) == 1  # deduped per event type
        assert "fx_not_a_real_event" in warns[0]
        # the record is still written — strict mode observes, never
        # drops
        lines = open(os.path.join(jdir,
                                  "journal-rank0.jsonl")).read()
        assert '"fx_not_a_real_event"' in lines

    def test_committed_artifacts_validate_against_registry(self):
        """r11 chaos, r14 preempt, r16 serving, r18 decode journals —
        every record of every committed artifact conforms to the
        registry UNCHANGED (the registry documents history, it does
        not rewrite it)."""
        import glob as _glob
        dirs = [os.path.join(REPO, "benchmarks", d) for d in
                ("incident_chaos_r11", "incident_preempt_r14",
                 "serving_trace_r16", "serving_decode_r18")]
        checked = 0
        problems = []
        for d in dirs:
            assert os.path.isdir(d), d
            for seg in sorted(_glob.glob(
                    os.path.join(d, "journal-*.jsonl*"))):
                for line in open(seg):
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue  # torn tail: the loader's repair job
                    checked += 1
                    for p in journal.validate_event(rec):
                        problems.append((os.path.basename(seg), p))
        assert checked > 100  # the artifacts are substantial
        assert problems == [], problems[:10]

    def test_user_guide_table_is_generated_form(self):
        """The docs table between the hvdlint markers must be exactly
        event_schema_table_md()'s output — HVD008's drift leg assumes
        one source of truth."""
        guide = open(os.path.join(REPO, "docs",
                                  "user_guide.md")).read()
        begin = "<!-- hvdlint:event-schema-table:begin -->"
        end = "<!-- hvdlint:event-schema-table:end -->"
        assert begin in guide and end in guide
        between = guide.split(begin, 1)[1].split(end, 1)[0]
        assert between.strip("\n") == \
            journal.event_schema_table_md().strip("\n")


# -- live 2-rank chaos run (multiproc-gated like the other chaos
#    integration tests; the control-plane-only worker would run on
#    this jaxlib, but the probe keeps the gate uniform) --------------

_NO_MULTIPROC = ("this jaxlib's CPU backend cannot run cross-process "
                 "collectives (affects every multiprocess "
                 "integration test)")


@pytest.fixture(scope="module")
def multiproc_backend():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         sys.executable, "-c",
         "import jax.numpy as jnp; import horovod_tpu as hvd; "
         "hvd.init(); hvd.allreduce(jnp.ones(4), name='probe'); "
         "hvd.shutdown()"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=180)
    if "Multiprocess computations aren't implemented" in (
            r.stdout + r.stderr):
        pytest.skip(_NO_MULTIPROC)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr


@pytest.mark.integration
def test_two_rank_chaos_names_injected_rank(tmp_path,
                                            multiproc_backend):
    """Live seeded soak (same shape as the committed artifact's):
    the incident report must attribute the crash to the rank the
    fault spec targeted, with a complete decomposition and zero
    committed-step loss."""
    jdir = tmp_path / "journal"
    jdir.mkdir()
    script = tmp_path / "discover.sh"
    script.write_text("#!/bin/sh\necho localhost:2\n")
    script.chmod(0o755)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["ELASTIC_TEST_LOG"] = str(tmp_path / "progress")
    env["ELASTIC_TEST_STEPS"] = "10"
    env["ELASTIC_TEST_SLEEP"] = "0.15"
    env["HOROVOD_JOURNAL_DIR"] = str(jdir)
    env["HOROVOD_FAULTS"] = (
        f"elastic.step:crash:at=3,rank=1,"
        f"once={tmp_path / 'crash.latch'}")
    env["HOROVOD_FAULTS_SEED"] = "7"
    env["HOROVOD_ELASTIC_TEARDOWN_GRACE"] = "3"
    p = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner",
         "--host-discovery-script", str(script),
         "--min-num-proc", "2",
         "--host-change-detection-interval", "0.5",
         "--reset-limit", "3",
         sys.executable,
         os.path.join("tests", "journal_chaos_worker.py")],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    out, _ = p.communicate(timeout=420)
    assert p.returncode == 0, out
    report = journal.incident_report(str(jdir))
    assert report["summary"]["recoveries"] >= 1
    rec = report["recoveries"][0]
    assert rec["cause"]["rank"] == 1, rec
    assert rec["cause"]["kind"] == "crash"
    assert rec["cause"]["seam"] == "elastic.step:crash"
    assert rec["complete"], rec
    assert rec["steps"]["committed_step_loss"] == 0
