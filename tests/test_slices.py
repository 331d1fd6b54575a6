"""Slice-atomic elastic membership tests: discovery slice-column
parsing, the -H @slice suffix, SliceTracker rump parking / forget
window, driver-level whole-slice admission + blacklist escalation +
contiguous-rank invariants, the host.preempt SIGTERM->SIGKILL seam,
the committed preemption-storm artifact's regeneration pin, and
(nightly) the live whole-slice preemption-storm soak behind
benchmarks/INCIDENT_preempt_r14.json."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from horovod_tpu import faults, journal  # noqa: E402
from horovod_tpu.runner.elastic import driver as driver_mod  # noqa: E402
from horovod_tpu.runner.elastic.discovery import (  # noqa: E402
    HostDiscovery, HostDiscoveryScript, hosts_key,
    parse_discovery_line)
from horovod_tpu.runner.elastic.driver import (  # noqa: E402
    ElasticDriver, _Slot)
from horovod_tpu.runner.elastic.slices import SliceTracker  # noqa: E402
from horovod_tpu.runner.hosts import (  # noqa: E402
    HostSlots, RankInfo, assign_ranks, parse_hosts, per_chip_env)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARTIFACT_DIR = os.path.join(REPO, "benchmarks", "incident_preempt_r14")
ARTIFACT = os.path.join(REPO, "benchmarks", "INCIDENT_preempt_r14.json")


# -- discovery parsing ----------------------------------------------

class TestDiscoveryParsing:
    def test_plain_lines_keep_legacy_contract(self):
        assert parse_discovery_line("h1:4") == HostSlots("h1", 4)
        assert parse_discovery_line("h1") == HostSlots("h1", 1)
        assert parse_discovery_line("h1").slice_id is None

    def test_slice_column(self):
        h = parse_discovery_line("h1:4 slice=pod0")
        assert h == HostSlots("h1", 4, "pod0")
        assert parse_discovery_line("h2 slice=pod1").slots == 1

    def test_unknown_attribute_fails_loud(self):
        with pytest.raises(ValueError):
            parse_discovery_line("h1:4 zone=us-central1")
        with pytest.raises(ValueError):
            parse_discovery_line("h1:4 slice")

    def test_empty_slice_id_rejected(self):
        with pytest.raises(ValueError):
            parse_discovery_line("h1:4 slice=")

    def test_hosts_key_shapes(self):
        # slice-less lists keep the historical {host: slots} shape so
        # single-slice jobs' membership-change detection is unchanged
        plain = [HostSlots("h1", 4), HostSlots("h2", 4)]
        assert hosts_key(plain) == {"h1": 4, "h2": 4}
        mixed = [HostSlots("h1", 4, "pod0"), HostSlots("h2", 4)]
        key = hosts_key(mixed)
        assert key["h1"] == (4, "pod0") and key["h2"] == 4

    def test_script_end_to_end(self, tmp_path):
        script = tmp_path / "discover.sh"
        script.write_text("#!/bin/sh\n"
                          "echo 'h1:4 slice=pod0'\n"
                          "echo 'h2:4 slice=pod0'\n"
                          "echo h3:2\n")
        script.chmod(0o755)
        hosts = HostDiscoveryScript(
            str(script)).find_available_hosts_and_slots()
        assert hosts == [HostSlots("h1", 4, "pod0"),
                         HostSlots("h2", 4, "pod0"),
                         HostSlots("h3", 2)]


class TestParseHostsSlices:
    def test_at_suffix(self):
        hosts = parse_hosts("h1:4@pod0,h2:4@pod0,h3:2@pod1", 10)
        assert [h.slice_id for h in hosts] == ["pod0", "pod0", "pod1"]

    def test_empty_slice_suffix_rejected(self):
        with pytest.raises(ValueError):
            parse_hosts("h1:4@", 4)

    def test_rank_env_legacy_without_slice(self):
        infos = assign_ranks([HostSlots("h1", 2)], 2)
        env = infos[1].env()
        assert set(env) == {
            "HOROVOD_RANK", "HOROVOD_SIZE", "HOROVOD_LOCAL_RANK",
            "HOROVOD_LOCAL_SIZE", "HOROVOD_CROSS_RANK",
            "HOROVOD_CROSS_SIZE"}

    def test_rank_env_carries_slice_id(self):
        infos = assign_ranks([HostSlots("h1", 2, "pod0")], 2)
        assert infos[0].env()["HOROVOD_ELASTIC_SLICE_ID"] == "pod0"

    def test_slice_ranks_contiguous(self):
        hosts = parse_hosts("h1:4@pod0,h2:4@pod0,h3:4@pod1,h4:4@pod1",
                            16)
        infos = assign_ranks(hosts, 16)
        by_slice = {}
        for i in infos:
            by_slice.setdefault(i.slice_id, []).append(i.rank)
        for sid, ranks in by_slice.items():
            assert ranks == list(range(min(ranks), max(ranks) + 1)), \
                (sid, ranks)

    def test_per_chip_single_implicit_slice_unchanged(self):
        infos = assign_ranks([HostSlots("h1", 2), HostSlots("h2", 2)],
                             4)
        env = per_chip_env(infos[2], infos)
        # the whole job is one mesh: every slot in the address list,
        # task id == rank, exactly as before slices existed
        assert env["TPU_PROCESS_ADDRESSES"] == \
            "h1:8476,h1:8477,h2:8476,h2:8477"
        assert env["CLOUD_TPU_TASK_ID"] == "2"

    def test_per_chip_mesh_is_per_slice(self):
        hosts = [HostSlots("h1", 2, "pod0"), HostSlots("h2", 2, "pod1")]
        infos = assign_ranks(hosts, 4)
        env = per_chip_env(infos[2], infos)  # rank 2 = h2 slot 0
        assert env["TPU_PROCESS_ADDRESSES"] == "h2:8476,h2:8477"
        # slice-relative task id: pod1's first process is task 0
        assert env["CLOUD_TPU_TASK_ID"] == "0"


# -- SliceTracker ---------------------------------------------------

P0 = [HostSlots("a1", 2, "p0"), HostSlots("a2", 2, "p0")]
P1 = [HostSlots("b1", 2, "p1"), HostSlots("b2", 2, "p1")]


class TestSliceTracker:
    def test_rump_parked_until_complete(self):
        t = SliceTracker()
        t.observe(P0)
        admitted, rumps, newly = t.admit(P0[:1], now=0.0)
        assert admitted == [] and rumps == P0[:1] and newly == set()
        admitted, rumps, newly = t.admit(P0, now=1.0)
        assert admitted == P0 and rumps == [] and newly == {"p0"}

    def test_sliceless_always_admitted(self):
        t = SliceTracker()
        plain = [HostSlots("h1", 4)]
        t.observe(plain)
        admitted, rumps, _ = t.admit(plain, now=0.0)
        assert admitted == plain and rumps == []

    def test_slice_major_input_order(self):
        t = SliceTracker()
        interleaved = [P0[0], P1[0], P0[1], P1[1]]
        t.observe(interleaved)
        admitted, _, _ = t.admit(interleaved, now=0.0)
        assert [h.slice_id for h in admitted] == \
            ["p0", "p0", "p1", "p1"]
        assert [h.host for h in admitted] == ["a1", "a2", "b1", "b2"]

    def test_forget_window_rebaselines(self):
        t = SliceTracker(forget_seconds=5.0)
        t.observe(P0)
        admitted, rumps, _ = t.admit(P0[:1], now=100.0)
        assert admitted == [] and rumps == P0[:1]
        # still inside the window: parked
        admitted, _, _ = t.admit(P0[:1], now=104.0)
        assert admitted == []
        # past the window: reconfiguration, not outage
        admitted, _, newly = t.admit(P0[:1], now=105.5)
        assert admitted == P0[:1] and newly == {"p0"}
        assert t.members("p0") == {"a1"}

    def test_rehomed_host_leaves_old_slice(self):
        t = SliceTracker()
        t.observe(P0)
        moved = [P0[0], HostSlots("a2", 2, "p9")]
        t.observe(moved)
        assert t.members("p0") == {"a1"}
        assert t.slice_of("a2") == "p9"
        admitted, rumps, _ = t.admit(moved, now=0.0)
        assert admitted == moved and rumps == []

    def test_atomic_off_admits_rumps(self):
        t = SliceTracker(atomic=False)
        t.observe(P0)
        admitted, rumps, _ = t.admit(P0[:1], now=0.0)
        assert admitted == P0[:1] and rumps == []


# -- driver-level membership ----------------------------------------

class ListDiscovery(HostDiscovery):
    """In-memory discovery: tests mutate .hosts between polls."""

    def __init__(self, hosts):
        self.hosts = list(hosts)

    def find_available_hosts_and_slots(self):
        return list(self.hosts)


@pytest.fixture
def mkdriver():
    """ElasticDriver factory; rendezvous servers stopped and journal
    module state restored after the test."""
    made = []

    def make(hosts, **kw):
        disc = ListDiscovery(hosts)
        kw.setdefault("env", {})
        d = ElasticDriver([sys.executable, "-c", "pass"], disc, **kw)
        made.append(d)
        return d, disc

    yield make
    for d in made:
        d.rendezvous.stop()
    if journal._journal is not None:
        journal._journal.close()
    journal._journal = None
    journal._first_commit_pending = None


POD0 = [HostSlots(f"h{i}", 1, "pod0") for i in range(4)]
POD1 = [HostSlots("x1", 1, "pod1"), HostSlots("x2", 1, "pod1")]


class TestDriverMembership:
    def test_rump_slice_is_never_assigned_ranks(self, mkdriver):
        """Acceptance pin: a 3-of-4-host slice must not hold ranks."""
        drv, disc = mkdriver(POD0 + POD1)
        drv._discover()  # learn full membership
        disc.hosts = [h for h in POD0 if h.host != "h3"] + POD1
        admitted = drv._discover()
        assert all(h.slice_id == "pod1" for h in admitted)
        infos, table = drv._assignments(admitted)
        assert sorted(i.host for i in infos) == ["x1", "x2"]
        assert all(i.slice_id == "pod1" for i in infos)
        assert not any(k[0].startswith("h") for k in table)

    def test_whole_slice_blacklist_on_member_failure(self, mkdriver):
        drv, _ = mkdriver(POD0 + POD1)
        drv._discover()
        drv._blacklist_failed({"h0": "crash"})
        now = time.time()
        assert set(drv.blacklist) == {"h0", "h1", "h2", "h3"}
        for until in drv.blacklist.values():
            assert 0 < until - now <= drv.blacklist_window + 1

    def test_escalation_window_keyed_by_slice(self, mkdriver):
        """The window doubles even when a DIFFERENT member fails the
        second time: the slice, not the host, is the flapping unit."""
        drv, _ = mkdriver(POD0 + POD1)
        drv._discover()
        drv._blacklist_failed({"h0": "crash"})
        drv.blacklist = {}  # simulate window expiry
        drv._blacklist_failed({"h2": "hung"})
        now = time.time()
        for until in drv.blacklist.values():
            assert until - now > drv.blacklist_window * 1.5
        assert drv._slice_failures["pod0"] == 2

    def test_min_np_guard_refuses_slice_eviction(self, mkdriver):
        drv, _ = mkdriver(list(POD0), min_np=3)
        drv._discover()
        drv._blacklist_failed({"h1": "crash"})
        assert drv.blacklist == {}

    def test_contiguous_ranks_from_interleaved_discovery(self,
                                                         mkdriver):
        interleaved = [POD0[0], POD1[0], POD0[1], POD1[1]]
        drv, _ = mkdriver(interleaved)
        admitted = drv._discover()
        infos, _ = drv._assignments(admitted)
        by_slice = {}
        for i in infos:
            by_slice.setdefault(i.slice_id, []).append(i.rank)
        assert by_slice == {"pod0": [0, 1], "pod1": [2, 3]}

    def test_max_np_admits_whole_slices_only(self, mkdriver):
        pods = [HostSlots("a1", 2, "p0"), HostSlots("b1", 2, "p1")]
        drv, _ = mkdriver(pods, max_np=3)
        admitted = drv._discover()
        assert [h.slice_id for h in admitted] == ["p0"]
        # slice-less lists keep the legacy truncate-at-np behavior
        plain = [HostSlots("h1", 2), HostSlots("h2", 2)]
        drv2, _ = mkdriver(plain, max_np=3)
        assert drv2._discover() == plain

    def test_single_slice_epoch_table_unchanged(self, mkdriver,
                                                monkeypatch):
        """Acceptance pin: a slice-less job's published assignment
        table is byte-for-byte the pre-slice contract — exactly the
        legacy key set, no slice variable anywhere."""
        ports = iter([43211, 43212])
        monkeypatch.setattr(driver_mod, "free_port",
                            lambda: next(ports))
        drv, _ = mkdriver([HostSlots("localhost", 2)], min_np=2)
        hosts = drv._discover()
        infos, table = drv._publish_epoch(hosts)
        rdv = f"localhost:{drv.rendezvous.port}"
        expected = {}
        for lr in (0, 1):
            expected[("localhost", lr)] = {
                "HOROVOD_RANK": str(lr),
                "HOROVOD_SIZE": "2",
                "HOROVOD_LOCAL_RANK": str(lr),
                "HOROVOD_LOCAL_SIZE": "2",
                "HOROVOD_CROSS_RANK": "0",
                "HOROVOD_CROSS_SIZE": "1",
                "HOROVOD_COORDINATOR_ADDR": "localhost:43211",
                "HOROVOD_CONTROL_ADDR": "localhost:43212",
                "HOROVOD_CONTROL_HOSTS": "localhost,localhost",
                "HOROVOD_HOSTNAME": "localhost",
                "HOROVOD_RENDEZVOUS_ADDR": rdv,
                "HOROVOD_ELASTIC_EPOCH": "1",
            }
        assert table == expected

    def test_journal_slice_events(self, mkdriver, tmp_path):
        jdir = str(tmp_path / "journal")
        drv, _ = mkdriver(POD0 + POD1,
                          env={"HOROVOD_JOURNAL_DIR": jdir})
        drv._discover()
        drv._blacklist_failed({"h0": "preempt"})
        journal._journal.close()
        journal._journal = None
        events, _ = journal.read_journal(
            os.path.join(jdir, "journal-driver.jsonl"))
        by_type = {}
        for e in events:
            by_type.setdefault(e["type"], []).append(e)
        admitted = {e["slice"] for e in by_type["slice_admitted"]}
        assert admitted == {"pod0", "pod1"}
        lost = by_type["slice_lost"]
        assert len(lost) == 1 and lost[0]["slice"] == "pod0"
        assert lost[0]["cause"] == "preempt"
        assert lost[0]["hosts"] == ["h0", "h1", "h2", "h3"]
        bl = [e for e in by_type["blacklist"]
              if e.get("slice") == "pod0"]
        assert len(bl) == 4


# -- host.preempt seam ----------------------------------------------

_IGNORE_TERM = ("import signal, time; "
                "signal.signal(signal.SIGTERM, signal.SIG_IGN); "
                "time.sleep(30)")
_OBEY_TERM = "import time; time.sleep(30)"


def _add_slot(drv, host, local_rank, rank, code):
    p = subprocess.Popen([sys.executable, "-c", code])
    info = RankInfo(rank=rank, size=2, local_rank=local_rank,
                    local_size=1, cross_rank=rank, cross_size=2,
                    host=host)
    drv.slots[(host, local_rank)] = _Slot(info, p)
    return p


class TestPreemptSeam:
    def test_host_selector_targets_only_tagged_host(self, mkdriver):
        drv, _ = mkdriver([HostSlots("hA", 1), HostSlots("hB", 1)])
        p_a = p_b = None
        try:
            p_a = _add_slot(drv, "hA", 0, 0, _OBEY_TERM)
            p_b = _add_slot(drv, "hB", 0, 1, _OBEY_TERM)
            faults.configure("host.preempt:preempt:at=1,host=hB", 0)
            drv._check_preempt_faults()
            assert ("hB", 0) in drv._preempt_pending
            assert ("hA", 0) not in drv._preempt_pending
            assert p_b.wait(timeout=10) == -signal.SIGTERM
            assert p_a.poll() is None
        finally:
            faults.configure(None)
            for p in (p_a, p_b):
                if p is not None and p.poll() is None:
                    p.kill()

    def test_sigterm_then_sigkill_after_grace(self, mkdriver):
        """XLA's preemption notifier catches SIGTERM without exiting;
        the reaper must model the VM poweroff with SIGKILL."""
        drv, _ = mkdriver([HostSlots("hA", 1)])
        drv.preempt_grace = 0.3
        p = None
        try:
            p = _add_slot(drv, "hA", 0, 0, _IGNORE_TERM)
            # let the child install its TERM handler first
            time.sleep(1.0)
            faults.configure("host.preempt:preempt:at=1,host=hA", 0)
            drv._check_preempt_faults()
            assert ("hA", 0) in drv._preempt_pending
            time.sleep(0.1)
            assert p.poll() is None  # survived the SIGTERM storm
            deadline = time.time() + 10
            while p.poll() is None and time.time() < deadline:
                drv._reap_preempted()
                time.sleep(0.05)
            assert p.poll() == -signal.SIGKILL
        finally:
            faults.configure(None)
            if p is not None and p.poll() is None:
                p.kill()

    def test_reaper_drops_stale_keys(self, mkdriver):
        drv, _ = mkdriver([HostSlots("hA", 1)])
        drv._preempt_pending[("hA", 0)] = time.time() - 1
        drv._reap_preempted()  # slot gone: entry must not linger
        assert drv._preempt_pending == {}

    def test_host_param_rejected_at_untagged_point(self):
        with pytest.raises(ValueError):
            faults.parse("wire.send:delay:ms=5,host=h1")

    def test_gang_restart_clears_pending(self, mkdriver):
        drv, _ = mkdriver([HostSlots("localhost", 1)])
        drv._preempt_pending[("localhost", 0)] = time.time() + 99
        drv._hung_pending[("localhost", 0)] = 1.0
        drv._gang_restart()
        assert drv._preempt_pending == {}
        assert drv._hung_pending == {}


# -- live preemption-storm soak -------------------------------------

def _storm_env(tmp_path, jdir):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["ELASTIC_TEST_LOG"] = os.path.join(str(tmp_path), "progress")
    env["HOROVOD_JOURNAL_DIR"] = str(jdir)
    env["HOROVOD_FAULTS_SEED"] = "14"
    env["HOROVOD_ELASTIC_PREEMPT_GRACE"] = "1"
    env["HOROVOD_ELASTIC_TEARDOWN_GRACE"] = "1"
    return env


def _driver_events(jdir):
    events, _ = journal.read_journal(
        os.path.join(str(jdir), "journal-driver.jsonl"))
    return events


@pytest.mark.integration
def test_preempt_recovery_is_slice_atomic(tmp_path,
                                          multiproc_data_plane):
    """Tier-1 representative: preempt one host of a two-slice world;
    the journal must show the whole slice lost (cause preempt) and
    the job must still complete after re-admission."""
    jdir = tmp_path / "journal"
    jdir.mkdir()
    script = tmp_path / "discover.sh"
    script.write_text("#!/bin/sh\n"
                      "echo '127.0.0.1:1 slice=a'\n"
                      "echo '127.0.0.2:1 slice=b'\n")
    script.chmod(0o755)
    env = _storm_env(tmp_path, jdir)
    env["ELASTIC_TEST_STEPS"] = "30"
    env["ELASTIC_TEST_SLEEP"] = "0.2"
    env["HOROVOD_ELASTIC_BLACKLIST_WINDOW"] = "6"
    env["HOROVOD_FAULTS"] = "host.preempt:preempt:at=40,host=127.0.0.1"
    p = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner",
         "--host-discovery-script", str(script),
         "--min-num-proc", "1",
         "--host-change-detection-interval", "0.5",
         sys.executable, os.path.join("tests", "elastic_worker.py")],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    out, _ = p.communicate(timeout=420)
    assert p.returncode == 0, out
    events = _driver_events(jdir)
    lost = [e for e in events if e["type"] == "slice_lost"]
    assert lost and lost[0]["slice"] == "a" and \
        lost[0]["cause"] == "preempt", lost
    detects = [e for e in events if e["type"] == "detect"]
    assert any(e["cause"] == "preempt" and e.get("slice") == "a"
               for e in detects), detects
    admitted = [e for e in events if e["type"] == "slice_admitted"
                and e["slice"] == "a"]
    assert len(admitted) >= 2, admitted  # initial + re-admission


def _run_preempt_storm(workdir, steps=150, sleep=0.25,
                       storm1=150, storm2=380):
    """The r14 soak: a 4-host / 2-slice world (loopback aliases stand
    in for hosts); both hosts of slice a are preemption-stormed at
    the same driver tick mid-run, then slice b after a has been
    re-admitted. Control-plane-only worker (journal_chaos_worker.py)
    so the soak runs on jaxlib builds without multiprocess
    collectives — the container the committed artifact is generated
    in. Returns (rc, out, jdir)."""
    jdir = os.path.join(workdir, "journal")
    os.makedirs(jdir, exist_ok=True)
    script = os.path.join(workdir, "discover.sh")
    with open(script, "w") as f:
        f.write("#!/bin/sh\n"
                "echo '127.0.0.1:1 slice=a'\n"
                "echo '127.0.0.2:1 slice=a'\n"
                "echo '127.0.0.3:1 slice=b'\n"
                "echo '127.0.0.4:1 slice=b'\n")
    os.chmod(script, 0o755)
    env = _storm_env(workdir, jdir)
    env["ELASTIC_TEST_LOG"] = os.path.join(workdir, "progress")
    env["ELASTIC_TEST_STEPS"] = str(steps)
    env["ELASTIC_TEST_SLEEP"] = str(sleep)
    env["HOROVOD_ELASTIC_BLACKLIST_WINDOW"] = "10"
    # Both hosts of a slice storm at the same per-host tick, so the
    # slice dies as a unit; slice b's storm lands after slice a's
    # blacklist window has expired and a is back (otherwise evicting
    # b would be refused by the min_np capacity guard).
    env["HOROVOD_FAULTS"] = ";".join([
        f"host.preempt:preempt:at={storm1},host=127.0.0.1",
        f"host.preempt:preempt:at={storm1},host=127.0.0.2",
        f"host.preempt:preempt:at={storm2},host=127.0.0.3",
        f"host.preempt:preempt:at={storm2},host=127.0.0.4",
    ])
    p = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner",
         "--host-discovery-script", script,
         "--min-num-proc", "2",
         "--host-change-detection-interval", "0.5",
         sys.executable,
         os.path.join("tests", "journal_chaos_worker.py")],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    out, _ = p.communicate(timeout=560)
    return p.returncode, out, jdir


def _check_storm_report(report):
    s = report["summary"]
    assert s["recoveries"] >= 2, s
    assert s["by_cause"].get("preempt", 0) >= 2, s
    assert s["by_slice"].get("a", 0) >= 1, s
    assert s["by_slice"].get("b", 0) >= 1, s
    assert s["complete_decompositions"] == s["recoveries"], s
    assert s["committed_step_loss_total"] == 0, s
    for rec in report["recoveries"]:
        assert rec["cause"]["slice"] in ("a", "b"), rec["cause"]
        assert rec["cause"]["seam"] == "host.preempt:preempt", rec
        assert rec["steps"]["committed_step_loss"] == 0, rec
        assert rec["slices_lost"], rec
        for ph in ("detect", "teardown", "rendezvous", "respawn",
                   "restore", "first_commit"):
            assert rec["phases"][ph] is not None, (ph, rec)


@pytest.mark.nightly
def test_whole_slice_preemption_storm_soak(tmp_path):
    """Live seeded soak (the committed artifact's shape, fresh run):
    two whole-slice preemption storms, each detected as preempt,
    blacklisted slice-atomically, re-admitted as a unit, with zero
    committed-step loss at the durable watermark."""
    rc, out, jdir = _run_preempt_storm(str(tmp_path))
    assert rc == 0, out
    _check_storm_report(journal.incident_report(jdir))


class TestCommittedPreemptArtifact:
    """Acceptance pin: the committed preemption-storm artifact holds
    >= 2 whole-slice preempt recoveries with complete decompositions,
    zero committed-step loss, each attributed to its lost slice — and
    regenerates byte-identically from the committed journals."""

    def test_regenerates_byte_identically(self, tmp_path):
        out = str(tmp_path / "regen.json")
        journal.write_incident_report(ARTIFACT_DIR, out=out)
        assert open(out, "rb").read() == open(ARTIFACT, "rb").read()
        assert open(os.path.join(
            ARTIFACT_DIR, "incident_report.json"), "rb").read() == \
            open(ARTIFACT, "rb").read()

    def test_acceptance_invariants(self):
        report = json.load(open(ARTIFACT))
        _check_storm_report(report)
        assert report["source"]["faults"][0]["seed"] == 14
        assert "host.preempt:preempt" in \
            report["source"]["faults"][0]["spec"]


if __name__ == "__main__":
    # Artifact generation (run manually; see docs/benchmarks.md):
    #   python tests/test_slices.py /tmp/storm-work
    import shutil
    work = sys.argv[1] if len(sys.argv) > 1 else "/tmp/preempt_r14"
    os.makedirs(work, exist_ok=True)
    rc, out, jdir = _run_preempt_storm(work)
    print(out)
    print("rc =", rc)
    if rc != 0:
        sys.exit(1)
    report = journal.incident_report(jdir)
    _check_storm_report(report)
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    for name in sorted(os.listdir(jdir)):
        if name.startswith("journal-"):
            shutil.copy(os.path.join(jdir, name),
                        os.path.join(ARTIFACT_DIR, name))
    journal.write_incident_report(ARTIFACT_DIR, out=ARTIFACT)
    journal.write_incident_report(ARTIFACT_DIR)
    print("committed artifact written:", ARTIFACT)
