"""Live weight pipeline tests (weights.py + serving.py adoption):
publisher round-trip/digest/sharding, corrupt + torn snapshot
rejection with the worker still serving its previous version,
verified rollback and recovery-path repair, version GC, subscriber
seq semantics (republish = retry), the epoch-fenced hot-swap under
live traffic with zero dropped requests, worker death mid-swap, the
trainer commit-path publication hook, the armed-or-not contract of
the `weights.publish` / `weights.adopt` seams, journal event
registration (old incident artifacts stay byte-identical), and the
committed weight-swap bench artifact's pins."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from horovod_tpu import faults, journal
from horovod_tpu import weights as W
from horovod_tpu.metrics import REGISTRY
from horovod_tpu.serving import ServingFrontend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_ARTIFACT = os.path.join(REPO, "benchmarks",
                              "BENCH_weightswap_r17.json")
TRAJECTORY = os.path.join(REPO, "benchmarks", "BENCH_trajectory.json")

D = 4  # feature width for every frontend in this file


def _forward(params, x):
    import jax.numpy as jnp
    return jnp.tanh(x @ params["w"]) + params["b"]


def _params(scale=1.0, bias=0.0):
    # explicit float32: conftest enables x64, but the remote-worker
    # subprocesses (no conftest) build float32 bootstraps — and the
    # structure contract rejects dtype drift by design
    import jax.numpy as jnp
    return {"w": jnp.eye(D, dtype=jnp.float32) * scale,
            "b": jnp.full((D,), bias, dtype=jnp.float32)}


@pytest.fixture(autouse=True)
def _clean_fault_and_journal_state():
    yield
    faults.configure("", seed=0)
    if journal._journal is not None:
        journal._journal.close()
    journal._journal = None


def _base_env(tmp_path=None, **over):
    env = {
        "HOROVOD_SERVING_MAX_BATCH": "4",
        "HOROVOD_SERVING_LATENCY_BUDGET_MS": "5",
        "HOROVOD_SERVING_MIN_WORKERS": "1",
        "HOROVOD_SERVING_MAX_WORKERS": "4",
        "HOROVOD_SERVING_SCALE_INTERVAL_S": "0.05",
        "HOROVOD_SERVING_WORKER_TIMEOUT_S": "30",
        "HOROVOD_WEIGHTS_POLL_MS": "20",
    }
    if tmp_path is not None:
        jdir = os.path.join(str(tmp_path), "journal")
        os.makedirs(jdir, exist_ok=True)
        env["HOROVOD_JOURNAL_DIR"] = jdir
    env.update({k: str(v) for k, v in over.items()})
    return env


def _journal_events(tmp_path, role="serving"):
    path = os.path.join(str(tmp_path), "journal",
                        f"journal-{role}.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def _wait(pred, timeout=15.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


# -- publisher / subscriber ------------------------------------------------


class TestPublisher:
    def test_publish_poll_load_round_trip(self, tmp_path):
        d = str(tmp_path / "w")
        pub = W.WeightPublisher(d)
        p = _params(3.0, 0.5)
        v = pub.publish(p, step=42)
        assert v.seq == 1 and v.step == 42
        sub = W.WeightSubscriber(d)
        got = sub.poll()
        assert got == v
        assert sub.poll() is None        # each seq surfaces once
        names, treedef = W.tree_spec(p)
        tree = W.rebuild(sub.load_named(got), names, treedef)
        np.testing.assert_allclose(np.asarray(tree["w"]),
                                   np.eye(D) * 3.0)
        np.testing.assert_allclose(np.asarray(tree["b"]), 0.5)

    def test_digest_is_content_addressed(self, tmp_path):
        pub = W.WeightPublisher(str(tmp_path / "w"))
        v1 = pub.publish(_params(1.0), 1)
        v2 = pub.publish(_params(2.0), 2)
        v3 = pub.publish(_params(1.0), 3)
        assert v1.digest != v2.digest
        assert v1.digest == v3.digest    # same bytes, same identity
        assert v3.seq == 3               # but a fresh epoch

    def test_sharding_splits_and_reassembles(self, tmp_path):
        import jax.numpy as jnp
        d = str(tmp_path / "w")
        # ~1 KiB leaves against the 1 MiB floor would never split;
        # force multi-shard with many leaves via a tiny target.
        pub = W.WeightPublisher(d)
        pub._shard_bytes = 256
        p = {f"l{i}": jnp.full((16,), float(i)) for i in range(8)}
        v = pub.publish(p, 1)
        man = W.load_manifest(d, v)
        assert len(man["shards"]) > 1
        names, treedef = W.tree_spec(p)
        tree = W.rebuild(W.load_named(d, v), names, treedef)
        for i in range(8):
            np.testing.assert_allclose(np.asarray(tree[f"l{i}"]),
                                       float(i))

    def test_corrupt_shard_rejected(self, tmp_path):
        d = str(tmp_path / "w")
        pub = W.WeightPublisher(d)
        faults.configure("weights.publish:corrupt:at=1", seed=1)
        v = pub.publish(_params(), 1)
        faults.configure("", seed=0)
        with pytest.raises(W.WeightIntegrityError):
            W.load_named(d, v)

    def test_torn_shard_rejected(self, tmp_path):
        d = str(tmp_path / "w")
        pub = W.WeightPublisher(d)
        faults.configure("weights.publish:torn:at=1", seed=1)
        v = pub.publish(_params(), 1)
        faults.configure("", seed=0)
        with pytest.raises(W.WeightIntegrityError) as ei:
            W.load_named(d, v)
        assert W.rejection_reason(ei.value) == "torn"

    def test_structure_drift_rejected(self, tmp_path):
        d = str(tmp_path / "w")
        pub = W.WeightPublisher(d)
        v = pub.publish(_params(), 1)
        other = {"w": np.eye(D), "extra": np.zeros(2)}
        names, treedef = W.tree_spec(other)
        with pytest.raises(W.WeightStructureError):
            W.rebuild(W.load_named(d, v), names, treedef)

    def test_dtype_drift_rejected(self, tmp_path):
        # a trainer that changed precision must not be adopted by a
        # pool whose executables were compiled for the old dtype
        d = str(tmp_path / "w")
        pub = W.WeightPublisher(d)
        v = pub.publish({"w": np.eye(D, dtype=np.float64)}, 1)
        boot = {"w": np.eye(D, dtype=np.float32)}
        names, treedef = W.tree_spec(boot)
        with pytest.raises(W.WeightStructureError):
            W.rebuild(W.load_named(d, v), names, treedef,
                      W.leaf_spec(boot))

    def test_rollback_restores_previous_digest(self, tmp_path):
        d = str(tmp_path / "w")
        pub = W.WeightPublisher(d)
        v1 = pub.publish(_params(1.0), 1)
        v2 = pub.publish(_params(2.0), 2)
        rb = pub.rollback()
        assert rb.digest == v1.digest
        assert rb.seq > v2.seq           # a fresh epoch: pool adopts
        sub = W.WeightSubscriber(d)
        assert sub.poll().digest == v1.digest
        names, treedef = W.tree_spec(_params())
        tree = W.rebuild(sub.load_named(rb), names, treedef)
        np.testing.assert_allclose(np.asarray(tree["w"]), np.eye(D))

    def test_repair_repoints_damaged_current(self, tmp_path):
        d = str(tmp_path / "w")
        pub = W.WeightPublisher(d)
        v1 = pub.publish(_params(1.0), 1)
        faults.configure("weights.publish:corrupt:at=1", seed=1)
        pub.publish(_params(2.0), 2)
        faults.configure("", seed=0)
        rep = pub.repair()
        assert rep is not None and rep.digest == v1.digest
        assert pub.repair() is None      # now healthy: no-op
        W.load_named(d, W._read_current(d))   # verifies clean

    def test_gc_keeps_n_versions(self, tmp_path):
        d = str(tmp_path / "w")
        pub = W.WeightPublisher(
            d, env={"HOROVOD_WEIGHTS_KEEP": "2"})
        for i in range(5):
            pub.publish(_params(float(i + 1)), i)
        vdirs = [n for n in os.listdir(d) if n.startswith("v")]
        assert len(vdirs) == 2
        # the live version always survives GC
        cur = W._read_current(d)
        assert cur.dir in vdirs

    def test_seq_resumes_across_publisher_restart(self, tmp_path):
        d = str(tmp_path / "w")
        v1 = W.WeightPublisher(d).publish(_params(1.0), 1)
        v2 = W.WeightPublisher(d).publish(_params(2.0), 2)
        assert v2.seq == v1.seq + 1      # monotonic epoch across


# -- fault seams: armed-or-not (negative-control) contract -----------------


class TestWeightSeams:
    def test_publish_seam_disarmed_fires_nothing(self, tmp_path):
        assert not faults.active()
        before = REGISTRY.snapshot().get("hvd_faults_fired_total", {})
        W.WeightPublisher(str(tmp_path / "w")).publish(_params(), 1)
        after = REGISTRY.snapshot().get("hvd_faults_fired_total", {})
        assert before == after

    def test_publish_seam_error_counted(self, tmp_path):
        pub = W.WeightPublisher(str(tmp_path / "w"))
        faults.configure("weights.publish:error:at=1", seed=1)
        with pytest.raises(W.WeightError):
            pub.publish(_params(), 1)
        fired = REGISTRY.snapshot().get("hvd_faults_fired_total", {})
        assert fired.get(("weights.publish", "error"), 0) >= 1
        # the failed attempt left no CURRENT pointer behind
        assert W._read_current(pub.dir) is None

    def test_adopt_seam_fires_armed_or_not(self, tmp_path):
        # the seam is on the adoption path regardless of pipeline
        # feature flags — same contract as numerics.grad
        faults.configure("weights.adopt:delay:ms=1,at=1", seed=1)
        faults.fire("weights.adopt", tag="w0")
        fired = REGISTRY.snapshot().get("hvd_faults_fired_total", {})
        assert fired.get(("weights.adopt", "delay"), 0) >= 1

    def test_illegal_action_rejected_at_parse(self):
        with pytest.raises(ValueError):
            faults.configure("weights.adopt:torn:at=1", seed=1)


# -- serving adoption: the epoch-fenced hot-swap ---------------------------


class TestServingHotSwap:
    def _frontend(self, tmp_path, wdir, **over):
        env = _base_env(tmp_path, **over)
        return ServingFrontend(_forward, (D,), env=env,
                               autoscale=False, params=_params(),
                               weights=wdir)

    def test_swap_under_traffic_zero_dropped(self, tmp_path):
        wdir = str(tmp_path / "w")
        pub = W.WeightPublisher(wdir)
        v1 = pub.publish(_params(1.0), 100)
        env = _base_env(tmp_path, HOROVOD_SERVING_MIN_WORKERS=2,
                        HOROVOD_SERVING_TRACE=1)
        fe = ServingFrontend(_forward, (D,), env=env,
                             autoscale=False, params=_params(),
                             weights=wdir)
        try:
            x = np.ones((D,), np.float32)
            rows1 = [fe.submit(x).result(timeout=30)
                     for _ in range(8)]
            v2 = pub.publish(_params(2.0, 1.0), 200)
            assert _wait(lambda: all(
                w["digest"] == v2.digest for w in
                fe.stats()["weights"]["workers"].values()))
            rows2 = [fe.submit(x).result(timeout=30)
                     for _ in range(8)]
            # the swap changed what the pool computes
            np.testing.assert_allclose(
                rows1[0], np.tanh(np.ones(D)), atol=1e-6)
            np.testing.assert_allclose(
                rows2[0], np.tanh(2.0 * np.ones(D)) + 1.0,
                atol=1e-6)
            st = fe.stats()
            assert st["dropped"] == 0
            assert st["weights"]["swaps"] >= 2
            assert st["weights"]["rejections"] == 0
            # the epoch fence, witnessed by the trace: every request
            # was served under exactly one published digest
            digs = {r["weights"] for r in fe.traces()}
            assert digs <= {v1.digest, v2.digest}
            assert v2.digest in digs
        finally:
            fe.close()
        adopted = [e for e in _journal_events(tmp_path)
                   if e["type"] == "weights_adopted"]
        assert {e["digest"] for e in adopted} >= {v2.digest}

    def test_corrupt_publish_rejected_pool_keeps_old(self, tmp_path):
        wdir = str(tmp_path / "w")
        pub = W.WeightPublisher(wdir)
        v1 = pub.publish(_params(1.0), 1)
        fe = self._frontend(tmp_path, wdir)
        try:
            assert _wait(lambda:
                         fe.stats()["weights"]["swaps"] >= 1)
            faults.configure("weights.publish:corrupt:at=1", seed=1)
            pub.publish(_params(5.0), 2)
            faults.configure("", seed=0)
            assert _wait(lambda:
                         fe.stats()["weights"]["rejections"] >= 1)
            # degraded, not down: still serving v1
            st = fe.stats()["weights"]
            assert all(w["digest"] == v1.digest
                       for w in st["workers"].values())
            x = np.ones((D,), np.float32)
            np.testing.assert_allclose(
                fe.submit(x).result(timeout=30),
                np.tanh(np.ones(D)), atol=1e-6)
            # the publisher's retry (a fresh seq) converges the pool
            v3 = pub.publish(_params(5.0), 3)
            assert _wait(lambda: all(
                w["digest"] == v3.digest for w in
                fe.stats()["weights"]["workers"].values()))
            assert fe.stats()["dropped"] == 0
        finally:
            fe.close()
        rej = [e for e in _journal_events(tmp_path)
               if e["type"] == "weights_rejected"]
        assert rej and rej[0]["reason"] == "digest"
        assert rej[0]["serving"] == v1.digest

    def test_worker_death_mid_swap_pool_recovers(self, tmp_path):
        wdir = str(tmp_path / "w")
        pub = W.WeightPublisher(wdir)
        pub.publish(_params(1.0), 1)
        env = _base_env(tmp_path, HOROVOD_SERVING_MIN_WORKERS=2)
        fe = ServingFrontend(_forward, (D,), env=env,
                             autoscale=True, params=_params(),
                             weights=wdir)
        try:
            assert _wait(lambda:
                         fe.stats()["weights"]["swaps"] >= 2)
            faults.configure("weights.adopt:error:at=1", seed=1)
            v2 = pub.publish(_params(2.0), 2)
            x = np.ones((D,), np.float32)
            rows = [fe.submit(x).result(timeout=30)
                    for _ in range(8)]
            assert len(rows) == 8
            fired = REGISTRY.snapshot().get(
                "hvd_faults_fired_total", {})
            assert fired.get(("weights.adopt", "error"), 0) >= 1
            # the autoscaler restores the floor and the respawned
            # member adopts v2; the pool converges
            assert _wait(lambda: (
                len(fe.stats()["weights"]["workers"]) >= 2
                and all(w["digest"] == v2.digest for w in
                        fe.stats()["weights"]["workers"].values())))
            assert fe.stats()["dropped"] == 0
        finally:
            fe.close()

    def test_rollback_end_to_end(self, tmp_path):
        wdir = str(tmp_path / "w")
        pub = W.WeightPublisher(wdir)
        v1 = pub.publish(_params(1.0), 1)
        v2 = pub.publish(_params(2.0), 2)
        fe = self._frontend(tmp_path, wdir)
        try:
            assert _wait(lambda: all(
                w["digest"] == v2.digest for w in
                fe.stats()["weights"]["workers"].values()))
            rb = pub.rollback()
            assert rb.digest == v1.digest
            assert _wait(lambda: all(
                w["digest"] == v1.digest for w in
                fe.stats()["weights"]["workers"].values()))
            x = np.ones((D,), np.float32)
            np.testing.assert_allclose(
                fe.submit(x).result(timeout=30),
                np.tanh(np.ones(D)), atol=1e-6)
        finally:
            fe.close()

    def test_stats_staleness_and_no_recompile(self, tmp_path):
        wdir = str(tmp_path / "w")
        pub = W.WeightPublisher(wdir)
        pub.publish(_params(1.0), 10)
        fe = self._frontend(tmp_path, wdir)
        try:
            assert _wait(lambda:
                         fe.stats()["weights"]["swaps"] >= 1)
            compiles0 = fe.stats()["compiles"]
            pub.publish(_params(2.0), 30)
            assert _wait(lambda:
                         fe.stats()["weights"]["swaps"] >= 2)
            st = fe.stats()
            # hot-swap must not recompile: executables are
            # specialized on shapes only, which adoption preserves
            assert st["compiles"] == compiles0
            w = next(iter(st["weights"]["workers"].values()))
            assert w["staleness_steps"] == 0
            assert st["weights"]["target_step"] == 30
        finally:
            fe.close()

    def test_params_without_weights_is_static(self, tmp_path):
        # two-arg forward with a fixed tree: no watcher, no target
        env = _base_env(tmp_path)
        fe = ServingFrontend(_forward, (D,), env=env,
                             autoscale=False, params=_params(3.0))
        try:
            x = np.ones((D,), np.float32)
            np.testing.assert_allclose(
                fe.submit(x).result(timeout=30),
                np.tanh(3.0 * np.ones(D)), atol=1e-6)
            assert "weights" not in fe.stats()
        finally:
            fe.close()

    def test_weights_requires_params(self, tmp_path):
        with pytest.raises(ValueError):
            ServingFrontend(_forward, (D,),
                            env=_base_env(tmp_path),
                            start_pool=False, autoscale=False,
                            weights=str(tmp_path / "w"))


# -- remote pool member: a REAL process death mid-swap ---------------------


def _spawn_weighted_worker(port, secret, wid, wdir, extra_env=None):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["SERVING_TEST_STANDALONE"] = "1"
    env["SERVING_TEST_ADDR"] = "127.0.0.1"
    env["SERVING_TEST_PORT"] = str(port)
    env["SERVING_TEST_SECRET"] = secret
    env["SERVING_TEST_DMODEL"] = str(D)
    env["SERVING_TEST_WID"] = wid
    env["SERVING_TEST_WEIGHTS_DIR"] = wdir
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable,
         os.path.join("tests", "serving_chaos_worker.py")],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)


@pytest.mark.integration
def test_remote_worker_crash_mid_swap_zero_dropped(tmp_path):
    """Two real worker processes serve the two-arg live-weight
    forward over the wire; a version is published mid-traffic and
    one member is seeded `weights.adopt:crash` — a REAL process
    death (os._exit) mid-swap. The survivor adopts, the dead
    member's in-flight batch is requeued, and every request
    completes — zero dropped, no batch mixing versions."""
    jdir = tmp_path / "journal"
    jdir.mkdir()
    wdir = str(tmp_path / "w")
    env = _base_env(None, HOROVOD_SERVING_WORKER_TIMEOUT_S="1",
                    HOROVOD_SERVING_TRACE="1")
    env["HOROVOD_JOURNAL_DIR"] = str(jdir)
    boot = _params()                     # matches the worker's
    fe = ServingFrontend(_forward, (D,), env=env, params=boot,
                         start_pool=False, autoscale=False)
    boot_digest = fe._params0_digest
    procs = []
    try:
        port, secret = fe.serve_endpoint()
        wa = _spawn_weighted_worker(
            port, secret, "wA", wdir,
            {"HOROVOD_FAULTS": "weights.adopt:crash:at=1",
             "HOROVOD_FAULTS_SEED": "3",
             "HOROVOD_JOURNAL_DIR": str(jdir)})
        wb = _spawn_weighted_worker(
            port, secret, "wB", wdir,
            {"HOROVOD_JOURNAL_DIR": str(jdir)})
        procs = [wa, wb]
        rng = np.random.RandomState(7)
        xs = [rng.randn(D).astype(np.float32) for _ in range(10)]
        futs = [fe.submit(x) for x in xs]
        for f in futs:
            f.result(timeout=120)        # both members live, boot
        v1 = W.WeightPublisher(wdir).publish(
            _params(2.0, 1.0), step=50)
        xs2 = [rng.randn(D).astype(np.float32) for _ in range(14)]
        futs2 = []
        for x in xs2:
            futs2.append(fe.submit(x))
            time.sleep(0.02)
        for f in futs2:
            f.result(timeout=120)
        s = fe.stats()
        assert wa.wait(timeout=60) == 43, \
            "wA should die on the adopt seam"
    finally:
        fe.close()
        for p in procs:
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
    assert wb.returncode == 0, wb.stdout.read()
    assert s["completed"] == 24 and s["failed"] == 0
    assert s["dropped"] == 0
    # epoch fence across the wire: every traced batch executed under
    # exactly one digest, all from the known version set
    digs = {r["weights"] for r in fe.traces()}
    assert digs <= {boot_digest, v1.digest}
    assert v1.digest in digs             # the survivor converged
    # the dead member's journal attributes the mid-swap death
    wa_events = _journal_events(tmp_path, role="serving-wA")
    fired = [e for e in wa_events if e["type"] == "fault_fired"]
    assert fired and fired[0]["point"] == "weights.adopt"
    assert fired[0]["action"] == "crash"
    # the survivor journaled its adoption of the published version
    wb_events = _journal_events(tmp_path, role="serving-wB")
    adopted = [e for e in wb_events
               if e["type"] == "weights_adopted"]
    assert adopted and adopted[0]["digest"] == v1.digest


# -- trainer commit-path publication ---------------------------------------


class TestCommitPathPublish:
    def test_maybe_publish_rides_commit(self, tmp_path, monkeypatch):
        import jax.numpy as jnp
        from horovod_tpu.elastic.state import JaxState
        wdir = str(tmp_path / "w")
        monkeypatch.setenv("HOROVOD_WEIGHTS_DIR", wdir)
        monkeypatch.setenv("HOROVOD_WEIGHTS_PUBLISH_EVERY", "2")
        st = JaxState(params={"w": jnp.ones(D)}, step=0)
        st.commit()                      # commit 1: always publishes
        cur = W._read_current(wdir)
        assert cur is not None and cur.seq == 1
        st.params = {"w": jnp.full(D, 2.0)}
        st.step = 1
        st.commit()                      # commit 2: off-cadence
        assert W._read_current(wdir).seq == 1
        st.params = {"w": jnp.full(D, 3.0)}
        st.step = 2
        st.commit()                      # commit 3: publishes
        cur = W._read_current(wdir)
        assert cur.seq == 2 and cur.step == 2
        named = W.load_named(wdir, cur)
        assert len(named) == 1
        np.testing.assert_allclose(named[0][1], np.full(D, 3.0))

    def test_disarmed_commit_does_not_publish(self, tmp_path,
                                              monkeypatch):
        import jax.numpy as jnp
        from horovod_tpu.elastic.state import JaxState
        monkeypatch.delenv("HOROVOD_WEIGHTS_DIR", raising=False)
        st = JaxState(params={"w": jnp.ones(D)}, step=0)
        st.commit()
        assert not hasattr(st, "_weights_publisher")

    def test_publish_failure_never_kills_training(self, tmp_path,
                                                  monkeypatch):
        import jax.numpy as jnp
        from horovod_tpu.elastic.state import JaxState
        wdir = str(tmp_path / "w")
        monkeypatch.setenv("HOROVOD_WEIGHTS_DIR", wdir)
        monkeypatch.setenv("HOROVOD_WEIGHTS_PUBLISH_EVERY", "1")
        faults.configure("weights.publish:error:at=1", seed=1)
        st = JaxState(params={"w": jnp.ones(D)}, step=0)
        st.commit()                      # publish fails; commit wins
        faults.configure("", seed=0)
        assert W._read_current(wdir) is None
        st.step = 1
        st.commit()                      # retry on the next cadence
        assert W._read_current(wdir) is not None

    def test_maybe_repair_recovers_torn_current(self, tmp_path,
                                                monkeypatch):
        wdir = str(tmp_path / "w")
        pub = W.WeightPublisher(wdir)
        v1 = pub.publish(_params(1.0), 1)
        faults.configure("weights.publish:torn:at=1", seed=1)
        pub.publish(_params(2.0), 2)
        faults.configure("", seed=0)
        monkeypatch.setenv("HOROVOD_WEIGHTS_DIR", wdir)
        W.maybe_repair()
        cur = W._read_current(wdir)
        assert cur.digest == v1.digest
        W.load_named(wdir, cur)          # verifies intact


# -- journal registration: new typed events, old readers -------------------


class TestJournalRegistration:
    def test_weights_events_are_critical(self):
        assert {"weights_published", "weights_adopted",
                "weights_rejected"} <= journal.CRITICAL_EVENTS

    def test_timeline_carries_weights_events(self, tmp_path,
                                             monkeypatch):
        jdir = tmp_path / "journal"
        jdir.mkdir()
        monkeypatch.setenv("HOROVOD_JOURNAL_DIR", str(jdir))
        journal.configure("worker", rank=0)
        journal.record("weights_published", digest="d1", seq=1,
                       step=10, kind="publish", ms=1.0)
        journal.record("weights_rejected", worker="w0", digest="d1",
                       seq=1, reason="torn", detail="x",
                       serving="d0")
        journal.record("weights_adopted", worker="w0", digest="d1",
                       seq=2, step=10, ms=2.0, staleness_steps=0)
        journal._journal.close()
        journal._journal = None
        _, report = journal.write_incident_report(str(jdir))
        # timeline rows are [t_rel, who, type, detail]
        types = [e[2] for e in report["timeline"]]
        assert types.count("weights_published") == 1
        assert types.count("weights_adopted") == 1
        assert types.count("weights_rejected") == 1

    def test_old_incident_artifacts_unaffected(self, tmp_path):
        """The new event types must not perturb regeneration of the
        committed r11/r14 incident artifacts (their journals contain
        no weights events) — the byte-identity pins live in
        test_journal.py / test_slices.py; here we pin the keep-set
        semantics they rely on: unknown-to-old-readers event types
        outside the keep-set still do not leak into timelines."""
        entries = journal._timeline_entries(
            [{"type": "weights_published", "t": 1.0, "n": 1,
              "role": "worker", "digest": "d"},
             {"type": "not_a_real_event", "t": 2.0, "n": 2,
              "role": "worker"}], 0.0)
        assert [e[2] for e in entries] == ["weights_published"]


# -- committed bench artifact pins -----------------------------------------


class TestWeightSwapBenchArtifact:
    def test_artifact_pins(self):
        doc = json.load(open(BENCH_ARTIFACT))
        swap = doc["rolling_update"]
        # zero-downtime: nothing dropped, nothing failed, across
        # every leg of the rolling update
        assert swap["dropped"] == 0 and swap["failed"] == 0
        assert swap["swaps"] >= 1
        # epoch fence witnessed in the trace: every served batch
        # carries exactly one digest from the published set
        assert swap["fence"]["mixed_version_batches"] == 0
        assert swap["fence"]["digests_seen"] >= 2
        # p99 during the swap window stays inside the SLO budget
        assert 0 < swap["p99_during_swap_ms"] <= \
            doc["config"]["slo_budget_ms"]
        assert swap["swap_ms"]["max"] >= swap["swap_ms"]["mean"] > 0
        chaos = doc["chaos"]
        assert chaos["dropped"] == 0 and chaos["failed"] == 0
        assert chaos["worker_deaths"] >= 1
        assert chaos["corrupt_rejections"] >= 1
        assert chaos["converged_digest"] == chaos["final_digest"]
        rb = doc["rollback"]
        assert rb["restored_digest"] == rb["previous_digest"]
        assert rb["dropped"] == 0
        stale = doc["staleness_curve"]
        assert stale and stale[-1]["staleness_steps"] == 0

    def test_trajectory_row_matches_artifact(self):
        traj = json.load(open(TRAJECTORY))
        row = traj["r17_weightswap"]
        doc = json.load(open(BENCH_ARTIFACT))
        assert row["p99_during_swap_ms"] == \
            doc["rolling_update"]["p99_during_swap_ms"]
        assert row["swap_mean_ms"] == \
            doc["rolling_update"]["swap_ms"]["mean"]
        assert row["mixed_version_batches"] == 0
        assert row["source"] == "benchmarks/BENCH_weightswap_r17.json"

    @pytest.mark.integration
    def test_trajectory_regenerates_byte_identical(self, tmp_path):
        """--trajectory is a pure function of the committed
        artifacts: regenerating with the r17 row wired in must
        reproduce the committed bytes exactly."""
        out = tmp_path / "traj.json"
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["BENCH_TRAJECTORY_OUT"] = str(out)
        subprocess.run(
            [sys.executable, "bench.py", "--trajectory"],
            cwd=REPO, env=env, check=True,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        assert out.read_bytes() == \
            open(TRAJECTORY, "rb").read()
