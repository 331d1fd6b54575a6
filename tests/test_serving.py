"""Elastic inference serving tests: bucket-ladder determinism (incl.
across fresh interpreters), the no-recompile pin under mixed request
shapes, dynamic batching under the latency budget, retry exactly-once
semantics under injected `serving.batch` faults (error / hang /
exhausted budget), queue-depth autoscaling, the ElasticDriver
membership hook, a real mid-batch remote-worker kill over the wire
(zero dropped requests), the committed serving bench artifact's pins,
and (behind the multiproc probe) a 2-rank chaos run through the
elastic runner."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from horovod_tpu import faults, journal
from horovod_tpu.common import config
from horovod_tpu.serving import (ServingError, ServingFrontend,
                                 build_ladder)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_ARTIFACT = os.path.join(REPO, "benchmarks",
                              "BENCH_serving_r15.json")

D = 8  # feature width used by every frontend in this file


def _forward(x):
    import jax.numpy as jnp
    return jnp.tanh(x) * 2.0


def _expect(x):
    return np.tanh(np.asarray(x, dtype=np.float32)) * 2.0


@pytest.fixture(autouse=True)
def _clean_fault_and_journal_state():
    """Frontends (re)configure the module journal and tests arm the
    fault plan; restore both so state never leaks across tests."""
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


# -- bucket ladder ---------------------------------------------------------


class TestBucketLadder:
    def test_pow2_rungs_and_rounding(self):
        lad = build_ladder(max_batch=8, max_len=0)
        assert lad.batch_buckets == (1, 2, 4, 8)
        assert lad.len_buckets == ()
        assert [lad.batch_bucket(n) for n in (1, 2, 3, 5, 8)] == \
            [1, 2, 4, 8, 8]

    def test_non_pow2_max_is_its_own_rung(self):
        lad = build_ladder(max_batch=6, max_len=0)
        assert lad.batch_buckets == (1, 2, 4, 6)
        assert lad.batch_bucket(5) == 6

    def test_oversize_raises_visibly(self):
        lad = build_ladder(max_batch=4, max_len=32)
        with pytest.raises(ServingError):
            lad.batch_bucket(5)
        with pytest.raises(ServingError):
            lad.len_bucket(33)

    def test_len_ladder_variants(self):
        assert build_ladder(4, 8).len_buckets == (8,)
        assert build_ladder(4, 16).len_buckets == (16,)
        assert build_ladder(4, 48).len_buckets == (16, 32, 48)
        assert build_ladder(4, 64).len_buckets == (16, 32, 64)

    def test_digest_is_canonical_string(self):
        assert build_ladder(8, 0).digest == \
            "serving-ladder-v1|b=1,2,4,8|l=-"
        assert build_ladder(4, 48).digest == \
            "serving-ladder-v1|b=1,2,4|l=16,32,48"

    def test_shapes_enumerates_full_cross_product(self):
        lad = build_ladder(4, 32)
        shapes = lad.shapes((D,))
        assert len(shapes) == 3 * 2
        assert (4, 32, D) in shapes and (1, 16, D) in shapes
        assert build_ladder(2, 0).shapes((D,)) == [(1, D), (2, D)]

    def test_knob_driven_build(self):
        lad = build_ladder(env={"HOROVOD_SERVING_MAX_BATCH": "16",
                                "HOROVOD_SERVING_MAX_LEN": "0"})
        assert lad.batch_buckets == (1, 2, 4, 8, 16)

    def test_digest_deterministic_across_fresh_interpreters(self):
        """The cross-process pin: frontends and workers must derive
        the identical digest in separate interpreters regardless of
        hash randomization (same contract as OverlapPlan's assignment
        digest)."""
        prog = ("import sys; sys.path.insert(0, sys.argv[1]); "
                "from horovod_tpu.serving import build_ladder; "
                "l = build_ladder(8, 48); "
                "print(l.digest); print(l.shapes((8,)))")
        outs = []
        for seed in ("0", "4242"):
            env = dict(os.environ)
            env["PYTHONHASHSEED"] = seed
            env["JAX_PLATFORMS"] = "cpu"
            r = subprocess.run(
                [sys.executable, "-c", prog, REPO], env=env,
                capture_output=True, text=True, timeout=120)
            assert r.returncode == 0, r.stderr
            outs.append(r.stdout)
        assert outs[0] == outs[1]
        assert outs[0].splitlines()[0] == build_ladder(8, 48).digest


def test_all_serving_knobs_declared():
    """Every HOROVOD_SERVING_* tunable is a declared knob (the HVD002
    registry/docs-drift gate hangs off this list)."""
    declared = {k.env: k for k in config.KNOBS}
    expected = {
        "HOROVOD_SERVING_MAX_BATCH": 8,
        "HOROVOD_SERVING_LATENCY_BUDGET_MS": 10.0,
        "HOROVOD_SERVING_MAX_LEN": 0,
        "HOROVOD_SERVING_MIN_WORKERS": 1,
        "HOROVOD_SERVING_MAX_WORKERS": 4,
        "HOROVOD_SERVING_SCALE_INTERVAL_S": 0.5,
        "HOROVOD_SERVING_SCALE_UP_QUEUE": 2.0,
        "HOROVOD_SERVING_SCALE_DOWN_IDLE_S": 5.0,
        "HOROVOD_SERVING_RETRY_LIMIT": 3,
        "HOROVOD_SERVING_WORKER_TIMEOUT_S": 30.0,
        "HOROVOD_SERVING_TRACE": True,
        "HOROVOD_SERVING_TRACE_BUFFER": 4096,
        "HOROVOD_SERVING_DEFAULT_SLO_MS": 0.0,
    }
    for name, default in expected.items():
        assert name in declared, name
        assert declared[name].default == default, name


# -- local frontend --------------------------------------------------------


class TestFrontendLocal:
    def test_round_trip_and_dynamic_batching(self, tmp_path):
        env = _base_env(tmp_path)
        fe = ServingFrontend(_forward, (D,), env=env,
                             start_pool=False, autoscale=False)
        try:
            fe.start_pool(1)
            rng = np.random.RandomState(0)
            xs = [rng.randn(D).astype(np.float32) for _ in range(10)]
            futs = [fe.submit(x) for x in xs]
            for x, f in zip(xs, futs):
                np.testing.assert_allclose(
                    f.result(timeout=60), _expect(x),
                    rtol=1e-5, atol=1e-5)
            s = fe.stats()
        finally:
            fe.close()
        assert s["submitted"] == 10
        assert s["completed"] == 10
        assert s["dropped"] == 0 and s["failed"] == 0
        # MAX_BATCH=4 => at least ceil(10/4) dynamic batches
        assert s["batches"] >= 3
        evs = _journal_events(tmp_path)
        admitted = [e for e in evs if e["type"] == "batch_admitted"]
        assert sum(e["size"] for e in admitted) == 10
        for e in admitted:
            assert e["bucket"] >= e["size"]

    def test_latency_budget_cuts_partial_batch(self):
        # A batch that can never fill must still complete within the
        # latency budget (plus execution), not wait forever.
        env = _base_env(None, HOROVOD_SERVING_MAX_BATCH=64,
                        HOROVOD_SERVING_LATENCY_BUDGET_MS=30)
        fe = ServingFrontend(_forward, (D,), env=env,
                             start_pool=False, autoscale=False)
        try:
            fe.start_pool(1)
            futs = [fe.submit(np.ones(D, np.float32))
                    for _ in range(3)]
            for f in futs:
                np.testing.assert_allclose(
                    f.result(timeout=60), _expect(np.ones(D)),
                    rtol=1e-5, atol=1e-5)
            s = fe.stats()
        finally:
            fe.close()
        assert s["batches"] == 1 and s["completed"] == 3

    def test_no_recompile_across_mixed_shapes(self):
        """The no-recompile pin: after warmup the compile count equals
        the ladder's closed shape set and NO mix of request shapes
        grows it."""
        env = _base_env(None, HOROVOD_SERVING_MAX_LEN=32)
        fe = ServingFrontend(_forward, (D,), env=env,
                             start_pool=False, autoscale=False)
        try:
            fe.start_pool(1)
            want = len(fe.ladder.shapes((D,)))
            assert want == 6  # b in (1,2,4) x L in (16,32)
            deadline = time.monotonic() + 60
            while (fe.stats()["compiles"] < want
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            assert fe.stats()["compiles"] == want
            rng = np.random.RandomState(1)
            xs = [rng.randn(L, D).astype(np.float32)
                  for L in (3, 17, 32, 1, 9, 16, 31, 5)]
            futs = [fe.submit(x) for x in xs]
            for x, f in zip(xs, futs):
                got = f.result(timeout=60)
                assert got.shape == x.shape  # unpadded to true length
                np.testing.assert_allclose(got, _expect(x),
                                           rtol=1e-5, atol=1e-5)
            s = fe.stats()
        finally:
            fe.close()
        assert s["compiles"] == want, \
            "a request shape escaped the bucket ladder"
        assert s["dropped"] == 0

    def test_submit_validates_shapes(self):
        env = _base_env(None, HOROVOD_SERVING_MAX_LEN=32)
        fe = ServingFrontend(_forward, (D,), env=env,
                             start_pool=False, autoscale=False)
        try:
            with pytest.raises(ValueError):
                fe.submit(np.ones((3, D + 1), np.float32))
            with pytest.raises(ServingError):
                fe.submit(np.ones((33, D), np.float32))  # > MAX_LEN
        finally:
            fe.close()
        fe2 = ServingFrontend(_forward, (D,), env=_base_env(),
                              start_pool=False, autoscale=False)
        try:
            with pytest.raises(ValueError):
                fe2.submit(np.ones(D + 1, np.float32))
        finally:
            fe2.close()

    def test_submit_after_close_fails_visibly(self):
        fe = ServingFrontend(_forward, (D,), env=_base_env(),
                             start_pool=False, autoscale=False)
        fe.close()
        with pytest.raises(ServingError):
            fe.submit(np.ones(D, np.float32))


# -- retry / exactly-once under injected faults ----------------------------


class TestRetryExactlyOnce:
    def test_injected_worker_death_retries_without_loss(self, tmp_path):
        """`serving.batch:error` kills a worker mid-batch: the batch
        must be re-dispatched on the survivor, every request must
        complete exactly once, and the retry must be journaled with
        its cause."""
        env = _base_env(tmp_path)
        fe = ServingFrontend(_forward, (D,), env=env,
                             start_pool=False, autoscale=False)
        try:
            fe.start_pool(2)
            faults.configure("serving.batch:error:at=2", seed=0)
            rng = np.random.RandomState(2)
            xs = [rng.randn(D).astype(np.float32) for _ in range(12)]
            futs = [fe.submit(x) for x in xs]
            for x, f in zip(xs, futs):
                np.testing.assert_allclose(
                    f.result(timeout=60), _expect(x),
                    rtol=1e-5, atol=1e-5)
            faults.configure("", seed=0)
            s = fe.stats()
        finally:
            fe.close()
        assert s["completed"] == 12 and s["failed"] == 0
        assert s["dropped"] == 0
        assert s["retries"] >= 1
        evs = _journal_events(tmp_path)
        retried = [e for e in evs if e["type"] == "batch_retried"]
        assert retried and retried[0]["cause"] == "fault_error"
        assert retried[0]["attempt"] == 1
        deaths = [e for e in evs if e["type"] == "scale_event"
                  and e["reason"] == "worker_death:fault_error"]
        assert deaths and deaths[0]["worker"] == retried[0]["worker"]

    def test_hung_worker_deadline_and_duplicate_suppression(
            self, tmp_path):
        """`serving.batch:hang` parks a worker holding its batch: the
        per-batch deadline (the serving heartbeat detector) requeues
        it, and the revenant's late completion is suppressed by the
        exactly-once latch — counted, never double-delivered."""
        env = _base_env(tmp_path,
                        HOROVOD_SERVING_WORKER_TIMEOUT_S="0.4")
        fe = ServingFrontend(_forward, (D,), env=env,
                             start_pool=False, autoscale=False)
        try:
            fe.start_pool(2)
            faults.configure("serving.batch:hang:at=1", seed=0)
            xs = [np.full(D, i, np.float32) for i in range(4)]
            futs = [fe.submit(x) for x in xs]
            for x, f in zip(xs, futs):
                np.testing.assert_allclose(
                    f.result(timeout=60), _expect(x),
                    rtol=1e-5, atol=1e-5)
            faults.configure("", seed=0)
            # The revenant wakes after ~4x the timeout and attempts
            # completion; wait for the latch to absorb all 4 rows.
            deadline = time.monotonic() + 15
            while (fe.stats()["duplicates_suppressed"] < 4
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            s = fe.stats()
        finally:
            fe.close()
        assert s["completed"] == 4 and s["dropped"] == 0
        assert s["retries"] >= 1
        assert s["duplicates_suppressed"] == 4
        retried = [e for e in _journal_events(tmp_path)
                   if e["type"] == "batch_retried"]
        assert retried and retried[0]["cause"] == "timeout"

    def test_retry_budget_exhausted_fails_visibly(self, tmp_path):
        """When every dispatch dies, the request must FAIL (visible
        ServingError, counted) rather than silently drop or hang."""
        env = _base_env(tmp_path,
                        HOROVOD_SERVING_RETRY_LIMIT="1",
                        HOROVOD_SERVING_SCALE_INTERVAL_S="0.02")
        fe = ServingFrontend(_forward, (D,), env=env,
                             start_pool=False, autoscale=True)
        try:
            fe.start_pool(1)
            faults.configure("serving.batch:error", seed=0)
            fut = fe.submit(np.ones(D, np.float32))
            with pytest.raises(ServingError, match="dispatch attempts"):
                fut.result(timeout=60)
            faults.configure("", seed=0)
            s = fe.stats()
        finally:
            faults.configure("", seed=0)
            fe.close()
        assert s["failed"] == 1 and s["completed"] == 0
        assert s["dropped"] == 0
        assert s["retries"] == 1  # limit=1: one requeue, then fail


# -- autoscaling -----------------------------------------------------------


class TestAutoscale:
    def test_scale_up_on_queue_depth_then_down_on_idle(self, tmp_path):
        env = _base_env(tmp_path,
                        HOROVOD_SERVING_MAX_BATCH="1",
                        HOROVOD_SERVING_LATENCY_BUDGET_MS="1",
                        HOROVOD_SERVING_MAX_WORKERS="3",
                        HOROVOD_SERVING_SCALE_INTERVAL_S="0.02",
                        HOROVOD_SERVING_SCALE_UP_QUEUE="1.0",
                        HOROVOD_SERVING_SCALE_DOWN_IDLE_S="0.25")
        fe = ServingFrontend(_forward, (D,), env=env,
                             start_pool=True, autoscale=True)
        peak = 0
        try:
            # Slow every batch so the queue builds faster than one
            # worker drains it.
            faults.configure("serving.batch:delay:ms=30", seed=0)
            futs = [fe.submit(np.full(D, i, np.float32))
                    for i in range(30)]
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                peak = max(peak, fe.stats()["workers"])
                if peak >= 2 and all(f.done for f in futs):
                    break
                time.sleep(0.02)
            for f in futs:
                f.result(timeout=60)
            faults.configure("", seed=0)
            assert peak >= 2, "queue depth never scaled the pool out"
            # Idle: the pool must shrink back to the floor.
            deadline = time.monotonic() + 20
            while (fe.stats()["workers"] > 1
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            s = fe.stats()
        finally:
            faults.configure("", seed=0)
            fe.close()
        assert s["workers"] == 1
        assert s["dropped"] == 0
        dirs = [e["direction"] for e in _journal_events(tmp_path)
                if e["type"] == "scale_event"]
        assert "up" in dirs and "down" in dirs

    def test_floor_restored_after_worker_death(self, tmp_path):
        env = _base_env(tmp_path,
                        HOROVOD_SERVING_MIN_WORKERS="2",
                        HOROVOD_SERVING_SCALE_INTERVAL_S="0.02")
        fe = ServingFrontend(_forward, (D,), env=env,
                             start_pool=True, autoscale=True)
        try:
            faults.configure("serving.batch:error:at=1", seed=0)
            fut = fe.submit(np.ones(D, np.float32))
            fut.result(timeout=60)
            faults.configure("", seed=0)
            deadline = time.monotonic() + 20
            while (fe.stats()["workers"] < 2
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            s = fe.stats()
        finally:
            faults.configure("", seed=0)
            fe.close()
        assert s["workers"] == 2, "autoscaler never restored the floor"
        reasons = [e["reason"] for e in _journal_events(tmp_path)
                   if e["type"] == "scale_event"]
        assert "floor" in reasons


# -- elastic membership hook -----------------------------------------------


class TestMembershipHook:
    def test_driver_listener_fires_and_is_contained(self):
        from horovod_tpu.runner.elastic import (ElasticDriver,
                                                FixedHosts)
        drv = ElasticDriver(["true"], FixedHosts("", 2))
        try:
            seen = []
            drv.add_membership_listener(
                lambda epoch, infos: seen.append(
                    (epoch, len(infos))))
            drv.add_membership_listener(
                lambda epoch, infos: 1 / 0)  # must be contained
            hosts = drv.discovery.find_available_hosts_and_slots()
            infos, _ = drv._publish_epoch(hosts)
            assert seen == [(1, len(infos))]
            drv._publish_epoch(hosts)
            assert seen[-1][0] == 2
        finally:
            drv.rendezvous.stop()

    def test_on_membership_resizes_pool(self, tmp_path):
        env = _base_env(tmp_path)
        fe = ServingFrontend(_forward, (D,), env=env,
                             start_pool=True, autoscale=False)
        try:
            fe.on_membership(7, [object()] * 3)
            assert fe.stats()["workers"] == 3
            fe.on_membership(8, [object()] * 1)
            assert fe.stats()["workers"] == 1
            # clamped to the knob ceiling (MAX_WORKERS=4)
            fe.on_membership(9, [object()] * 9)
            assert fe.stats()["workers"] == 4
        finally:
            fe.close()
        evs = [e for e in _journal_events(tmp_path)
               if e["type"] == "scale_event"
               and e["reason"] == "membership"]
        assert [e["epoch"] for e in evs] == [7, 8, 9]
        assert [e["workers_to"] for e in evs] == [3, 1, 4]


# -- remote pool: real mid-batch process kill over the wire ----------------


def _spawn_remote_worker(tmp_path, port, secret, wid, extra_env=None):
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
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable,
         os.path.join("tests", "serving_chaos_worker.py")],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)


@pytest.mark.integration
def test_remote_worker_mid_batch_kill_zero_dropped(tmp_path):
    """Two real worker processes pull batches over the HMAC-signed
    wire; one is seeded to CRASH (os._exit) mid-batch. The dispatch
    deadline must requeue its in-flight batch on the survivor and
    every request must complete — zero dropped."""
    jdir = tmp_path / "journal"
    jdir.mkdir()
    env = _base_env(None, HOROVOD_SERVING_WORKER_TIMEOUT_S="1")
    env["HOROVOD_JOURNAL_DIR"] = str(jdir)
    fe = ServingFrontend(_forward, (D,), env=env,
                         start_pool=False, autoscale=False)
    procs = []
    try:
        port, secret = fe.serve_endpoint()
        wa = _spawn_remote_worker(
            tmp_path, port, secret, "wA",
            {"HOROVOD_FAULTS": "serving.batch:crash:at=2",
             "HOROVOD_FAULTS_SEED": "3",
             "HOROVOD_JOURNAL_DIR": str(jdir)})
        wb = _spawn_remote_worker(tmp_path, port, secret, "wB")
        procs = [wa, wb]
        rng = np.random.RandomState(4)
        xs = [rng.randn(D).astype(np.float32) for _ in range(24)]
        futs = []
        for x in xs:
            futs.append(fe.submit(x))
            time.sleep(0.02)
        for x, f in zip(xs, futs):
            np.testing.assert_allclose(
                f.result(timeout=120), _expect(x),
                rtol=1e-5, atol=1e-5)
        s = fe.stats()
        assert wa.wait(timeout=60) == 43, "wA should die on the seam"
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
    assert s["retries"] >= 1
    retried = [e for e in _journal_events(tmp_path)
               if e["type"] == "batch_retried"]
    assert retried and retried[0]["cause"] == "timeout"
    assert retried[0]["worker"] == "wA"
    # the dead worker's own journal carries the fault attribution
    wa_events = _journal_events(tmp_path, role="serving-wA")
    fired = [e for e in wa_events if e["type"] == "fault_fired"]
    assert fired and fired[0]["point"] == "serving.batch"
    assert fired[0]["action"] == "crash"


# -- committed bench artifact pins -----------------------------------------


class TestServingBenchArtifact:
    def test_artifact_pins(self):
        doc = json.load(open(BENCH_ARTIFACT))
        # the measured numbers are tied to the exact executable-shape
        # set via the ladder digest — same derivation here must match
        assert doc["ladder"]["digest"] == build_ladder(
            doc["config"]["max_batch"], 0).digest
        # acceptance bar: the injected mid-batch worker death lost
        # nothing, and the recovery went through the retry path
        assert doc["retry"]["dropped"] == 0
        assert doc["retry"]["failed"] == 0
        assert doc["retry"]["retries"] >= 1
        assert sorted(doc["latency_vs_qps"]) == \
            ["qps100", "qps200", "qps50"]
        for leg in doc["latency_vs_qps"].values():
            assert 0 < leg["p50_ms"] <= leg["p99_ms"]
        assert sorted(doc["scaleout"]) == \
            ["workers1", "workers2", "workers4"]
        for leg in doc["scaleout"].values():
            assert leg["achieved_qps"] > 0


# -- probe-gated 2-rank chaos run through the elastic runner ---------------


@pytest.mark.integration
def test_two_rank_pool_chaos_zero_dropped(tmp_path,
                                          multiproc_data_plane):
    """The acceptance chaos leg: a 2-rank elastic-runner gang joins
    the frontend's pool; rank 1 is seeded to crash mid-batch (once,
    latched across the gang restart). The frontend — which outlives
    the gang, as a serving driver does — must retry on survivors and
    complete every request, and the incident report must attribute
    the recovery to the injected seam."""
    jdir = tmp_path / "journal"
    jdir.mkdir()
    script = tmp_path / "discover.sh"
    script.write_text("#!/bin/sh\necho localhost:2\n")
    script.chmod(0o755)

    senv = _base_env(None, HOROVOD_SERVING_WORKER_TIMEOUT_S="2")
    senv["HOROVOD_JOURNAL_DIR"] = str(jdir)
    fe = ServingFrontend(_forward, (D,), env=senv,
                         start_pool=False, autoscale=False)
    p = None
    try:
        port, secret = fe.serve_endpoint()
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + \
            env.get("PYTHONPATH", "")
        env["SERVING_TEST_ADDR"] = "127.0.0.1"
        env["SERVING_TEST_PORT"] = str(port)
        env["SERVING_TEST_SECRET"] = secret
        env["SERVING_TEST_DMODEL"] = str(D)
        env["HOROVOD_JOURNAL_DIR"] = str(jdir)
        env["HOROVOD_FAULTS"] = (
            f"serving.batch:crash:at=3,rank=1,"
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
             os.path.join("tests", "serving_chaos_worker.py")],
            cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        rng = np.random.RandomState(5)
        xs = [rng.randn(D).astype(np.float32) for _ in range(60)]
        futs = []
        for x in xs:
            futs.append(fe.submit(x))
            time.sleep(0.05)
        for x, f in zip(xs, futs):
            np.testing.assert_allclose(
                f.result(timeout=300), _expect(x),
                rtol=1e-5, atol=1e-5)
        s = fe.stats()
    finally:
        fe.close()
        if p is not None:
            try:
                out, _ = p.communicate(timeout=420)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
    assert p.returncode == 0, out
    assert s["completed"] == 60 and s["failed"] == 0
    assert s["dropped"] == 0
    assert s["retries"] >= 1
    retried = [e for e in _journal_events(tmp_path)
               if e["type"] == "batch_retried"]
    assert retried, "mid-batch crash must journal the retry"
    report = journal.incident_report(str(jdir))
    assert report["summary"]["recoveries"] >= 1
    rec = report["recoveries"][0]
    assert rec["cause"]["rank"] == 1, rec
    assert rec["cause"]["kind"] == "crash"
    assert rec["cause"]["seam"] == "serving.batch:crash"
