"""Elastic integration tests — the reference's key techniques
(SURVEY.md §4): a discovery script that IS a rewritable temp file, and
rank suicide for failure injection. Real subprocesses, no mocks."""

import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_env(tmp_path, steps=30, sleep=0.2):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["ELASTIC_TEST_LOG"] = str(tmp_path / "progress")
    env["ELASTIC_TEST_STEPS"] = str(steps)
    env["ELASTIC_TEST_SLEEP"] = str(sleep)
    return env


def write_discovery(tmp_path, content):
    script = tmp_path / "discover.sh"
    script.write_text(f"#!/bin/sh\n{content}\n")
    script.chmod(0o755)
    return script


def read_logs(tmp_path):
    lines = []
    for p in tmp_path.glob("progress.*"):
        lines += p.read_text().splitlines()
    return lines


def launch(script, env, extra=(), worker="elastic_worker.py"):
    return subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner",
         "--host-discovery-script", str(script),
         "--min-num-proc", "1",
         "--host-change-detection-interval", "0.5",
         *extra,
         sys.executable, os.path.join("tests", worker)],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)


@pytest.mark.integration
class TestElastic:
    def test_unit_driver_pieces(self, tmp_path):
        """Discovery parse + rendezvous endpoints (no processes)."""
        from horovod_tpu.runner.elastic import (HostDiscoveryScript,
                                                RendezvousServer)
        s = write_discovery(tmp_path, "echo localhost:2")
        d = HostDiscoveryScript(str(s))
        hosts = d.find_available_hosts_and_slots()
        assert [(h.host, h.slots) for h in hosts] == [("localhost", 2)]

        rs = RendezvousServer()
        rs.publish(1, {("localhost", 0): {"HOROVOD_RANK": "0"}})
        import urllib.request
        with urllib.request.urlopen(
                f"http://localhost:{rs.port}/rank/localhost/0") as r:
            assert json.loads(r.read()) == {"HOROVOD_RANK": "0"}
        with urllib.request.urlopen(
                f"http://localhost:{rs.port}/world") as r:
            assert json.loads(r.read())["epoch"] == 1
        req = urllib.request.Request(
            f"http://localhost:{rs.port}/notify/localhost/0",
            data=b'{"port": 1234}', method="PUT")
        urllib.request.urlopen(req).read()
        assert rs.notify_ports() == {("localhost", 0): 1234}
        rs.stop()

    def test_static_elastic_run_completes(self, tmp_path):
        script = write_discovery(tmp_path, "echo localhost:2")
        env = make_env(tmp_path, steps=6, sleep=0.05)
        p = launch(script, env)
        out, _ = p.communicate(timeout=180)
        assert p.returncode == 0, out
        lines = read_logs(tmp_path)
        assert sum("done" in ln for ln in lines) == 2, lines
        assert any("world 2" in ln for ln in lines)

    def _scale_up(self, tmp_path, worker, steps):
        """Shared scale-up sequence: start at 2 procs, grow the
        discovery file to 3 once 2-proc progress is OBSERVED (a fixed
        sleep races worker startup on a loaded machine), assert
        committed progress never regresses below the resize point."""
        hosts_file = tmp_path / "hosts.txt"
        hosts_file.write_text("localhost:2\n")
        script = write_discovery(tmp_path, f"cat {hosts_file}")
        env = make_env(tmp_path, steps=steps, sleep=0.25)
        p = launch(script, env, worker=worker)
        try:
            deadline = time.time() + 240
            while time.time() < deadline:
                if any("world 2" in ln for ln in read_logs(tmp_path)):
                    break
                if p.poll() is not None:
                    break
                time.sleep(0.5)
            hosts_file.write_text("localhost:3\n")
            out, _ = p.communicate(timeout=420)
        finally:
            if p.poll() is None:
                p.kill()
                out = p.communicate()[0]
        assert p.returncode == 0, out
        lines = read_logs(tmp_path)
        assert any("world 2" in ln for ln in lines), (lines, out)
        assert any("world 3" in ln for ln in lines), (lines, out)
        dones = [ln for ln in lines if "done" in ln]
        assert len(dones) == 3, (dones, out)
        # committed steps never regress below the resize point: the
        # max step logged in world 2 must be <= min step logged by the
        # new world's rank 0 continuation + 1
        w2 = [int(ln.split()[1]) for ln in lines
              if ln.startswith("step") and "world 2" in ln]
        w3 = [int(ln.split()[1]) for ln in lines
              if ln.startswith("step") and "world 3" in ln]
        assert w2 and w3 and min(w3) >= max(w2) - 1, (max(w2), min(w3))

    def test_graceful_scale_up(self, tmp_path):
        """Start at 2 procs; mid-run the discovery file grows to 3;
        workers resize without losing committed progress."""
        self._scale_up(tmp_path, "elastic_worker.py", steps=40)

    def test_torch_frontend_elastic_scale_up(self, tmp_path):
        """The torch frontend rides the same elastic machinery:
        TorchState + hook optimizer survive a mid-run scale-up with
        committed progress intact and identical final weights (the
        worker asserts weight agreement before logging done).
        steps=40 like the jax variant: the respawned workers pay
        torch-import startup, and fewer steps can run out before the
        new world-3 member joins on a loaded host (observed flake)."""
        self._scale_up(tmp_path, "elastic_worker_torch.py", steps=40)

    def test_resize_rebuilds_wide_mesh(self, tmp_path):
        """Elastic resize x multi-chip processes: after a scale-down,
        the device-spanning ('proc','dev') eager path must rebuild
        for the NEW world size (the wide-mesh caches live on
        ProcessSet instances that re-init replaces) — every step
        asserts path == wide with the current world in the mesh."""
        hosts_file = tmp_path / "hosts.txt"
        hosts_file.write_text("localhost:3\n")
        script = write_discovery(tmp_path, f"cat {hosts_file}")
        env = make_env(tmp_path, steps=30, sleep=0.25)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["ELASTIC_TEST_WIDE"] = "1"
        p = launch(script, env)
        try:
            deadline = time.time() + 240
            while time.time() < deadline:
                if any("wide ok world 3" in ln
                       for ln in read_logs(tmp_path)):
                    break
                if p.poll() is not None:
                    break
                time.sleep(0.5)
            hosts_file.write_text("localhost:2\n")
            out, _ = p.communicate(timeout=300)
        finally:
            if p.poll() is None:
                p.kill()
                out = p.communicate()[0]  # reap + keep the output
        assert p.returncode == 0, out
        lines = read_logs(tmp_path)
        # wide engaged at BOTH world sizes, 2 devices per process
        # (the worker asserts mesh_shape == {proc: size, dev: 2} on
        # every step, so one line per size proves the rebuild).
        assert any("wide ok world 3 devs 6" in ln for ln in lines), \
            lines[-10:]
        assert any("wide ok world 2 devs 4" in ln for ln in lines), \
            lines[-10:]

    def test_graceful_scale_down(self, tmp_path):
        """Start at 3 procs; mid-run the discovery file shrinks to 2.
        The removed rank drains voluntarily (clean exit at its commit
        boundary — no SIGTERM mid-collective), survivors resize
        without a gang restart, and committed progress carries over
        (reference: horovod/runner/elastic/driver.py host-removal
        path treats remove symmetrically with add)."""
        hosts_file = tmp_path / "hosts.txt"
        hosts_file.write_text("localhost:3\n")
        script = write_discovery(tmp_path, f"cat {hosts_file}")
        env = make_env(tmp_path, steps=40, sleep=0.25)
        env["HOROVOD_LOG_LEVEL"] = "info"
        p = launch(script, env)
        try:
            deadline = time.time() + 240
            while time.time() < deadline:
                if any("world 3" in ln for ln in read_logs(tmp_path)):
                    break
                if p.poll() is not None:
                    break
                time.sleep(0.5)
            hosts_file.write_text("localhost:2\n")
            # Graceful-resize latency ceiling (round-3 verdict Next
            # #9): the shrunken world must be RUNNING within a bound —
            # the drain + re-init path may not lean on a long init
            # timeout. 90 s is generous for this loaded 1-core box;
            # the healthy path takes a few seconds.
            t_shrink = time.time()
            resize_s = None
            while time.time() - t_shrink < 240:
                if any("world 2" in ln for ln in read_logs(tmp_path)):
                    resize_s = time.time() - t_shrink
                    break
                if p.poll() is not None:
                    break
                time.sleep(0.5)
            out, _ = p.communicate(timeout=420)
        finally:
            if p.poll() is None:
                p.kill()
                out = p.communicate()[0]
        assert p.returncode == 0, out
        lines = read_logs(tmp_path)
        assert any("world 3" in ln for ln in lines), lines
        assert any("world 2" in ln for ln in lines), lines
        assert resize_s is not None and resize_s < 90, (
            f"graceful resize took {resize_s}s (ceiling 90s)")
        # graceful: drain, not failure — no gang restart anywhere
        assert "worker failure" not in out, out
        assert "draining removed rank" in out, out
        # the drained worker exits voluntarily with rc=0
        assert "exited (rc=0)" in out, out
        # exactly the 2 surviving ranks finish the job
        dones = [ln for ln in lines if "done" in ln]
        assert len(dones) == 2, (dones, out)
        assert all("world 2" in ln for ln in dones), dones
        # progress continuity across the shrink: the new world resumes
        # at (or one past) the old world's last committed step
        w3 = [int(ln.split()[1]) for ln in lines
              if ln.startswith("step") and "world 3" in ln]
        w2 = [int(ln.split()[1]) for ln in lines
              if ln.startswith("step") and "world 2" in ln]
        assert w3 and w2 and min(w2) >= max(w3) - 1, (max(w3), min(w2))

    def test_scale_down_then_up_churn(self, tmp_path):
        """Membership churn: 3 -> 2 -> 3. The re-added slot joins the
        running job (fresh process, synced by rank 0) and all three
        ranks complete (reference: remove-then-re-add cycle over the
        same HostsUpdatedInterrupt machinery)."""
        hosts_file = tmp_path / "hosts.txt"
        hosts_file.write_text("localhost:3\n")
        script = write_discovery(tmp_path, f"cat {hosts_file}")
        env = make_env(tmp_path, steps=60, sleep=0.25)
        env["HOROVOD_LOG_LEVEL"] = "info"
        p = launch(script, env)
        out = ""
        try:
            def wait_for(pred, timeout=240):
                deadline = time.time() + timeout
                while time.time() < deadline:
                    if pred(read_logs(tmp_path)) or p.poll() is not None:
                        return
                    time.sleep(0.5)

            wait_for(lambda ls: any("world 3" in ln for ln in ls))
            hosts_file.write_text("localhost:2\n")
            wait_for(lambda ls: any("world 2" in ln for ln in ls))
            hosts_file.write_text("localhost:3\n")
            out, _ = p.communicate(timeout=600)
        finally:
            if p.poll() is None:
                p.kill()
                out = p.communicate()[0]
            if os.environ.get("ELASTIC_TEST_DUMP"):
                with open(os.environ["ELASTIC_TEST_DUMP"], "w") as f:
                    f.write(out or "")
        assert p.returncode == 0, out
        lines = read_logs(tmp_path)
        assert any("world 2" in ln for ln in lines), lines
        assert "worker failure" not in out, out
        # the job ends back at world 3, with all three ranks finishing
        dones = [ln for ln in lines if "done" in ln]
        assert len(dones) == 3, (dones, out)
        assert all("world 3" in ln for ln in dones), dones

    def test_scale_down_below_min_np_is_ignored(self, tmp_path):
        """Discovery shrinking under --min-num-proc must NOT resize
        the job below the floor: the world stays at 3 and completes
        (reference: ElasticDriver honors min_num_proc on the way
        down, not just at startup)."""
        hosts_file = tmp_path / "hosts.txt"
        hosts_file.write_text("localhost:3\n")
        script = write_discovery(tmp_path, f"cat {hosts_file}")
        env = make_env(tmp_path, steps=25, sleep=0.25)
        p = launch(script, env, extra=("--min-num-proc", "3"))
        try:
            deadline = time.time() + 240
            while time.time() < deadline:
                if any("world 3" in ln for ln in read_logs(tmp_path)):
                    break
                if p.poll() is not None:
                    break
                time.sleep(0.5)
            hosts_file.write_text("localhost:2\n")
            out, _ = p.communicate(timeout=420)
        finally:
            if p.poll() is None:
                p.kill()
                out = p.communicate()[0]
        assert p.returncode == 0, out
        lines = read_logs(tmp_path)
        assert not any("world 2" in ln for ln in lines), lines
        dones = [ln for ln in lines if "done" in ln]
        assert len(dones) == 3, (dones, out)

    def test_worker_failure_gang_restart(self, tmp_path):
        """Rank suicide mid-run: the driver restarts the gang and
        training completes (snapshot-level recovery)."""
        script = write_discovery(tmp_path, "echo localhost:2")
        env = make_env(tmp_path, steps=12, sleep=0.2)
        env["ELASTIC_TEST_DIE_AT"] = "4"  # rank 1 exits at step 4
        p = launch(script, env, extra=("--reset-limit", "3"))
        out, _ = p.communicate(timeout=420)
        assert p.returncode == 0, out
        lines = read_logs(tmp_path)
        assert sum("done" in ln for ln in lines) >= 2, (lines, out)
        # progress preservation: the rank died AFTER logging step 4 but
        # BEFORE committing it, so the snapshot holds step 3 and the
        # restarted gang must resume at step >= 4 — "step 1" may only
        # ever be logged by the first incarnation's 2 ranks.
        step1 = [ln for ln in lines if ln.startswith("step 1 ")]
        assert len(step1) <= 2, (step1, lines)


def test_jax_state_orbax_snapshot_roundtrip(tmp_path, hvd_single):
    """Orbax snapshot backend: async versioned commits, restart-style
    load (SURVEY.md §5.4 'integrate, don't rebuild')."""
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.elastic.state import JaxState
    path = str(tmp_path / "snap")
    st = JaxState(params={"w": jnp.arange(4.0)},
                  opt_state={"m": jnp.zeros(4)},
                  snapshot_path=path, snapshot_backend="orbax",
                  step=0, epoch=0)
    assert not st.maybe_load_snapshot()   # nothing yet; arms writes
    st.params = {"w": jnp.full(4, 7.0)}
    st.step = 3
    st.commit()
    st.params = {"w": jnp.full(4, 9.0)}   # uncommitted progress
    st.step = 4
    st.commit()
    # ensure async write landed before simulating the restart
    st._orbax().wait_until_finished()

    # "restarted gang": fresh state object, same path
    st2 = JaxState(params={"w": jnp.zeros(4)},
                   opt_state={"m": jnp.zeros(4)},
                   snapshot_path=path, snapshot_backend="orbax",
                   step=0, epoch=0)
    assert st2.maybe_load_snapshot()
    np.testing.assert_allclose(np.asarray(st2.params["w"]),
                               np.full(4, 9.0))
    assert st2.step == 4
    # restore() rolls back to the loaded commit
    st2.params = {"w": jnp.full(4, 1.0)}
    st2.restore()
    np.testing.assert_allclose(np.asarray(st2.params["w"]),
                               np.full(4, 9.0))


@pytest.mark.integration
def test_elastic_remote_spawn_via_ssh_shim(tmp_path):
    """Elastic driver's remote-spawn branch through the fake-ssh shim
    (see test_runner._write_fake_ssh): workers on 'fakehost' are
    spawned with the secret on stdin and the full (blocklist-filtered)
    env inlined; the job completes and the secret never rides argv."""
    import socket
    from tests.test_runner import _write_fake_ssh
    _, log = _write_fake_ssh(tmp_path)
    # The real hostname: not in LOCALHOSTS (so the ssh branch fires)
    # but resolvable, which elastic needs — rank 0 lives on the
    # "remote" host and every worker must reach its coordinator.
    host = socket.gethostname()
    script = write_discovery(tmp_path, f"echo {host}:2")
    env = make_env(tmp_path, steps=4, sleep=0.05)
    env["PATH"] = str(tmp_path) + os.pathsep + env["PATH"]
    p = launch(script, env)
    out, _ = p.communicate(timeout=420)
    assert p.returncode == 0, out
    lines = read_logs(tmp_path)
    assert sum("done" in ln for ln in lines) == 2, (lines, out)
    argv = log.read_text()
    assert "HOROVOD_SECRET=" not in argv
    assert "read -r __HVD_ENV" in argv


class TestElasticSampler:
    """Resharding-aware sampler (reference:
    horovod/torch/elastic/sampler.py ElasticSampler) — pure-logic
    tests with the world faked via attributes, the reference suite's
    own technique for sampler coverage."""

    def _mk(self, n=20, rank=0, world=2, shuffle=False):
        # hvd is not initialized in these unit tests, so _reset keeps
        # the injected rank/world (the reference suite fakes the world
        # the same way for sampler coverage).
        from horovod_tpu.elastic.sampler import ElasticSampler
        s = ElasticSampler(n, shuffle=shuffle)
        s.rank, s.world_size = rank, world
        s._reset()
        return s

    def test_even_sharding_no_overlap(self):
        a = self._mk(rank=0)
        b = self._mk(rank=1)
        ia, ib = list(a), list(b)
        assert len(ia) == len(ib) == 10
        assert not set(ia) & set(ib)
        assert sorted(ia + ib) == list(range(20))

    def test_resharding_preserves_unprocessed(self):
        """After processing 2 batches and growing 2 -> 4 ranks, the
        remaining pool is exactly the unprocessed indices, split with
        no repeats across the new world."""
        ranks = [self._mk(rank=r, world=2) for r in range(2)]
        done = []
        for s in ranks:
            s.record_batch(0, 3)
            s.record_batch(1, 3)
            done += s.processed_indices
        assert len(set(done)) == 12
        new = []
        for r in range(4):
            s = ranks[r % 2]
            import copy
            s4 = copy.copy(s)
            s4.processed_indices = list(done)
            s4.rank, s4.world_size = r, 4
            s4.reset_from_state()
            new.append(list(s4))
        flat = [i for idx in new for i in idx]
        assert not set(flat) & set(done)      # nothing repeated
        assert len(set(flat)) == len(flat)    # no cross-rank overlap
        assert set(flat) == set(range(20)) - set(done)  # none dropped

    def test_set_epoch_reshuffles_and_restores_full_pool(self):
        s = self._mk(shuffle=True)
        s.record_batch(0, 5)
        assert len(s.processed_indices) == 5
        order1 = list(s.remaining_indices)
        s.set_epoch(1)
        assert len(s.remaining_indices) == 20
        s2 = self._mk(shuffle=True)
        s2.set_epoch(1)
        assert s.remaining_indices == s2.remaining_indices
        assert s.remaining_indices != order1

    def test_ragged_tail_dropped_evenly(self):
        a = self._mk(n=21, rank=0, world=2)
        b = self._mk(n=21, rank=1, world=2)
        assert len(list(a)) == len(list(b)) == 10
        assert len(a) == 10
