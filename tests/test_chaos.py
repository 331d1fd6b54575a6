"""Chaos tests: the elastic stack under injected fault schedules
(HOROVOD_FAULTS through the real seams — wire frames, rendezvous HTTP,
discovery polls, commit boundaries). Real subprocesses, no mocks, same
harness as test_elastic.py.

Two tiers: fast FIXED-SEED schedules run in tier-1 (a rotted fault
seam or recovery path fails CI immediately), and a randomized soak is
marked `slow` for the long lane."""

import os
import subprocess
import sys
import time

import pytest

from tests.test_elastic import (REPO, launch, make_env, read_logs,
                                write_discovery)

_NO_MULTIPROC = ("this jaxlib's CPU backend cannot run cross-process "
                 "collectives (affects every multiprocess "
                 "integration test)")


@pytest.fixture(scope="module")
def multiproc_backend():
    """Cheap capability probe, shared by the chaos runs: one tiny
    2-rank allreduce. Without it, an incapable backend (the same gate
    test_metrics.py skips on) would burn a full reset-limit's worth
    of gang restarts PER chaos test before we could tell."""
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


def _skip_if_no_multiproc(out, returncode):
    """In-run fallback for the same capability gate."""
    if returncode != 0 and \
            "Multiprocess computations aren't implemented" in out:
        pytest.skip(_NO_MULTIPROC)


def _chaos_env(tmp_path, steps, sleep, spec, seed=7, heartbeat=None):
    env = make_env(tmp_path, steps=steps, sleep=sleep)
    env["HOROVOD_FAULTS"] = spec
    env["HOROVOD_FAULTS_SEED"] = str(seed)
    env["HOROVOD_LOG_LEVEL"] = "info"
    if heartbeat is not None:
        env["HOROVOD_ELASTIC_HEARTBEAT_TIMEOUT"] = str(heartbeat)
    return env


@pytest.mark.integration
class TestChaosFixedSeed:
    def test_crash_at_step_gang_restart(self, tmp_path, multiproc_backend):
        """Injected crash-at-step-N (rank 1 hard-exits inside its 4th
        commit) plus low-probability wire drops: the driver
        gang-restarts and the job trains to completion, with the fired
        fault and the reset visible in the captured logs."""
        script = write_discovery(tmp_path, "echo localhost:2")
        latch = str(tmp_path / "crash.latch")
        env = _chaos_env(
            tmp_path, steps=12, sleep=0.15,
            spec=(f"elastic.step:crash:at=4,rank=1,once={latch};"
                  "wire.send:drop:p=0.1"))
        p = launch(script, env, extra=("--reset-limit", "3"))
        out, _ = p.communicate(timeout=420)
        _skip_if_no_multiproc(out, p.returncode)
        assert p.returncode == 0, out
        lines = read_logs(tmp_path)
        assert sum("done" in ln for ln in lines) == 2, (lines, out)
        # the schedule fired: the crash was injected (not a natural
        # death) and the driver recorded exactly one reset for it
        assert "faults: firing crash at elastic.step" in out, out
        assert os.path.exists(latch), "crash latch never created"
        assert "worker failure" in out, out
        assert "(reset 1)" in out, out
        # progress preservation across the injected crash: the rank
        # died inside commit 4, so the snapshot holds step >= 3 and
        # "step 1" may only ever come from the first incarnation
        step1 = [ln for ln in lines if ln.startswith("step 1 ")]
        assert len(step1) <= 2, (step1, lines)

    def test_hung_worker_detected_and_gang_restarted(self, tmp_path, multiproc_backend):
        """Injected livelock: rank 1 parks forever (heartbeat pacer
        stopped, the signature of a worker hung while holding
        everything). The liveness detector sees the stale heartbeat,
        kills the worker, and the ordinary hard-failure path restarts
        the gang — the job completes instead of stalling forever."""
        script = write_discovery(tmp_path, "echo localhost:2")
        latch = str(tmp_path / "hang.latch")
        env = _chaos_env(
            tmp_path, steps=10, sleep=0.1,
            spec=f"elastic.step:hang:at=3,rank=1,once={latch}",
            heartbeat=4)
        p = launch(script, env, extra=("--reset-limit", "3"))
        out, _ = p.communicate(timeout=420)
        _skip_if_no_multiproc(out, p.returncode)
        assert p.returncode == 0, out
        lines = read_logs(tmp_path)
        assert sum("done" in ln for ln in lines) == 2, (lines, out)
        assert "faults: firing hang at elastic.step" in out, out
        assert "heartbeat stale" in out, out
        assert "killing hung worker" in out, out
        assert "worker failure" in out, out


def test_faults_disabled_is_default_noop(tmp_path, hvd_single):
    """With HOROVOD_FAULTS unset the seams are inert: a normal
    allreduce fires nothing and the fired counter stays flat (the
    per-call overhead bound lives in test_faults.py)."""
    import jax.numpy as jnp
    from horovod_tpu import faults
    from horovod_tpu.metrics import REGISTRY
    assert not faults.active()
    snap_before = REGISTRY.snapshot().get("hvd_faults_fired_total", {})
    hvd_single.allreduce(jnp.ones(64), name="noop_chaos")
    snap_after = REGISTRY.snapshot().get("hvd_faults_fired_total", {})
    assert snap_before == snap_after


@pytest.mark.slow
@pytest.mark.integration
@pytest.mark.parametrize("seed", [1, 2])
def test_chaos_soak_randomized_schedule(tmp_path, seed, multiproc_backend):
    """Randomized (but seeded, hence replayable) soak: probabilistic
    wire drops, flaky rendezvous HTTP, discovery outages, dispatch
    delays, AND a deterministic crash — all at once, against a live
    2-rank elastic run with the liveness detector armed. The job must
    still train to completion. On failure, re-run with the printed
    spec + seed to reproduce the exact schedule."""
    script = write_discovery(tmp_path, "echo localhost:2")
    latch = str(tmp_path / f"soak{seed}.latch")
    spec = (f"elastic.step:crash:at=5,rank=1,once={latch};"
            "wire.send:drop:p=0.1;"
            "rendezvous.http:error:p=0.1;"
            "discovery.poll:error:p=0.2;"
            "dispatch.entry:delay:ms=20,p=0.05")
    env = _chaos_env(tmp_path, steps=16, sleep=0.15, spec=spec,
                     seed=seed, heartbeat=8)
    p = launch(script, env, extra=("--reset-limit", "6"))
    t0 = time.time()
    out, _ = p.communicate(timeout=540)
    _skip_if_no_multiproc(out, p.returncode)
    assert p.returncode == 0, (
        f"soak failed (reproduce: HOROVOD_FAULTS={spec!r} "
        f"HOROVOD_FAULTS_SEED={seed})\n{out}")
    lines = read_logs(tmp_path)
    assert sum("done" in ln for ln in lines) == 2, (lines, out)
    assert "faults: firing" in out, out
    print(f"soak seed={seed} survived in {time.time() - t0:.0f}s")
