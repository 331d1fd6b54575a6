"""Deterministic-execution-order assertion mode (HOROVOD_ORDER_CHECK)
— the runtime twin of the C++ TSAN stress's agreed-order assertion.
Reference anchor: controller.cc's identical-ResponseList guarantee
(SURVEY.md §5.2 calls for the rebuild to add this assertion mode)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestOrderCheckUnit:
    def test_digest_detects_divergence(self):
        from horovod_tpu.ops.order_check import OrderCheck
        a, b = OrderCheck(), OrderCheck()
        for n in ["x", "y", "z"]:
            a.record(n)
        for n in ["x", "z", "y"]:
            b.record(n)
        assert a.digest() != b.digest()
        assert a.count == b.count == 3

    def test_digest_matches_same_sequence(self):
        from horovod_tpu.ops.order_check import OrderCheck
        a, b = OrderCheck(), OrderCheck()
        for n in ["x", "y", "z"]:
            a.record(n)
            b.record(n)
        assert a.digest() == b.digest()

    def test_no_separator_confusion(self):
        # "ab"+"c" must not collide with "a"+"bc".
        from horovod_tpu.ops.order_check import OrderCheck
        a, b = OrderCheck(), OrderCheck()
        a.record("ab"); a.record("c")
        b.record("a"); b.record("bc")
        assert a.digest() != b.digest()


def test_single_process_check(tmp_path):
    import horovod_tpu as hvd
    import jax.numpy as jnp
    hvd.init(config_overrides={"HOROVOD_ORDER_CHECK": True})
    try:
        hvd.allreduce(jnp.ones(3), name="a")
        hvd.broadcast(jnp.ones(3), root_rank=0, name="b")
        n = hvd.check_execution_order()
        assert n >= 2
    finally:
        hvd.shutdown()


def test_disabled_raises(tmp_path):
    import horovod_tpu as hvd
    hvd.init()
    try:
        with pytest.raises(RuntimeError, match="HOROVOD_ORDER_CHECK"):
            hvd.check_execution_order()
    finally:
        hvd.shutdown()


@pytest.mark.integration
def test_two_proc_opposite_submission_order(multiproc_data_plane):
    """Ranks submit in opposite orders; the agreed execution order is
    still identical — the coordinator's core contract, asserted.
    (multiproc_data_plane: the worker's collectives dispatch through
    cross-process XLA, absent on this image's jaxlib.)"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         sys.executable, os.path.join("tests", "mp_worker_ordercheck.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("ORDER CHECK OK") == 2, r.stdout
