"""Callback layer tests (reference: horovod/_keras/callbacks.py —
BroadcastGlobalVariablesCallback / MetricAverageCallback /
LearningRateWarmupCallback / LearningRateScheduleCallback; the BERT
BASELINE config drives these)."""

import subprocess
import sys
import os

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.callbacks import (BroadcastParametersCallback,
                                   CallbackContext, CallbackList,
                                   LearningRateScheduleCallback,
                                   LearningRateWarmupCallback,
                                   MetricAverageCallback,
                                   lr_scale_schedule,
                                   multiplier_schedule,
                                   warmup_schedule)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestLRCallbacks:
    def test_warmup_ramp(self):
        ctx = CallbackContext()
        cb = LearningRateWarmupCallback(warmup_epochs=4,
                                        target_scale=8.0)
        scales = []
        for e in range(6):
            cb.on_epoch_begin(e, ctx)
            scales.append(ctx.lr_scale)
        # linear ramp 1 -> 8 over 4 epochs, then flat at 8
        np.testing.assert_allclose(scales,
                                   [2.75, 4.5, 6.25, 8.0, 8.0, 8.0])

    def test_warmup_defaults_to_size(self, hvd_single):
        ctx = CallbackContext()
        cb = LearningRateWarmupCallback(warmup_epochs=1)
        cb.on_epoch_begin(0, ctx)
        assert ctx.lr_scale == float(hvd_single.size())

    def test_schedule_staircase_window(self):
        ctx = CallbackContext()
        warm = LearningRateWarmupCallback(warmup_epochs=1,
                                          target_scale=4.0)
        decay = LearningRateScheduleCallback(0.5, start_epoch=2)
        cbs = CallbackList([warm, decay])
        seen = []
        for e in range(4):
            cbs.on_epoch_begin(e, ctx)
            seen.append(ctx.lr_scale)
        # warmup sets scale to 4 every epoch; decay multiplies after it
        np.testing.assert_allclose(seen, [4.0, 4.0, 2.0, 2.0])

    def test_schedule_callable_multiplier(self):
        ctx = CallbackContext()
        cb = LearningRateScheduleCallback(lambda e: 0.1 ** e,
                                          start_epoch=1, end_epoch=3)
        for e in range(4):
            ctx.lr_scale = 1.0
            cb.on_epoch_begin(e, ctx)
            want = 0.1 ** e if 1 <= e < 3 else 1.0
            assert ctx.lr_scale == pytest.approx(want)

    def test_lr_scale_schedule_reads_live(self):
        ctx = CallbackContext()
        sched = lr_scale_schedule(ctx, 0.01)
        assert float(sched(0)) == pytest.approx(0.01)
        ctx.lr_scale = 8.0
        assert float(sched(123)) == pytest.approx(0.08)


class TestOptaxSchedules:
    def test_warmup_schedule_pure(self):
        s = warmup_schedule(0.1, warmup_steps=10, target_scale=4.0)
        assert float(s(0)) == pytest.approx(0.1 * (1 + 3 * 0.1))
        assert float(s(9)) == pytest.approx(0.4)
        assert float(s(100)) == pytest.approx(0.4)

    def test_warmup_schedule_with_after(self):
        after = lambda step: 0.4 * 0.5 ** (step // 10)  # noqa: E731
        s = warmup_schedule(0.1, warmup_steps=10, target_scale=4.0,
                            after=after)
        assert float(s(9)) == pytest.approx(0.4)
        assert float(s(10)) == pytest.approx(0.4)
        assert float(s(20)) == pytest.approx(0.2)

    def test_multiplier_schedule(self):
        s = multiplier_schedule(1.0, [(10, 0.1), (20, 0.1)])
        assert float(s(0)) == pytest.approx(1.0)
        assert float(s(10)) == pytest.approx(0.1)
        assert float(s(25)) == pytest.approx(0.01)

    def test_composes_with_optax(self, hvd_single):
        import optax
        opt = optax.adamw(warmup_schedule(1e-3, 5, target_scale=2.0))
        params = {"w": jnp.ones(3)}
        st = opt.init(params)
        up, st = opt.update({"w": jnp.ones(3)}, st, params)
        assert jnp.all(jnp.isfinite(up["w"]))


class TestBroadcastAndMetrics:
    def test_broadcast_callback_single(self, hvd_single):
        ctx = CallbackContext(params={"w": jnp.arange(4.0)},
                              opt_state={"m": jnp.zeros(4)})
        BroadcastParametersCallback().on_train_begin(ctx)
        np.testing.assert_allclose(np.asarray(ctx.params["w"]),
                                   np.arange(4.0))

    def test_metric_average_single(self, hvd_single):
        cb = MetricAverageCallback()
        out = cb.on_epoch_end(0, {"loss": 2.5, "tag": "x"},
                              CallbackContext())
        assert out["loss"] == pytest.approx(2.5)
        assert out["tag"] == "x"


@pytest.mark.integration
def test_bert_example_with_callbacks(multiproc_data_plane):
    """BASELINE config 3 driver: the BERT example runs 2-process with
    warmup + broadcast + metric averaging through the callback API.
    (multiproc_data_plane: the on_train_begin parameter broadcast is
    a cross-process XLA collective, absent on this image's jaxlib —
    the failure mode is the data plane, not the example or the
    callbacks, so it shares the one probe-gated skip.)"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         sys.executable, os.path.join("examples",
                                      "bert_large_pretraining.py"),
         "--epochs", "2", "--steps", "2", "--batch-size", "2",
         "--seq-len", "16", "--warmup-epochs", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "lr_scale=2.00" in r.stdout, r.stdout
    assert "avg loss" in r.stdout
