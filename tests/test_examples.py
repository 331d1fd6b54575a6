"""Smoke-run every BASELINE example config (reference: the CI matrix
runs examples/ as tests; SURVEY.md §6 configs 1-5)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_example(script, args=(), np_=0, timeout=300, env_extra=None):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if env_extra:
        env.update(env_extra)
    if np_:
        cmd = [sys.executable, "-m", "horovod_tpu.runner", "-np",
               str(np_), sys.executable,
               os.path.join("examples", script), *args]
    else:
        cmd = [sys.executable, os.path.join("examples", script), *args]
    return subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)


@pytest.mark.integration
class TestExamples:
    def test_mnist_single(self):
        r = run_example("mnist_mlp.py", ["--epochs", "2"])
        assert r.returncode == 0, r.stdout + r.stderr
        assert "final train accuracy" in r.stdout

    def test_mnist_two_proc(self):
        r = run_example("mnist_mlp.py", ["--epochs", "1"], np_=2)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "epoch 0" in r.stdout

    def test_flax_train_state_two_proc(self):
        """The flax-idiom sugar path (DistributedTrainState.create)
        trains to accuracy at 2 ranks with rank-different init erased
        by the built-in broadcast."""
        r = run_example("flax_train_state.py", ["--epochs", "2"],
                        np_=2)
        assert r.returncode == 0, r.stdout + r.stderr
        acc = float(r.stdout.split("final train accuracy:")[1]
                    .strip().split()[0])
        assert acc > 0.9, r.stdout

    def test_torch_mnist_two_proc(self):
        """The reference's canonical torch script, one changed import
        (the torch frontend binding), trains to accuracy at 2 ranks."""
        r = run_example("torch_mnist.py", ["--epochs", "2"], np_=2)
        assert r.returncode == 0, r.stdout + r.stderr
        acc = float(r.stdout.split("final train accuracy:")[1]
                    .strip().split()[0])
        assert acc > 0.9, r.stdout

    def test_pipelined_two_proc(self):
        """The pipelined apply-then-grad recipe trains to accuracy
        through the negotiated grouped allreduce at 2 ranks."""
        r = run_example("pipelined_mlp.py", ["--epochs", "3"], np_=2)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "final train accuracy" in r.stdout
        acc = float(r.stdout.split("final train accuracy:")[1]
                    .strip().split()[0])
        assert acc > 0.9, r.stdout

    def test_resnet_synthetic(self):
        r = run_example("resnet50_synthetic.py",
                        ["--batch-size", "2", "--num-iters", "2",
                         "--num-warmup", "1", "--image-size", "32",
                         "--fp32"])
        assert r.returncode == 0, r.stdout + r.stderr
        assert "Img/sec" in r.stdout

    def test_bert_fp16_fusion(self):
        r = run_example("bert_large_pretraining.py",
                        ["--steps", "2", "--batch-size", "2",
                         "--seq-len", "32"], np_=2)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "gradient tensors fused via fp16" in r.stdout

    def test_llama_adasum(self):
        r = run_example("llama2_7b_dp.py",
                        ["--steps", "2", "--batch-size", "2",
                         "--seq-len", "32"], np_=2)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "Adasum+fp16" in r.stdout

    def test_elastic_resnet(self, tmp_path):
        disc = tmp_path / "d.sh"
        disc.write_text("#!/bin/sh\necho localhost:2\n")
        disc.chmod(0o755)
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get(
            "PYTHONPATH", "")
        r = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner",
             "--host-discovery-script", str(disc),
             "--min-num-proc", "1",
             sys.executable, os.path.join("examples",
                                          "elastic_resnet50.py"),
             "--epochs", "1", "--batches-per-epoch", "2",
             "--image-size", "32", "--batch-size", "2",
             "--snapshot", str(tmp_path / "snap.bin")],
            cwd=REPO, env=env, capture_output=True, text=True,
            # ~230s alone (two CPU ResNet compiles); leave headroom
            # for a loaded machine running the full suite.
            timeout=600)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "elastic training complete" in r.stdout


@pytest.mark.integration
class TestParallelismExamples:
    """SP/EP showcase examples on the 8-device virtual CPU mesh."""

    def test_ring_attention_long_context(self):
        r = run_example(
            "ring_attention_long_context.py",
            ["--seq-len", "256", "--heads", "2", "--head-dim", "16",
             "--verify"],
            env_extra={"XLA_FLAGS":
                       "--xla_force_host_platform_device_count=8"})
        assert r.returncode == 0, r.stdout + r.stderr
        assert "verified against full attention" in r.stdout

    def test_moe_expert_parallel(self):
        r = run_example(
            "moe_expert_parallel.py",
            ["--experts", "8", "--tokens", "64", "--d-model", "32",
             "--d-ff", "64"],
            env_extra={"XLA_FLAGS":
                       "--xla_force_host_platform_device_count=8"})
        assert r.returncode == 0, r.stdout + r.stderr
        assert "expert-parallel MoE OK" in r.stdout


@pytest.mark.integration
def test_serving_inference_chaos():
    """The serving example end to end with the injected mid-batch
    worker death: zero dropped requests is asserted inside the
    example and re-checked here."""
    r = run_example("serving_inference.py",
                    ["--chaos", "--requests", "60", "--qps", "400"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK (zero dropped requests)" in r.stdout
    assert "dropped=0" in r.stdout
