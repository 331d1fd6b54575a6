"""The loader finds every kind of file by name, a run without the
cell's chips fails, and the FLOP functions equal a hand count."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import peaks, run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(ROOT)

TINY = {
    "transformer": {
        "config": {
            "hidden_size": 64, "intermediate_size": 160,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 128,
            "rope_theta": 10000.0, "rms_norm_eps": 1e-6},
        "cell": {"batch_per_chip": 2, "seq": 32,
                 "rate_metric": "tokens_per_s_chip",
                 "sample": {"per_chip": 1, "seq": 16}}},
    "resnet": {
        "config": {
            "image_size": 32, "num_classes": 10, "stage_sizes": [1, 1],
            "num_filters": 8, "bn_epsilon": 1e-5},
        "cell": {"batch_per_chip": 4, "rate_metric": "images_per_s_chip",
                 "sample": {"per_chip": 4}}},
}

LAYER_METRIC = '''
NAME = "steps_counted"
UNIT = "steps"
LAYER = "test"
MOVES = "setup_s"


def compute(ctx):
    return len(ctx["step_s"])
'''


def drop_cell(tmp_path, family, chips):
    """A configuration, a cell and a per-layer metric as new files in a
    directory of their own, with the manifest that names them; drivers,
    adapters and references are the benchmark's."""
    root = tmp_path / "perfbench"
    for sub in ("configs", "workloads", "layer_metrics"):
        (root / sub).mkdir(parents=True)
    for sub in ("drivers", "models", "reference"):
        os.symlink(os.path.join(ROOT, sub), root / sub)
    tiny = TINY[family]
    (root / "configs" / "tiny.json").write_text(json.dumps(tiny["config"]))
    (root / "workloads" / "tiny.cell.json").write_text(json.dumps(dict(
        tiny["cell"], driver="jit_train", model=family, chips=chips,
        ring=2, traced_steps=2)))
    (root / "layer_metrics" / "steps_counted.py").write_text(LAYER_METRIC)
    rate = tiny["cell"]["rate_metric"]
    manifest = tmp_path / "BENCHMARK.json"
    manifest.write_text(json.dumps({
        "workloads": [{"name": "tiny.cell", "config": "tiny",
                       "traffic": "cell", "chips": chips}],
        "end_to_end": [{"name": n, "unit": "u"} for n in
                       (rate, "step_ms_p95", "peak_hbm_gb", "setup_s")],
        "per_layer": [
            {"name": "steps_counted", "unit": "steps"},
            {"name": "not_here", "unit": "u", "workloads": ["other"]}],
    }))
    return str(root), str(manifest)


@pytest.mark.parametrize("family,chips", [
    ("transformer", 1), ("transformer", 4), ("resnet", 1)])
def test_dropped_files_are_found_and_run(tmp_path, family, chips):
    import jax
    root, manifest = drop_cell(tmp_path, family, chips)
    devices = jax.devices()[:chips]
    result = run.run_cell("tiny.cell", 2**31 + 12345, 1.5, False, root=root,
                          manifest_path=manifest, devices=devices)
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 4
    assert set(result["metrics"]) == {
        TINY[family]["cell"]["rate_metric"], "step_ms_p95",
        "peak_hbm_gb", "setup_s"}
    assert result["device"]["count"] == chips
    with open(os.path.join(root, "out", "tiny.cell.window.json")) as f:
        first = json.load(f)

    # the same seed gives the same losses; per-layer metrics are read
    # by the dropped file, and only those of this cell
    again = run.run_cell("tiny.cell", 2**31 + 12345, 0.5, False, root=root,
                         manifest_path=manifest, devices=devices)
    assert again["correct"] is True
    with open(os.path.join(root, "out", "tiny.cell.window.json")) as f:
        second = json.load(f)
    n = min(len(first["losses"]), len(second["losses"]))
    assert first["warmup_losses"] == second["warmup_losses"]
    assert first["losses"][:n] == second["losses"][:n]


def test_command_line_refuses_a_machine_without_the_chips():
    out = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload",
         "mistral7b-l4.jit-dp1", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    assert '"metrics"' not in out.stdout


def test_unknown_device_kind_is_an_error():
    assert peaks.lookup("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert peaks.lookup("TPU v5 lite")["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError, match="cpu"):
        peaks.lookup("cpu")


def test_transformer_flops_equal_the_hand_count():
    model = run.load_module(ROOT, "models", "transformer")
    config = run.read_json(
        os.path.join(ROOT, "configs", "mistral7b-l4.json"))
    # By hand, Mistral-7B widths at 4 layers: per layer
    #   wq 4096*4096 + wk, wv 2 * 4096*1024 + wo 4096*4096 = 41,943,040
    #   gate, up, down 3 * 4096*14336                     = 176,160,768
    # 4 layers = 872,415,232; tied head 32000*4096 = 131,072,000;
    # 1,003,487,232 weights in matmuls (1,003.5 M parameters with the
    # 36,864 norm gains). Forward 2 FLOP a weight a token, plus QK^T
    # and PV: 2 * 2 * seq * 32 heads * 128 = 4 * 2048 * 4096 a layer,
    # unmasked half included. Backward twice the forward. Recompute
    # under remat is not counted.
    weights = 4 * (41_943_040 + 176_160_768) + 131_072_000
    by_hand = 6 * weights + 12 * 4 * 2048 * 4096
    assert by_hand == 6_423_576_576         # 6.42 GFLOP a token
    assert model.flops_per_unit(config, {"seq": 2048}) == by_hand


def test_resnet_flops_equal_the_hand_count():
    model = run.load_module(ROOT, "models", "resnet")
    config = run.read_json(os.path.join(ROOT, "configs", "resnet50.json"))
    # By hand, ResNet-50 v1.5 at 224 px, multiply-adds of the forward
    # pass (output side^2 * kernel^2 * c_in * c_out):
    macs = 112 * 112 * 49 * 3 * 64                      # 7x7 stem
    # stage (side after the stride, width f, blocks, input channels):
    for side, f, blocks, c_in in ((56, 64, 3, 64), (28, 128, 4, 256),
                                  (14, 256, 6, 512), (7, 512, 3, 1024)):
        first_side = side if f == 64 else 2 * side      # 1x1 before the stride
        macs += first_side ** 2 * c_in * f              # block 1: 1x1
        macs += side ** 2 * 9 * f * f                   #          3x3, strided
        macs += side ** 2 * f * 4 * f                   #          1x1
        macs += side ** 2 * c_in * 4 * f                #          shortcut
        macs += (blocks - 1) * side ** 2 * (
            4 * f * f + 9 * f * f + f * 4 * f)          # the other blocks
    macs += 2048 * 1000                                 # dense
    assert macs == 4_089_184_256                        # 4.09 GMAC forward
    assert model.flops_per_unit(config, {}) == 3 * 2 * macs  # 24.5 GFLOP
