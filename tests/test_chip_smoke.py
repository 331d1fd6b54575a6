"""chip_smoke.py rehearsed on the CPU: every phase function at a tiny
size through the same code the chip runs, the refusal to run without a
TPU, and the compile-cache helper's placement rule. Nothing here is a
device measurement."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY_FLAGSHIP = dict(vocab=256, d_model=64, n_layers=2, n_heads=4,
                     d_ff=128, seq=128, batch=4)
TINY_RESNET = dict(batch=4, image=32, stages=(1, 1, 1, 1))


@pytest.fixture
def hvd_native():
    import horovod_tpu as hvd
    hvd.init(config_overrides={"HOROVOD_CONTROLLER": "native"})
    yield hvd
    hvd.shutdown()


@pytest.fixture(scope="module")
def counter():
    return chip_smoke.CompileCounter()


def test_flagship_jit_then_eager_agree(hvd_native, counter):
    assert chip_smoke.require_native_core() == "NativeCore"
    import jax
    from horovod_tpu.parallel.mesh import data_parallel_mesh
    # one device, as on the one-chip machine (conftest provisions 8)
    ref = chip_smoke.phase_flagship_jit(
        TINY_FLAGSHIP, steps=3, counter=counter,
        mesh=data_parallel_mesh(jax.devices()[:1]))
    assert len(ref["losses"]) == 3
    got = chip_smoke.phase_flagship_eager(ref["losses"], TINY_FLAGSHIP,
                                          steps=2, counter=counter)
    assert abs(got["losses"][0] - ref["losses"][0]) \
        <= chip_smoke.BF16_LOSS_TOL
    assert abs(got["pipelined_losses"][0] - ref["losses"][1]) \
        <= chip_smoke.BF16_LOSS_TOL


def test_resnet_jit_phase(hvd_native, counter):
    out = chip_smoke.phase_resnet_jit(TINY_RESNET, steps=3,
                                      counter=counter)
    assert out["losses"][-1] < out["losses"][0]


def test_pair_combine_matches_reference_and_cpu_is_refused(hvd_native):
    # Off the TPU the library's own dispatch entry interprets the
    # kernel: numerics are checked, and the phase still refuses
    # because no tpu_custom_call was compiled.
    rec = chip_smoke.pair_combine_case(1003)
    assert rec["tpu_custom_call"] is False
    assert rec["max_abs_err"] < 1e-3
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        chip_smoke.phase_adasum(sizes=(1003,))


def test_a_failed_check_raises(hvd_native, counter):
    with pytest.raises(AssertionError, match="did not fall"):
        chip_smoke.check_losses("x", [1.0, 2.0])
    with pytest.raises(AssertionError, match="non-finite"):
        chip_smoke.check_losses("x", [1.0, float("nan")])
    # a wrong reference loss fails the eager phase
    with pytest.raises(AssertionError, match="first-step loss"):
        chip_smoke.phase_flagship_eager([0.0, 0.0], TINY_FLAGSHIP,
                                        steps=2, counter=counter)


def _cpu_env(tmp_path, devices=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    if devices:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    return env


def test_script_refuses_the_cpu(tmp_path):
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                       env=_cpu_env(tmp_path), capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr


def test_script_with_a_raising_phase_prints_no_ok(tmp_path):
    """One phase made to raise (past the TPU gate, so the phase itself
    is what fails): non-zero exit, no result line."""
    code = (
        "import chip_smoke as cs\n"
        "cs.require_tpu = cs.device_record\n"
        "def boom(**kw):\n"
        "    raise AssertionError('phase made to raise')\n"
        "cs.phase_flagship_jit = boom\n"
        "raise SystemExit(cs.main([]))\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       env=_cpu_env(tmp_path), capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "phase made to raise" in r.stderr


_LEG = (
    "import sys, chip_smoke as cs\n"
    "cs.require_tpu = cs.device_record\n"
    "tiny = dict(vocab=256, d_model=64, n_layers=2, n_heads=4,"
    " d_ff=128, seq=128, batch=4)\n"
    "leg = sys.argv[1]\n"
    "cs.leg_dp(tiny) if leg == 'dp' else cs.LEGS[leg]()\n")


@pytest.mark.parametrize("leg", ["dp", "eager", "sharded"])
def test_multichip_leg_on_four_virtual_devices(leg, tmp_path):
    r = subprocess.run([sys.executable, "-c", _LEG, leg], cwd=REPO,
                       env=_cpu_env(tmp_path, devices=4),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    recs = [json.loads(ln) for ln in r.stdout.splitlines()
            if ln.startswith('{"phase"')]
    last = recs[-1]
    assert last["phase"] == f"{leg}.ok", recs
    if leg == "dp":
        setup = next(x for x in recs
                     if x["phase"] == "flagship_jit.setup")
        assert setup["batch_shard_devices"] == [0, 1, 2, 3]
        coll = next(x for x in recs if x["phase"] == "dp.collectives")
        assert coll["buckets"] > 0 and coll["all_reduce_ops"] > 0
    if leg == "sharded":
        assert last["param_devices"] == [0, 1, 2, 3]


def test_multichip_parent_stays_off_jax(tmp_path):
    """Importing the script and the launcher initialises no backend —
    the --multichip parent must not hold the chips its legs need."""
    code = ("import chip_smoke, horovod_tpu.runner.launch\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       env=_cpu_env(tmp_path), capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


_CACHE_PROBE = (
    "import jax\n"
    "from horovod_tpu.common import compile_cache as cc\n"
    "set_in_code = []\n"
    "real = jax.config.update\n"
    "def spy(k, v):\n"
    "    if k.endswith('cache_dir'):\n"
    "        set_in_code.append(v)\n"
    "    return real(k, v)\n"
    "jax.config.update = spy\n"
    "print(cc.enable()); print(set_in_code)\n")


@pytest.mark.parametrize("placed", [True, False],
                         ids=["variable-set", "variable-unset"])
def test_compile_cache_placement(placed, tmp_path):
    env = _cpu_env(tmp_path)
    env.pop("JAX_COMPILATION_CACHE_DIR")
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "elsewhere")
    outs = []
    for _ in range(2):  # two processes must agree on the directory
        r = subprocess.run([sys.executable, "-c", _CACHE_PROBE],
                           cwd=str(tmp_path), env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        outs.append(r.stdout.strip().splitlines())
    assert outs[0] == outs[1]
    where, set_in_code = outs[0]
    if placed:
        assert where == str(tmp_path / "elsewhere")
        assert set_in_code == "[]"  # JAX reads the variable itself
    else:
        assert where == os.path.join(REPO, ".jax_cache")
        assert set_in_code == repr([where])
