"""Lifecycle, topology, config, metadata tests
(reference analog: test/single/test_run.py basics + hvd API queries in
test/parallel/test_torch.py)."""

import os

import pytest


def test_init_rank_size(hvd_single):
    hvd = hvd_single
    assert hvd.is_initialized()
    assert hvd.rank() == 0
    assert hvd.size() == 1
    assert hvd.local_rank() == 0
    assert hvd.local_size() == 1
    assert hvd.cross_rank() == 0
    assert hvd.cross_size() == 1
    assert hvd.is_homogeneous()


def test_init_idempotent(hvd_single):
    hvd = hvd_single
    hvd.init()
    assert hvd.rank() == 0


def test_uninitialized_raises():
    import horovod_tpu as hvd
    hvd.shutdown()
    with pytest.raises(RuntimeError, match="init"):
        hvd.rank()


def test_shutdown_and_reinit():
    import horovod_tpu as hvd
    hvd.init()
    assert hvd.is_initialized()
    hvd.shutdown()
    assert not hvd.is_initialized()
    hvd.init()
    assert hvd.size() == 1
    hvd.shutdown()


def test_config_env_parsing():
    from horovod_tpu.common.config import Config
    cfg = Config(env={"HOROVOD_FUSION_THRESHOLD": "1048576",
                      "HOROVOD_CYCLE_TIME": "2.5",
                      "HOROVOD_AUTOTUNE": "true",
                      "HOROVOD_LOG_LEVEL": "debug"})
    assert cfg.fusion_threshold == 1048576
    assert cfg.cycle_time_ms == 2.5
    assert cfg.autotune is True
    assert cfg.log_level == "debug"
    # defaults
    assert cfg.cache_capacity == 1024
    assert cfg.stall_check_time == 60.0


def test_config_bad_value():
    from horovod_tpu.common.config import Config
    with pytest.raises(ValueError, match="HOROVOD_FUSION_THRESHOLD"):
        Config(env={"HOROVOD_FUSION_THRESHOLD": "lots"})


def test_config_overrides():
    from horovod_tpu.common.config import Config
    cfg = Config(overrides={"HOROVOD_CYCLE_TIME": 7.0})
    assert cfg.cycle_time_ms == 7.0


def test_describe_knobs_lists_everything():
    from horovod_tpu.common.config import KNOBS, describe_knobs
    text = describe_knobs()
    for k in KNOBS:
        assert k.env in text


@pytest.mark.parametrize("name", [
    "HOROVOD_JIT_OVERLAP", "HOROVOD_FLASH_ATTENTION",
    "HOROVOD_COMPRESSION_RANK", "HOROVOD_COMPRESSION_WARMUP_STEPS",
    "HOROVOD_COMPRESSION_MIN_ELEMENTS"])
def test_retired_switch_is_no_knob(name):
    """PR 33: the jit step takes these decisions from what it
    observes. A read of the name fails loudly and no help text or
    Config attribute offers it."""
    from horovod_tpu.common import config
    with pytest.raises(KeyError, match="not a declared knob"):
        config.env_value(name)
    assert name not in config.describe_knobs()
    assert name not in config.Config._ATTR_MAP.values()


def test_metadata_flags():
    import horovod_tpu as hvd
    # The north-star constraint: never NCCL/MPI/Gloo.
    assert not hvd.nccl_built()
    assert not hvd.mpi_built()
    assert not hvd.gloo_built()
    assert not hvd.cuda_built()
    assert hvd.xla_built()
    summary = hvd.check_build_summary()
    assert "XLA collectives" in summary
    assert "NCCL (never linked" in summary
    import importlib.util
    expect = ("[X]" if importlib.util.find_spec("torch") else "[ ]")
    assert f"{expect} torch frontend binding" in summary


def test_process_set_registration(hvd_single):
    import horovod_tpu as hvd
    ps = hvd.add_process_set([0])
    assert ps.process_set_id is not None
    assert ps.included()
    assert ps.rank() == 0
    # duplicate registration returns the same set
    ps2 = hvd.add_process_set([0])
    assert ps2.process_set_id == ps.process_set_id


def test_process_set_out_of_range(hvd_single):
    import horovod_tpu as hvd
    with pytest.raises(ValueError, match="out of range"):
        hvd.add_process_set([0, 5])


def test_capability_shims_match_reference_contract():
    """The reference's capability probes must exist and answer
    honestly: no NCCL/MPI/Gloo anywhere (the data plane is XLA over
    PJRT), XLA always built (reference: horovod/metadata and
    mpi_ops.py mpi_threads_supported)."""
    import horovod_tpu as hvd
    assert hvd.nccl_built() is False
    assert hvd.mpi_built() is False
    assert hvd.gloo_built() is False
    assert hvd.cuda_built() is False
    assert hvd.rocm_built() is False
    assert hvd.ddl_built() is False
    assert hvd.ccl_built() is False
    assert hvd.nccl_enabled() is False
    assert hvd.mpi_enabled() is False
    assert hvd.gloo_enabled() is False
    assert hvd.mpi_threads_supported() is False
    assert hvd.xla_built() is True
