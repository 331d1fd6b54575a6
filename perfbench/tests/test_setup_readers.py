"""The PR 39 readers of set-up: each returns the registry's value, and
nothing where the program has no such counter."""

import importlib
import os

import pytest

from perfbench import run
from perfbench.tests.test_harness import ROOT

# the module: on the package the name is the function hvd.metrics()
metrics = importlib.import_module("horovod_tpu.metrics")
NEW = ("pre_init_s", "hvd_init_s", "programs_trace_lower_s",
       "programs_backend_compile_s", "compile_cache_hit_pct")


def compute(name):
    return run.load_module(ROOT, "layer_metrics", name).compute({})


@pytest.fixture
def registry(monkeypatch):
    """A registry of its own under the program's `snapshot()`."""
    fresh = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "REGISTRY", fresh)
    return fresh


def test_a_program_without_the_counters_reports_nothing(registry):
    registry.counter("hvd_aot_lower_seconds_total", "older").inc(2.0)
    assert [compute(name) for name in NEW] == [None] * 5


def test_registered_but_never_moved_reports_nothing(registry):
    registry.counter("hvd_jit_seconds_total", "x", ("phase", "program"))
    registry.counter("hvd_compile_cache_requests_total", "x", ("result",))
    registry.counter("hvd_host_span_seconds_total", "x", ("span",))
    assert compute("programs_trace_lower_s") is None
    assert compute("programs_backend_compile_s") is None
    assert compute("compile_cache_hit_pct") is None
    assert compute("hvd_init_s") is None


def test_each_reader_returns_the_registrys_value(registry):
    registry.gauge("hvd_init_started_after_seconds", "x").set(11.5)
    spans = registry.counter("hvd_host_span_seconds_total", "x", ("span",))
    spans.labels(span="init").inc(1.25)
    spans.labels(span="init.topology").inc(1.0)
    spans.labels(span="aot.lower").inc(7.0)
    jit = registry.counter("hvd_jit_seconds_total", "x",
                           ("phase", "program"))
    for phase, program, s in (("trace", "step", 20.0), ("lower", "step", 4.0),
                              ("backend", "step", 3.0), ("trace", "init", 0.5),
                              ("backend", "init", 0.25)):
        jit.labels(phase=phase, program=program).inc(s)
    requests = registry.counter("hvd_compile_cache_requests_total", "x",
                                ("result",))
    requests.labels(result="hit").inc(3)
    requests.labels(result="miss").inc(1)
    assert compute("pre_init_s") == 11.5
    assert compute("hvd_init_s") == 1.25
    assert compute("programs_trace_lower_s") == 24.5
    assert compute("programs_backend_compile_s") == 3.25
    assert compute("compile_cache_hit_pct") == 75.0


def test_a_warm_run_reads_100_and_a_cold_one_0(registry):
    requests = registry.counter("hvd_compile_cache_requests_total", "x",
                                ("result",))
    requests.labels(result="hit").inc(12)
    assert compute("compile_cache_hit_pct") == 100.0
    cold = metrics.MetricsRegistry()
    cold.counter("hvd_compile_cache_requests_total", "x",
                 ("result",)).labels(result="miss").inc(12)
    metrics.REGISTRY = cold              # the fixture puts it back
    assert compute("compile_cache_hit_pct") == 0.0


def test_the_readers_read_what_the_program_writes():
    """The names and label order the program registers are the ones
    the readers ask for: a real `hvd.init()` and a real program."""
    import jax
    import jax.numpy as jnp
    import horovod_tpu as hvd
    from horovod_tpu.common import compile_cache
    compile_cache.listen()
    hvd.init()
    hvd.shutdown()

    def program_of_the_readers_test(x):
        return jnp.cos(x) * 2.0
    jax.jit(program_of_the_readers_test)(jnp.ones((7,))).block_until_ready()
    snap = metrics.snapshot()
    assert compute("pre_init_s") == \
        snap["hvd_init_started_after_seconds"][()] > 0
    assert compute("hvd_init_s") == \
        snap["hvd_host_span_seconds_total"][("init",)] > 0
    jit = snap["hvd_jit_seconds_total"]
    assert compute("programs_trace_lower_s") >= \
        jit["trace", "program_of_the_readers_test"] + \
        jit["lower", "program_of_the_readers_test"] > 0
    assert compute("programs_backend_compile_s") >= \
        jit["backend", "program_of_the_readers_test"] > 0


def test_each_new_reader_is_in_the_manifest_for_every_cell():
    manifest = run.read_json(os.path.join(os.path.dirname(ROOT),
                                          "BENCHMARK.json"))
    cells = [w["name"] for w in manifest["workloads"]]
    assert [m["name"] for m in manifest["per_layer"][-5:]] == list(NEW)
    for m in manifest["per_layer"][-5:]:
        assert m["workloads"] == cells and m["moves"] == "setup_s"
        assert m["better"] == ("higher" if m["unit"] == "%" else "lower")
