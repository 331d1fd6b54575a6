"""Set-up's own measurement: `tracing.host_span` (ring, registry and,
in a capture, the profiler's clock), the phases of `hvd.init()`, and
the programs `common/compile_cache.py` counts from `jax.monitoring`."""

import glob
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu import tracing
from horovod_tpu.common import compile_cache
from horovod_tpu.metrics import snapshot

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INIT_CHILDREN = ("init.distributed", "init.topology", "init.engine",
                 "init.observability")


def span_seconds(name):
    return snapshot()["hvd_host_span_seconds_total"].get((name,), 0.0)


def jit_counters():
    snap = snapshot()
    return {name: dict(snap.get(name, {})) for name in (
        "hvd_jit_seconds_total", "hvd_jit_programs_total",
        "hvd_compile_cache_requests_total")}


@pytest.fixture
def ring():
    size = tracing._ring_size
    tracing.configure_ring(64)
    yield
    tracing.configure_ring(size)


def test_a_span_writes_begin_and_end_and_moves_its_counter(ring):
    seconds = span_seconds("aot.lower")
    with tracing.host_span("aot.lower") as span:
        time.sleep(0.01)
    begin, end = tracing.ring_events()[-2:]
    assert begin[1:3] == (tracing.SPAN_BEGIN, "aot.lower")
    assert end[1:3] == (tracing.SPAN_END, "aot.lower")
    assert begin[3] == end[3]            # one id on both entries
    assert end[4] == span.seconds >= 0.01
    assert (end[0] - begin[0]) / 1e9 == pytest.approx(span.seconds,
                                                      abs=1e-3)
    assert span_seconds("aot.lower") - seconds == pytest.approx(
        span.seconds)


def test_a_child_names_the_span_that_caused_it(ring):
    with tracing.host_span("init") as parent:
        with tracing.host_span("init.engine") as child:
            time.sleep(0.005)
        with tracing.host_span("init.topology") as second:
            pass
    events = {(e[1], e[2]): e for e in tracing.ring_events()}
    parent_id = events[tracing.SPAN_BEGIN, "init"][3]
    assert events[tracing.SPAN_BEGIN, "init"][4] == -1.0
    assert events[tracing.SPAN_BEGIN, "init.engine"][4] == parent_id
    assert events[tracing.SPAN_BEGIN, "init.topology"][4] == parent_id
    # self time: the parent's duration less its children's
    assert parent.seconds - child.seconds - second.seconds >= 0
    with tracing.host_span("aot.compile"):
        pass
    assert tracing.ring_events()[-2][4] == -1.0   # the stack unwound


def test_a_span_that_raises_still_ends(ring):
    seconds = span_seconds("aot.compile")
    with pytest.raises(KeyError):
        with tracing.host_span("aot.compile") as span:
            time.sleep(0.002)
            raise KeyError("x")
    assert tracing.ring_events()[-1][1:3] == (tracing.SPAN_END,
                                              "aot.compile")
    assert span.seconds >= 0.002
    assert span_seconds("aot.compile") - seconds == pytest.approx(
        span.seconds)
    with tracing.host_span("aot.lower"):
        pass
    assert tracing.ring_events()[-2][4] == -1.0


def test_an_unregistered_name_is_refused():
    with pytest.raises(ValueError, match="HOST_SPANS"):
        tracing.host_span("init.everything")
    assert set(INIT_CHILDREN) | {"init", "aot.lower", "aot.compile"} \
        == set(tracing.HOST_SPANS)


def test_the_ring_switched_off_leaves_the_counter(monkeypatch):
    monkeypatch.setattr(tracing, "_ring", None)
    seconds = span_seconds("aot.lower")
    with tracing.host_span("aot.lower") as span:
        time.sleep(0.002)
    assert tracing.ring_events() == []
    assert span_seconds("aot.lower") - seconds == pytest.approx(
        span.seconds)


def test_no_annotation_is_built_without_a_capture(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("TraceAnnotation built with no capture")
    assert not tracing.profiler_active()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    with tracing.host_span("aot.lower"):
        pass


def test_in_a_capture_the_span_is_on_the_profilers_clock(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with tracing.host_span("init"):
            jnp.ones((8,)).block_until_ready()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    names = {event.name
             for plane in jax.profiler.ProfileData.from_file(path).planes
             for line in plane.lines for event in line.events}
    assert "hvd::init" in names


def test_the_digest_leaves_host_spans_to_the_registry(ring):
    # Its totals are by kind: nested spans summed into one would count
    # the children twice, and a begin's arg is an id, no time.
    tracing.record("agree", "t", 0, 0.25)
    with tracing.host_span("init"):
        with tracing.host_span("init.engine"):
            pass
    spans = tracing.trace_digest()["spans"]
    assert spans == {"agree": {"count": 1, "total_s": 0.25}}


def test_a_dump_carries_the_ring_and_one_anchor_pair(ring, tmp_path):
    with tracing.host_span("init.engine"):
        pass
    before = time.time(), time.monotonic_ns()
    path = tracing.write_postmortem("test", path=str(tmp_path / "pm.json"))
    after = time.time(), time.monotonic_ns()
    with open(path) as f:
        doc = json.load(f)
    assert before[0] <= doc["unix_time"] <= after[0]
    assert before[1] <= doc["mono_ns"] <= after[1]
    kinds = [(e[1], e[2]) for e in doc["ring"]]
    assert (tracing.SPAN_BEGIN, "init.engine") in kinds
    assert (tracing.SPAN_END, "init.engine") in kinds
    assert "hvd_host_span_seconds_total" in doc["metrics"]


def test_init_leaves_its_phases_in_the_registry():
    import horovod_tpu as hvd
    names = ("init",) + INIT_CHILDREN
    before = {n: span_seconds(n) for n in names}
    hvd.init()
    try:
        moved = {n: span_seconds(n) - before[n] for n in names}
        hvd.init()                       # idempotent: no second span
        assert {n: span_seconds(n) - before[n] for n in names} == moved
    finally:
        hvd.shutdown()
    assert all(moved[n] > 0 for n in ("init", "init.topology",
                                      "init.engine",
                                      "init.observability")), moved
    assert 0 < sum(moved[n] for n in INIT_CHILDREN) <= moved["init"]
    after = snapshot()["hvd_init_started_after_seconds"][()]
    assert 0 < after < 24 * 3600
    # set once: a second init of the process leaves the gauge alone
    hvd.init()
    hvd.shutdown()
    assert snapshot()["hvd_init_started_after_seconds"][()] == after
    assert span_seconds("init") - before["init"] > moved["init"]


def test_aot_compile_is_two_spans_feeding_its_counters(ring):
    from horovod_tpu.parallel.aot import aot_compile
    before = snapshot()
    fn, _ = aot_compile(jax.jit(lambda x: x * 3.0), jnp.ones((4,)))
    assert float(fn(jnp.ones((4,)))[0]) == 3.0
    after = snapshot()
    for aot, span in (("hvd_aot_lower_seconds_total", "aot.lower"),
                      ("hvd_aot_compile_seconds_total", "aot.compile")):
        moved = after[aot][()] - before[aot][()]
        assert moved > 0
        assert moved == pytest.approx(
            after["hvd_host_span_seconds_total"][(span,)]
            - before["hvd_host_span_seconds_total"].get((span,), 0.0))
    kinds = [(e[1], e[2]) for e in tracing.ring_events()]
    assert kinds[-4:] == [(tracing.SPAN_BEGIN, "aot.lower"),
                          (tracing.SPAN_END, "aot.lower"),
                          (tracing.SPAN_BEGIN, "aot.compile"),
                          (tracing.SPAN_END, "aot.compile")]
    with open(os.path.join(REPO, "horovod_tpu", "parallel",
                           "aot.py")) as f:
        source = f.read()
    assert "perf_counter" not in source
    assert "TraceAnnotation" not in source


def test_every_program_of_the_process_is_counted():
    compile_cache.listen()
    before = jit_counters()

    def tripled_then_summed(x):
        return jnp.sum(x * 3.0)
    jax.jit(tripled_then_summed)(jnp.ones((5,))).block_until_ready()
    after = jit_counters()
    seconds = after["hvd_jit_seconds_total"]
    for phase in ("trace", "lower", "backend"):
        assert seconds[phase, "tripled_then_summed"] > 0, phase
    assert after["hvd_jit_programs_total"][("tripled_then_summed",)] \
        - before["hvd_jit_programs_total"].get(
            ("tripled_then_summed",), 0) == 1
    # the second call compiles nothing: the recompilation alarm is flat
    jax.jit(tripled_then_summed)(jnp.ones((5,))).block_until_ready()
    assert jit_counters()["hvd_jit_programs_total"] == \
        after["hvd_jit_programs_total"]


def test_a_phase_inside_another_is_not_counted_twice():
    compile_cache.listen()

    @jax.jit
    def inner_helper_of_the_test(x):
        time.sleep(0.05)                 # trace-time only
        return x + 1.0

    def outer_of_the_test(x):
        return inner_helper_of_the_test(x) * 2.0
    t = time.perf_counter()
    jax.jit(outer_of_the_test)(jnp.ones((3,))).block_until_ready()
    wall = time.perf_counter() - t
    seconds = jit_counters()["hvd_jit_seconds_total"]
    assert seconds["trace", "outer_of_the_test"] >= 0.05
    assert ("trace", "inner_helper_of_the_test") not in seconds
    mine = sum(v for (_, program), v in seconds.items()
               if program.endswith("_of_the_test"))
    assert mine <= wall


WORKER = """
import json, sys
import jax, jax.numpy as jnp
from jax._src import monitoring
from horovod_tpu.common import compile_cache
from horovod_tpu.metrics import snapshot

placed = compile_cache.enable()
listeners = [len(monitoring.get_event_listeners()),
             len(monitoring.get_event_duration_listeners()),
             len(monitoring.get_scalar_listeners())]
compile_cache.enable()
again = [len(monitoring.get_event_listeners()),
         len(monitoring.get_event_duration_listeners()),
         len(monitoring.get_scalar_listeners())]

def cached_program_of_the_test(x):
    return jnp.tanh(x) @ x.T
jax.jit(cached_program_of_the_test)(jnp.ones((16, 16))).block_until_ready()
snap = snapshot()
print(json.dumps({
    "placed": placed, "listeners": listeners, "again": again,
    "requests": {k[0]: v for k, v in
                 snap["hvd_compile_cache_requests_total"].items()},
    "programs": snap["hvd_jit_programs_total"].get(
        ("cached_program_of_the_test",), 0),
    "backend_s": snap["hvd_jit_seconds_total"].get(
        ("backend", "cached_program_of_the_test"), 0)}))
"""


def run_worker(cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir),
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", WORKER], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_a_cold_start_misses_and_a_warm_one_hits(tmp_path):
    cold = run_worker(tmp_path / "cache")
    assert cold["placed"] == str(tmp_path / "cache")
    assert cold["requests"].get("miss", 0) >= 1
    assert cold["requests"].get("hit", 0) == 0
    assert cold["programs"] == 1 and cold["backend_s"] > 0
    warm = run_worker(tmp_path / "cache")
    assert warm["requests"].get("hit", 0) == cold["requests"]["miss"]
    assert warm["requests"].get("miss", 0) == 0
    assert warm["programs"] == 1         # a load is a program too
    # enable() twice: one listener set
    for run in (cold, warm):
        assert run["listeners"] == run["again"] == [1, 1, 1]
