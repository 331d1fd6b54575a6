"""Readers of what the program counts about set-up, shared by the
per-layer metrics of `layer_metrics/` that PR 39 added: the host spans
of `horovod_tpu/tracing.py` (`hvd_host_span_seconds_total{span}`), the
gauges `hvd.init()` sets once, and the counters
`common/compile_cache.py` feeds from `jax.monitoring`
(`hvd_jit_seconds_total{phase, program}`,
`hvd_compile_cache_requests_total{result}`). They read the registry
after the run, which outlives `hvd.shutdown()`. Every reader returns
nothing where the program has no such counter, as a program older than
the names has not."""

from __future__ import annotations


def series_sum(name, accept=lambda key: True):
    """Sum of the series of the program's metric `name` whose label
    values (a tuple, in the order the program registered the labels)
    `accept` takes; nothing where no series does."""
    from horovod_tpu.metrics import snapshot
    values = [v for key, v in snapshot().get(name, {}).items()
              if accept(key)]
    return sum(values) if values else None


def jit_seconds(*phases):
    """Host seconds JAX spent in the given phases (`trace`, `lower`,
    `backend`) over every program of the process."""
    return series_sum("hvd_jit_seconds_total",
                      lambda key: key[0] in phases)


def cache_hit_pct():
    """100 x hits / (hits + misses) of the persistent compile cache;
    nothing where no request was made."""
    from horovod_tpu.metrics import snapshot
    requests = snapshot().get("hvd_compile_cache_requests_total", {})
    hits = requests.get(("hit",), 0.0)
    misses = requests.get(("miss",), 0.0)
    if not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
