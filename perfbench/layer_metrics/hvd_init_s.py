"""Seconds inside `hvd.init()`: the program's host span `init`
(`hvd_host_span_seconds_total{span="init"}`), whose children
`init.distributed`, `init.topology`, `init.engine` and
`init.observability` say where."""

from perfbench.setup_readers import series_sum

NAME = "hvd_init_s"
UNIT = "s"
LAYER = "entry points (hvd.init, common/compile_cache.py, parallel/aot.py)"
MOVES = "setup_s"


def compute(_ctx):
    return series_sum("hvd_host_span_seconds_total",
                      lambda key: key == ("init",))
