"""Share of the process's compile-cache requests that loaded a
program and did not compile it: 100 x hits / (hits + misses) of
`hvd_compile_cache_requests_total{result}`. 100 on a warm start; less
where entries were evicted or programs changed."""

from perfbench.setup_readers import cache_hit_pct

NAME = "compile_cache_hit_pct"
UNIT = "%"
LAYER = "entry points (hvd.init, common/compile_cache.py, parallel/aot.py)"
MOVES = "setup_s"


def compute(_ctx):
    return cache_hit_pct()
