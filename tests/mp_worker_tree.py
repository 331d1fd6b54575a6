"""Worker for the 4-rank hierarchical-control-plane wiring test:
HOROVOD_CONTROL_TREE_ARITY=2 over 4 ranks places rank 2 UNDER the
rank-1 aggregator (tier 2), so every negotiated op crosses a real
two-hop aggregation path. The ops here are negotiation-level only
(generic entries with per-rank metadata) — no cross-process XLA data
plane, so the test runs on jaxlibs whose CPU backend cannot (the same
gate every mp data-plane test skips on)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["HOROVOD_CONTROL_TREE_ARITY"] = "2"

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.common.basics import state  # noqa: E402
from horovod_tpu.core import native  # noqa: E402


def main():
    hvd.init()
    r, n = hvd.rank(), hvd.size()
    assert n == 4, f"test expects 4 ranks, got {n}"

    ctl = state().engine.controller
    assert ctl is not None, "negotiated controller required"
    from horovod_tpu.core.native import NativeCore
    assert isinstance(ctl.core, NativeCore), type(ctl.core)

    # The wiring must agree with the C++ placement arithmetic.
    want_tier = native.tree_tier(r, n, 2)
    assert ctl.core.tree_tier() == want_tier, \
        (r, ctl.core.tree_tier(), want_tier)
    # With (size=4, arity=2) rank 2 hangs under the rank-1
    # aggregator: the tree is genuinely deeper than the flat star.
    assert native.tree_depth(n, 2) == 2
    if r == 2:
        assert want_tier == 2, want_tier
        assert native.tree_parent(r, n, 2) == 1

    # Several rounds of negotiated generic ops with per-rank
    # metadata: the metas must come back ';'-aggregated by WORLD rank
    # on every rank — rank 2's meta crossed the aggregator hop both
    # ways, and steady-state rounds ride the response-cache-free
    # generic path.
    for step in range(5):
        got = {}

        def record(metas, step=step, got=got):
            got["metas"] = metas
            return None

        h = ctl.submit_generic(f"tree_meta_{step}", 4, record,
                               meta=f"r{r}s{step}")
        hvd.synchronize(h.id)
        assert got["metas"] == [f"r{i}s{step}" for i in range(n)], \
            got["metas"]

    # The tier gauge is visible in the metrics snapshot.
    snap = hvd.metrics()
    assert snap["hvd_control_tree_depth"][()] == float(want_tier), \
        snap["hvd_control_tree_depth"]
    # Rounds were observed.
    rounds = snap["hvd_control_round_seconds"][()]
    assert rounds["count"] >= 5, rounds

    hvd.shutdown()
    print(f"TREE WIRE OK rank={r} tier={want_tier}", flush=True)


main()
