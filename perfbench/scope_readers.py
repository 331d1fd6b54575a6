"""Readers of the program's own names and counters, shared by the
per-layer metrics of `layer_metrics/` that PR 28 added: device time by
`hvd.*` scope (`scope_reduce.py`) and the `hvd_aot_*` counters of
`horovod_tpu/parallel/aot.py`. `ctx` is the driver's context
(README.md). Every reader returns nothing where the program has no
such scope or counter, as a program older than the names has not."""

from __future__ import annotations

from perfbench import scope_reduce

GRAD_REDUCE = "hvd.grad_reduce"


def _reduced(ctx):
    """The scope reduction of the trace this run wrote, if the run was
    traced and the trace found is that run's."""
    traced = ctx["traced"]
    if traced is None:
        return None
    reduced = scope_reduce.newest()
    if reduced is None or reduced["steps"] != traced["steps"]:
        return None
    return reduced


def scope_ms(ctx, *scopes):
    """Device ms a traced step under the given scopes, all passes."""
    reduced = _reduced(ctx)
    if reduced is None:
        return None
    return scope_reduce.ms_a_step(reduced, lambda s: s in scopes)


def grad_reduce_ms(ctx):
    """Device ms a traced step under `hvd.grad_reduce` and every
    bucket's `hvd.grad_reduce.b<N>`: the all-reduces with their pack,
    cast, unpack and scale."""
    reduced = _reduced(ctx)
    if reduced is None:
        return None
    return scope_reduce.ms_a_step(
        reduced, lambda s: s == GRAD_REDUCE or
        s.startswith(GRAD_REDUCE + "."))


def recompute_ms(ctx):
    """Device ms a traced step of forward work run again in the
    backward pass under `jax.checkpoint`, whatever its scope: a cut
    across the layer scopes, not a part beside them."""
    reduced = _reduced(ctx)
    if reduced is None:
        return None
    return scope_reduce.ms_a_step(reduced, passes=("recompute",))


def unscoped_pct(ctx):
    """Share of the first chip's busy time under no `hvd.*` scope."""
    reduced = _reduced(ctx)
    if reduced is None:
        return None
    unscoped = sum(ps for (scope, _), ps in reduced["by"].items()
                   if scope == scope_reduce.UNSCOPED)
    return 100.0 * unscoped / reduced["busy_ps"]


def counter(name):
    """An unlabelled counter of the program's registry
    (`horovod_tpu/metrics.py`), which outlives `hvd.shutdown()`."""
    from horovod_tpu.metrics import snapshot
    return snapshot().get(name, {}).get(())
