"""DistributedOptimizer: the one-line optimizer wrapper.

API parity with the reference's optimizer wrappers
(reference: horovod/torch/optimizer.py — _DistributedOptimizer with
op / compression / backward_passes_per_step / num_groups / groups;
horovod/tensorflow/__init__.py — DistributedOptimizer /
DistributedGradientTape; gradient_aggregation*.py —
LocalGradientAggregationHelper), re-designed for JAX/optax:

* Instead of per-parameter backward hooks (impossible and unnecessary
  under XLA), the wrapper is an `optax.GradientTransformation` that
  averages gradients across workers before the inner transformation.
* Two reduction paths:
  - `axis_name=...`: for use **inside** `pjit`/`shard_map` training
    steps — lowers to `lax.psum` on the mesh axis; XLA's latency-hiding
    scheduler overlaps the reduction with remaining backprop, which is
    the compiler-native version of the reference's background-thread
    overlap.
  - default (no axis): eager cross-process reduction through the
    engine (hvd.grouped_allreduce) — for non-jitted update loops,
    mirroring the reference's eager torch path.
* `backward_passes_per_step=k` reproduces local gradient aggregation:
  gradients accumulate locally for k calls, the reduction happens on
  the k-th, and intermediate calls return zero updates.
* With `HOROVOD_NUMERICS_GUARD=1` each rank's scalar finite-flag
  rides the reduction (an extra fused leaf on the eager grouped
  allreduce, a pmin on the axis_name path) and a veto is imprinted
  onto the reduced gradients, so a `numerics.guard_non_finite`
  wrapper skips the step IDENTICALLY on every rank (numerics.py).
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
import optax

from .. import numerics as _numerics
from ..ops import collective_ops as C
from ..ops import sparse as S
from ..ops.compression import NoneCompressor
from ..ops.dispatch import AVERAGE, SUM, ADASUM, MIN
from ..ops.process_set import ProcessSet


class _AggState(NamedTuple):
    inner: Any
    acc: Any
    counter: jnp.ndarray


def _tree_zeros_like(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def _axis_reduce(grads, axis_name: str, op: int, compression, size_hint):
    """In-jit reduction over a mesh axis."""
    def red(g):
        wire, ctx = compression.compress(g)
        if op == AVERAGE:
            out = lax.pmean(wire, axis_name)
        elif op == SUM:
            out = lax.psum(wire, axis_name)
        elif op == ADASUM:
            from ..ops.adasum import _tree_fold
            stacked = lax.all_gather(wire.reshape(-1), axis_name)
            out = _tree_fold([stacked[i] for i in range(size_hint)]
                             ).reshape(wire.shape)
        else:
            raise ValueError(f"unsupported op {op} inside jit")
        return compression.decompress(out, ctx)
    return jax.tree_util.tree_map(red, grads)


def _eager_reduce(leaves: List[Any], op: int, compression,
                  process_set: Optional[ProcessSet], num_groups: int,
                  groups: Optional[Sequence[Sequence[Any]]],
                  prescale: float, postscale: float) -> List[Any]:
    """Cross-process reduction through the eager engine, fused into
    grouped allreduces (the tensor-fusion analog). Flat leaves in,
    reduced leaves out (the caller flattened once to scan for sparse
    leaves — don't traverse the tree twice on the hot path)."""
    if not leaves:
        return leaves
    if groups is not None:
        # Explicit fusion groups as lists of leaf indices (the pytree
        # analog of the reference's lists of parameters). Leaves not
        # covered by any group form one trailing group.
        seen = set()
        chunks = []
        for g in groups:
            idxs = [int(i) for i in g]
            bad = [i for i in idxs if i < 0 or i >= len(leaves)]
            if bad:
                raise ValueError(f"groups contains leaf indices {bad} out "
                                 f"of range for {len(leaves)} gradient "
                                 "leaves")
            dup = [i for i in idxs if i in seen]
            if dup:
                raise ValueError(f"leaf indices {dup} appear in multiple "
                                 "groups")
            seen.update(idxs)
            chunks.append(idxs)
        rest = [i for i in range(len(leaves)) if i not in seen]
        if rest:
            chunks.append(rest)
    elif num_groups and num_groups > 0:
        chunks = [list(c) for c in
                  _split_round_robin(list(range(len(leaves))), num_groups)]
    else:
        # Default submission order/shape comes from the SHARED bucket
        # partitioner (ops/bucketing.py — the same layer the jit
        # overlap path packs with): reverse (last-produced-first)
        # HOROVOD_FUSION_THRESHOLD-sized groups, the schedule the
        # reference's backward hooks produce. Sub-threshold trees
        # still submit as ONE group (bucket), so the stable-
        # composition fused program of the grouped eager path is
        # unchanged; results map back by leaf index either way.
        from ..common.config import knob_default
        from ..ops.bucketing import partition_cached
        thresh = int(_numerics._cfg(
            "HOROVOD_FUSION_THRESHOLD",
            knob_default("HOROVOD_FUSION_THRESHOLD")))
        # Signature-cached: the greedy walk runs once per distinct
        # (tree signature, threshold), not once per step — the knob
        # read stays per-step because the autotuner retunes it live.
        chunks = [list(b.indices)
                  for b in partition_cached(leaves, thresh)]
    out: List[Any] = [None] * len(leaves)
    for idxs in chunks:
        reduced = C.grouped_allreduce(
            [leaves[i] for i in idxs], op=op, compression=compression,
            prescale_factor=prescale, postscale_factor=postscale,
            process_set=process_set)
        for i, r in zip(idxs, reduced):
            out[i] = r
    return out


def _scale_bcoo(x, factor: float):
    from jax.experimental import sparse as jsparse
    if factor == 1.0:
        return x
    return jsparse.BCOO(
        (x.data * jnp.asarray(factor, x.data.dtype), x.indices),
        shape=x.shape, indices_sorted=x.indices_sorted,
        unique_indices=x.unique_indices)


def _eager_reduce_mixed(leaves, treedef, sp_idx, eff_op, compression,
                        process_set, num_groups, groups,
                        prescale: float, postscale: float):
    """Eager reduction of a gradient tree containing BCOO leaves:
    sparse leaves ride hvd.sparse_allreduce (allgather-based,
    reference: torch/optimizer.py routing sparse grads to
    sparse_allreduce_async_), dense leaves the grouped allreduce.
    Sparse submissions go first so their negotiation overlaps the
    dense grouped reduction; pre/postscale fold into the values
    (linear, so semantics match the dense path exactly).

    The reduced sparse leaves densify on return: the WIRE stays sparse
    (nnz rows instead of the full embedding table — the distributed
    cost the reference's sparse path exists to cut), but optax inner
    transformations are dense-only (torch's SGD applies sparse grads
    via index_add; optax tree_maps would corrupt BCOO indices), so the
    local update consumes the dense form. Divergence documented in
    docs/migrating_from_horovod.md."""
    if eff_op not in (AVERAGE, SUM):
        raise NotImplementedError(
            "sparse gradients support op=Average/Sum; pass "
            "sparse_as_dense=True to route them through the dense "
            f"path for op={eff_op}")
    handles = {}
    for i in sp_idx:
        handles[i] = S.sparse_allreduce_async(
            _scale_bcoo(leaves[i], prescale), op=eff_op,
            process_set=process_set)
    dense_idx = [i for i in range(len(leaves)) if i not in handles]
    if groups is not None:
        # `groups` holds leaf indices of the FULL gradient tree; the
        # dense reduction below sees a compacted list, so remap — and
        # reject sparse members (they ride sparse_allreduce, outside
        # any fusion group).
        dense_pos = {leaf: pos for pos, leaf in enumerate(dense_idx)}
        remapped = []
        for g in groups:
            idxs = [int(i) for i in g]
            bad = [i for i in idxs if i < 0 or i >= len(leaves)]
            if bad:
                raise ValueError(f"groups contains leaf indices {bad} "
                                 f"out of range for {len(leaves)} "
                                 "gradient leaves")
            sp_members = [i for i in idxs if i in handles]
            if sp_members:
                raise ValueError(
                    f"groups contains BCOO gradient leaves {sp_members}"
                    "; sparse leaves reduce via sparse_allreduce and "
                    "cannot join a dense fusion group")
            remapped.append([dense_pos[i] for i in idxs])
        groups = remapped
    if dense_idx:
        reduced = _eager_reduce([leaves[i] for i in dense_idx],
                                eff_op, compression, process_set,
                                num_groups, groups, prescale,
                                postscale)
        for i, r in zip(dense_idx, reduced):
            leaves[i] = r
    for i, h in handles.items():
        leaves[i] = _scale_bcoo(h.synchronize(), postscale).todense()
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _flag_min_eager(flag, process_set):
    """Coordinated finite-flag for reductions that cannot carry an
    extra fused leaf (Adasum folds, mixed sparse trees): one tiny
    negotiated Min allreduce of the f32 flag."""
    return C.allreduce(flag, op=MIN, name="numerics.flag",
                       process_set=process_set) > 0.5


def _split_round_robin(items, n):
    buckets = [[] for _ in range(min(n, len(items)))]
    for i, it in enumerate(items):
        buckets[i % len(buckets)].append(it)
    return buckets


def DistributedGradientTransformation(
        inner: optax.GradientTransformation,
        *,
        op: int = AVERAGE,
        compression=NoneCompressor,
        axis_name: Optional[str] = None,
        backward_passes_per_step: int = 1,
        num_groups: int = 0,
        groups: Optional[Sequence] = None,
        process_set: Optional[ProcessSet] = None,
        gradient_predivide_factor: float = 1.0,
        sparse_as_dense: bool = False,
        size_hint: Optional[int] = None,
) -> optax.GradientTransformation:
    """Wrap an optax transformation with cross-worker gradient reduction."""
    if gradient_predivide_factor != 1.0 and op != AVERAGE:
        raise ValueError(
            "gradient_predivide_factor requires op=Average "
            "(matches the reference's restriction)")

    k = int(backward_passes_per_step)
    if k < 1:
        raise ValueError("backward_passes_per_step must be >= 1")

    def reduce_grads(grads):
        guard = _numerics.guard_enabled()
        leaves, treedef = jax.tree_util.tree_flatten(
            grads, is_leaf=S.is_sparse)
        sp_idx = [i for i, l in enumerate(leaves) if S.is_sparse(l)]
        # numerics.grad chaos seam — UNCONDITIONAL (gated only on an
        # armed plan inside), so an armed spec always injects and
        # logs, guard on or off: injecting with the guard OFF is the
        # negative control that shows the poison propagating.
        corrupted = _numerics.maybe_corrupt_grads(leaves)
        if corrupted is not leaves:
            leaves = corrupted
            grads = jax.tree_util.tree_unflatten(treedef, leaves)
        flag = None
        if guard:
            # Coordinated skip-step (numerics.py): the scalar finite-
            # flag over the PRE-reduction gradients; the min-reduce
            # ride below is what carries the veto.
            flag = _numerics.local_finite_flag(
                [l.data if S.is_sparse(l) else l for l in leaves])
        if sp_idx and sparse_as_dense:
            # reference: optimizer.py sparse_as_dense — densify before
            # the ordinary dense reduction.
            for i in sp_idx:
                leaves[i] = leaves[i].todense()
            sp_idx = []
            grads = jax.tree_util.tree_unflatten(treedef, leaves)
        if axis_name is not None:
            if sp_idx:
                raise ValueError(
                    "BCOO gradients inside an axis_name (in-jit) "
                    "reduction require sparse_as_dense=True; the "
                    "allgather-based sparse path is eager-only")
            n = size_hint
            if op == ADASUM and n is None:
                raise ValueError("op=Adasum with axis_name requires "
                                 "size_hint=<axis size>")
            out = _axis_reduce(grads, axis_name, op, compression, n)
            if guard:
                # In-jit ride: a pmin alongside the data collectives —
                # same XLA program, no extra launch.
                ok = lax.pmin(flag, axis_name) > 0.5
                out = _numerics.imprint_non_finite(out, ok)
            # hvdlint: disable-next=HVD005 (exit of the axis_name
            # configuration branch: every rank of a call site passes
            # the same axis_name/op/compression, so the arms are
            # mutually exclusive uniform schedules)
            return out
        prescale, postscale = 1.0, 1.0
        eff_op = op
        if op == AVERAGE and gradient_predivide_factor != 1.0:
            # reference: prescale by 1/f before the sum, postscale by
            # f/size after — numerically safer for fp16 sums. Size is
            # the PROCESS SET's size (the reduction spans only its
            # members), matching the reference's process_set.size().
            import horovod_tpu as hvd
            n = process_set.size if process_set is not None else hvd.size()
            prescale = 1.0 / gradient_predivide_factor
            postscale = gradient_predivide_factor / n
            eff_op = SUM
        if sp_idx:
            out = _eager_reduce_mixed(leaves, treedef, sp_idx, eff_op,
                                      compression, process_set,
                                      num_groups, groups, prescale,
                                      postscale)
            if guard:
                out = _numerics.imprint_non_finite(
                    out, _flag_min_eager(flag, process_set))
            # hvdlint: disable-next=HVD005 (exit of the sparse-leaves
            # configuration branch; sparsity structure is part of the
            # call signature, uniform across ranks)
            return out
        if guard and leaves and op in (AVERAGE, SUM) \
                and compression is NoneCompressor:
            # Eager fused ride: the flag is ONE extra f32 leaf in the
            # same grouped allreduce (appended last, so the reverse-
            # order partitioner places it in the first-emitted
            # bucket), so the veto costs no extra launch. Under AVERAGE
            # (incl. the predivide prescale/postscale rewrite, which
            # nets out to the mean) the reduced flag is the mean of
            # the per-rank 0/1 votes — 1.0 iff everyone voted finite;
            # under SUM it is the finite-voter count. UNCOMPRESSED
            # groups only: a lossy wire dtype accumulates the vote
            # count in fp16/bf16, where n-1 rounds to n past a few
            # hundred ranks and a single veto would be rounded away —
            # compressed reductions take the exact Min ride below.
            import horovod_tpu as hvd
            n = process_set.size if process_set is not None \
                else hvd.size()
            reduced = _eager_reduce(
                leaves + [flag], eff_op, compression, process_set,
                num_groups, groups, prescale, postscale)
            rflag = reduced.pop()
            ok = (rflag > 1.0 - 0.5 / n) if op == AVERAGE \
                else (rflag > n - 0.5)
            # hvdlint: disable-next=HVD005 (exit of the fused-flag
            # configuration branch: guard/op/compression are static
            # per call site, uniform across ranks)
            return _numerics.imprint_non_finite(
                jax.tree_util.tree_unflatten(treedef, reduced), ok)
        out = jax.tree_util.tree_unflatten(treedef, _eager_reduce(
            leaves, eff_op, compression, process_set, num_groups,
            groups, prescale, postscale))
        if guard:
            # Adasum (and any exotic op): the flag cannot fold into
            # the data reduction — one tiny Min allreduce instead.
            out = _numerics.imprint_non_finite(
                out, _flag_min_eager(flag, process_set))
        # hvdlint: disable-next=HVD005 (fallback exit of the same
        # static configuration dispatch; all arms uniform)
        return out

    def init_fn(params):
        inner_state = inner.init(params)
        if k == 1:
            return inner_state
        return _AggState(inner=inner_state, acc=_tree_zeros_like(params),
                         counter=jnp.zeros((), jnp.int32))

    def update_fn(grads, state, params=None, **extra):
        if k == 1:
            reduced = reduce_grads(grads)
            return inner.update(reduced, state, params, **extra)
        # Local aggregation path (LocalGradientAggregationHelper analog).
        # The accumulator is dense (zeros_like(params)), so sparse
        # gradient leaves must densify before accumulating.
        if any(S.is_sparse(l) for l in jax.tree_util.tree_leaves(
                grads, is_leaf=S.is_sparse)):
            if not sparse_as_dense:
                raise ValueError(
                    "backward_passes_per_step > 1 with BCOO gradients "
                    "requires sparse_as_dense=True (the local "
                    "accumulator is dense)")
            grads = jax.tree_util.tree_map(
                lambda l: l.todense() if S.is_sparse(l) else l, grads,
                is_leaf=S.is_sparse)
        acc = jax.tree_util.tree_map(jnp.add, state.acc, grads)
        counter = state.counter + 1
        if axis_name is not None:
            # In-jit: branchlessly blend "flush" and "hold" updates.
            def flush(_):
                avg = jax.tree_util.tree_map(lambda a: a / k, acc)
                reduced = reduce_grads(avg)
                updates, new_inner = inner.update(reduced, state.inner,
                                                  params, **extra)
                return updates, new_inner, _tree_zeros_like(acc), \
                    jnp.zeros((), jnp.int32)

            def hold(_):
                return (_tree_zeros_like(grads), state.inner, acc, counter)

            updates, new_inner, new_acc, new_counter = lax.cond(
                counter >= k, flush, hold, operand=None)
        else:
            if int(counter) >= k:
                avg = jax.tree_util.tree_map(lambda a: a / k, acc)
                reduced = reduce_grads(avg)
                updates, new_inner = inner.update(reduced, state.inner,
                                                  params, **extra)
                new_acc = _tree_zeros_like(acc)
                new_counter = jnp.zeros((), jnp.int32)
            else:
                updates = _tree_zeros_like(grads)
                new_inner, new_acc, new_counter = state.inner, acc, counter
        return updates, _AggState(inner=new_inner, acc=new_acc,
                                  counter=new_counter)

    return optax.GradientTransformation(init_fn, update_fn)


# The hvd.DistributedOptimizer name, for the 5-line experience.
DistributedOptimizer = DistributedGradientTransformation
