"""Jitted SPMD training-step builders.

This is the jit-path counterpart of the eager engine: where the
reference overlaps communication with backprop via its background
thread (reference: horovod/common/operations.cc BackgroundThreadLoop +
horovod/torch/optimizer.py gradient hooks), here the entire training
step is one XLA program over a `Mesh` and the latency-hiding scheduler
does the overlap. Negotiation collapses to a compile-time concern
(SURVEY.md §5.8 — "the biggest architectural simplification the TPU
build gets to make").

Two builders:
  * `build_train_step`  — shard_map-based, explicit collectives
    (lax.psum over the batch axes; Adasum/compression via
    DistributedGradientTransformation(axis_name=...)). Horovod
    semantics, TPU lowering.
  * `build_gspmd_train_step` — constraint-based GSPMD: you give
    shardings, XLA inserts the collectives. The fully
    compiler-native path.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import numerics as _numerics
from ..ops.bucketing import (assignment_digest, partition_buckets,
                             split_by_dtype)
from ..ops.compression import (CompressionSpec, effective_rank,
                               gram_orthogonalize, init_q,
                               matrix_shape, powersgd_eligible,
                               powersgd_reduce, powersgd_wire_elements,
                               resolve_compression, wire_dtype_of)
from ..ops import compression as _compression
from ..tracing import bucket_scope, device_scope
from .mesh import AXIS_ORDER, FSDP_AXIS, batch_axes
from .sharding import replicated


def overlap_enabled() -> bool:
    """The HOROVOD_JIT_OVERLAP knob (build-time read, Config-aware)."""
    from ..common.config import knob_default
    return bool(_numerics._cfg("HOROVOD_JIT_OVERLAP",
                               knob_default("HOROVOD_JIT_OVERLAP")))


def overlap_threshold_bytes() -> int:
    """Bucket size for the jit overlap path — the SAME knob the eager
    fusion buffer packs to (HOROVOD_FUSION_THRESHOLD; default from
    the registry, not a second literal)."""
    from ..common.config import knob_default
    return int(_numerics._cfg("HOROVOD_FUSION_THRESHOLD",
                              knob_default("HOROVOD_FUSION_THRESHOLD")))


def compression_spec(compression=None, rank=None,
                     min_elements=None) -> CompressionSpec:
    """Resolve the builder's compression config: explicit args win,
    otherwise the HOROVOD_COMPRESSION knob family (Config-aware, same
    read path as the overlap/threshold knobs)."""
    from ..common.config import knob_default
    name = compression
    if name is None:
        name = str(_numerics._cfg(
            "HOROVOD_COMPRESSION", knob_default("HOROVOD_COMPRESSION")))
    # An explicit rank wins; a "powersgd:r" suffix wins next; the
    # rank knob is only the fallback (resolved here so Config
    # overrides are honored like every other builder knob).
    if rank is None and not any(c in str(name) for c in ":("):
        rank = int(_numerics._cfg(
            "HOROVOD_COMPRESSION_RANK",
            knob_default("HOROVOD_COMPRESSION_RANK")))
    if min_elements is None:
        min_elements = int(_numerics._cfg(
            "HOROVOD_COMPRESSION_MIN_ELEMENTS",
            knob_default("HOROVOD_COMPRESSION_MIN_ELEMENTS")))
    return resolve_compression(name, rank=rank,
                               min_elements=min_elements)


# Introspection for bench/tests, following dispatch.py's
# last_allreduce_info idiom: the LAST build_train_step's overlap
# resolution (written at build time, traced=False) and the LAST
# traced overlap-on step's bucket plan (traced=True). Like every
# last_* surface this is ordering-sensitive — read it right after
# the build/run you mean to inspect, before building another step.
# The partition itself is a pure function of the gradient tree, so
# every process records the identical plan (pinned by the bucketing
# tests).
_last_overlap_info: dict = {}


def last_overlap_info() -> dict:
    return dict(_last_overlap_info)


# ---------------------------------------------------------------------------
# Introspectable overlap plan (the SPMD cross-process contract)
# ---------------------------------------------------------------------------
#
# The bucket assignment and the per-bucket wire layout used to be
# private knowledge of `_bucketed_value_and_grad` (and of the tests
# that re-derived it by hand). They are now a first-class, queryable
# artifact: `overlap_plan()` computes exactly the plan the builder
# will emit for a given (params, mesh, specs, threshold, guard), and
# the jaxpr-tier verifier (analysis/jaxpr_verify.py, rule HVD007)
# checks the TRACED program against it — the agreed collective order
# "identical on every rank by construction" becomes a machine-checked
# invariant instead of a comment.

class WireGroup(NamedTuple):
    """One per-dtype wire array of a bucket's fused reduction.

    `n` counts payload elements INCLUDING the numerics finite-flag
    when it rides this group; `natural_shape` is set when the group
    is a single leaf with nothing riding it (the r08 wire gate: the
    psum goes out in the leaf's own shape, no pack round trip)."""
    dtype: str
    n: int
    rides_flag: bool
    natural_shape: Optional[Tuple[int, ...]]


class OverlapPlan(NamedTuple):
    """The bucketed-overlap reduction plan for one builder config.

    Indices refer to `jax.tree_util.tree_leaves(params)` order.
    `digest` is `bucketing.assignment_digest` over the bucketable
    subsequence's partition — the string every process must derive
    identically for the agreed collective order to hold."""
    threshold: int
    guard: bool
    n_leaves: int
    bucket_leaf_indices: Tuple[Tuple[int, ...], ...]
    bucket_raxes: Tuple[Tuple[str, ...], ...]
    bucket_nbytes: Tuple[int, ...]
    wire: Tuple[Tuple[WireGroup, ...], ...]
    digest: str
    leaf_raxes: Tuple[Tuple[str, ...], ...]
    loose_inexact: Tuple[int, ...]
    # Per-bucket compression tag ("none" / "fp16" / "bf16" /
    # "powersgd:r") — states WHAT transform each bucket's wire takes,
    # so the verifier can tie the traced factor psums / cast wire to
    # the plan and enforce check (e): a compressed bucket's
    # finite-flag vote is a separate exact f32 psum, never a ride on
    # the lossy carrier. All-"none" for uncompressed builds (the
    # digest then stays byte-identical to the historical format).
    bucket_compression: Tuple[str, ...] = ()


def _live_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes with more than one device — the only axes a psum
    moves bytes over. A reduce over a size-1 axis is the identity
    (the r08 wire-gate bug class: dead wire the program should never
    emit)."""
    return tuple(a for a in mesh.shape if mesh.shape[a] > 1)


def _plan_wire(idxs, leaves, guard,
               comp: str = "none") -> Tuple[WireGroup, ...]:
    """Per-dtype wire groups for one bucket — the same split the
    bucket tag packs (split_by_dtype + _flag_carrier_group), computed
    shape-only.

    `comp` is the bucket's compression tag. Cast compression
    ("fp16"/"bf16") rewrites each floating group's wire dtype to the
    cast target; "powersgd:r" replaces the payload groups entirely
    with the two f32 factor psums (packed P then packed Q — the
    order the tag emits them). Under ANY compression the flag never
    rides (check (e)): the vote travels as its own exact f32 scalar
    psum, which is not a wire GROUP (check_numerics matches it
    separately), so no group carries `rides_flag` here."""
    dtypes = [leaves[i].dtype for i in idxs]
    shapes = [tuple(leaves[i].shape) for i in idxs]
    if comp.startswith("powersgd"):
        rank = int(comp.split(":", 1)[1])
        np_el = sum(powersgd_wire_elements(s, rank)[0] for s in shapes)
        nq_el = sum(powersgd_wire_elements(s, rank)[1] for s in shapes)
        return (WireGroup("float32", np_el, False, None),
                WireGroup("float32", nq_el, False, None))
    groups = split_by_dtype([jnp.dtype(d) for d in dtypes])
    if comp in ("fp16", "bf16"):
        caster = (_compression.FP16Compressor if comp == "fp16"
                  else _compression.BF16Compressor)
        out = []
        for positions in groups:
            wd = wire_dtype_of(caster, dtypes[positions[0]])
            n = sum(int(np.prod(shapes[p])) if shapes[p] else 1
                    for p in positions)
            natural = (shapes[positions[0]] if len(positions) == 1
                       else None)
            out.append(WireGroup(str(wd), n, False, natural))
        return tuple(out)
    has_inexact = any(jnp.issubdtype(jnp.dtype(d), jnp.inexact)
                      for d in dtypes)
    flag_gi = (_flag_carrier_group(groups, dtypes)
               if guard and has_inexact else None)
    out = []
    for gi, positions in enumerate(groups):
        rides = flag_gi is not None and gi == flag_gi
        n = sum(int(np.prod(shapes[p])) if shapes[p] else 1
                for p in positions)
        if len(positions) == 1 and not rides:
            out.append(WireGroup(str(dtypes[positions[0]]), n, False,
                                 shapes[positions[0]]))
        else:
            out.append(WireGroup(str(dtypes[positions[0]]),
                                 n + (1 if rides else 0), rides, None))
    return tuple(out)


def plan_overlap(params: Any, mesh: Mesh,
                 param_specs: Any = None, *,
                 overlap_threshold: Optional[int] = None,
                 guard: Optional[bool] = None,
                 compression: Optional[str] = None,
                 compression_rank: Optional[int] = None,
                 compression_min_elements: Optional[int] = None
                 ) -> OverlapPlan:
    """The bucket plan `build_train_step(overlap=True)` will emit.

    Pure function of (leaf structure/shapes/dtypes, mesh shape,
    specs, threshold, guard, compression config) — no devices, no
    tracing — so any process (or the HVD007 verifier) can derive the
    agreed collective schedule without building a step. Defaults
    mirror the builder: threshold from HOROVOD_FUSION_THRESHOLD,
    guard from numerics.guard_enabled(), compression from the
    HOROVOD_COMPRESSION knob family.

    Compression is a bucketing-layer transform: under powersgd,
    eligible leaves (2-D-reshapeable, >= min_elements, replicated
    over every live axis — model-sharded leaves bypass: their
    residual would shard differently per leaf) form their own bucket
    families so a compressed bucket never mixes with bypass leaves;
    `bucket_compression` tags each bucket and the digest carries the
    tags (`|c=powersgd:4`) so the cross-process contract states the
    transform, not just the membership."""
    if param_specs is None:
        param_specs = P()
    bthresh = (overlap_threshold_bytes() if overlap_threshold is None
               else int(overlap_threshold))
    g = _numerics.guard_enabled() if guard is None else bool(guard)
    spec = compression_spec(compression, compression_rank,
                            compression_min_elements)
    leaves = jax.tree_util.tree_leaves(params)
    spec_tree = _broadcast_specs(param_specs, params)
    spec_leaves = jax.tree_util.tree_leaves(
        spec_tree, is_leaf=lambda x: isinstance(x, P))
    live = _live_axes(mesh)
    raxes_of = [tuple(a for a in live
                      if a not in _spec_named_axes(s))
                for s in spec_leaves]
    bucketable = [i for i in range(len(leaves))
                  if raxes_of[i]
                  and jnp.issubdtype(leaves[i].dtype, jnp.inexact)]
    if spec.kind == "powersgd":
        lowrank_set = {
            i for i in bucketable
            if raxes_of[i] == live and powersgd_eligible(
                leaves[i].shape, leaves[i].dtype, spec.min_elements)}

        def key_fn(j, leaf):
            return (raxes_of[bucketable[j]],
                    bucketable[j] in lowrank_set)
    else:
        lowrank_set = set()

        def key_fn(j, leaf):
            return raxes_of[bucketable[j]]
    parts = partition_buckets(
        [leaves[i] for i in bucketable], bthresh, key_fn=key_fn)
    bucket_idx = tuple(tuple(bucketable[j] for j in b.indices)
                       for b in parts)
    bucketed = {i for idxs in bucket_idx for i in idxs}
    if spec.kind == "powersgd":
        comp_tags = tuple(
            f"powersgd:{spec.rank}" if idxs[0] in lowrank_set
            else "none" for idxs in bucket_idx)
    else:
        comp_tags = tuple(spec.kind for _ in bucket_idx)
    return OverlapPlan(
        threshold=bthresh, guard=g, n_leaves=len(leaves),
        bucket_leaf_indices=bucket_idx,
        bucket_raxes=tuple(raxes_of[idxs[0]] for idxs in bucket_idx),
        bucket_nbytes=tuple(int(b.nbytes) for b in parts),
        wire=tuple(_plan_wire(idxs, leaves, g, comp_tags[bid])
                   for bid, idxs in enumerate(bucket_idx)),
        digest=assignment_digest(
            parts, compression=(comp_tags if spec.kind != "none"
                                else None)),
        leaf_raxes=tuple(raxes_of),
        loose_inexact=tuple(
            i for i in range(len(leaves)) if i not in bucketed
            and jnp.issubdtype(leaves[i].dtype, jnp.inexact)),
        bucket_compression=comp_tags)


def init_compression_state(params: Any, mesh: Mesh,
                           param_specs: Any = None, *,
                           compression: Optional[str] = None,
                           compression_rank: Optional[int] = None,
                           compression_min_elements: Optional[int]
                           = None,
                           overlap_threshold: Optional[int] = None,
                           guard: Optional[bool] = None):
    """Initial PowerSGD loop state for `build_train_step(
    compression="powersgd...")` — returns `(state, specs)`.

    `state` is the first-class compression pytree the compressed step
    threads: `{"q": {leaf_idx: (m, r) f32}, "e": {leaf_idx:
    (n_ranks*n, m) f32}}` keyed by flattened-leaf index (string keys
    for stable pytree ordering). Q factors are deterministic
    orthonormal warm starts (`ops.compression.init_q` — identical on
    every process, the SPMD purity contract) and replicated; each
    error-feedback residual is a GLOBAL array whose leading dim
    stacks the per-rank local (n, m) residuals, sharded over the live
    mesh axes by `specs["e"]` so every rank feeds its own slice back
    in — per-rank error memory expressed as one addressable global
    tree, which is exactly what elastic `JaxState` persists across
    restarts (no silent reset; test-pinned).

    Derives eligibility from the SAME `plan_overlap` the builder
    traces, so the state keys match the compressed buckets by
    construction; the builder re-checks at trace time and raises on
    any mismatch rather than letting autodiff hand back zeros (which
    would silently drop accumulated error)."""
    plan = plan_overlap(params, mesh, param_specs,
                        overlap_threshold=overlap_threshold,
                        guard=guard, compression=compression,
                        compression_rank=compression_rank,
                        compression_min_elements=compression_min_elements)
    live = _live_axes(mesh)
    n_red = 1
    for a in live:
        n_red *= mesh.shape[a]
    leaves = jax.tree_util.tree_leaves(params)
    state = {"q": {}, "e": {}}
    for bid, idxs in enumerate(plan.bucket_leaf_indices):
        tag = plan.bucket_compression[bid]
        if not tag.startswith("powersgd"):
            continue
        rank = int(tag.split(":", 1)[1])
        for i in idxs:
            shape = tuple(leaves[i].shape)
            n, m = matrix_shape(shape)
            state["q"][str(i)] = init_q(shape, rank, i)
            state["e"][str(i)] = jnp.zeros((n_red * n, m),
                                           jnp.float32)
    specs = {"q": P(), "e": P(tuple(live)) if live else P()}
    return state, specs


def _fsdp_gather_fn(param_specs, mesh):
    """ZeRO-3 on the explicit-collective path: returns a pytree map
    that all_gathers every fsdp-sharded parameter dim over the `fsdp`
    axis (tiled, in-place dim). Running it INSIDE the differentiated
    loss means JAX's transpose turns each gather into the
    psum_scatter of the gradients — the all-gather(param)/
    reduce-scatter(grad) ZeRO schedule, hand-derived here exactly
    where the GSPMD path lets XLA derive it. Composes with tp/sp/ep:
    only the fsdp axis is gathered, model-parallel dims stay sharded
    for the model's own collectives. None when the mesh doesn't carry
    a live fsdp axis or no spec names it."""
    if mesh.shape.get(FSDP_AXIS, 1) <= 1:
        return None

    def dims_of(spec):
        out = []
        if not isinstance(spec, P):
            return out
        for d, entry in enumerate(spec):
            names = entry if isinstance(entry, tuple) else (entry,)
            if FSDP_AXIS in names:
                if names[0] != FSDP_AXIS:
                    raise ValueError(
                        f"fsdp must be the major axis of a combined "
                        f"dim sharding to gather in place, got {spec}")
                out.append(d)
        return out

    any_fsdp = any(
        dims_of(s) for s in jax.tree.leaves(
            param_specs, is_leaf=lambda x: isinstance(x, P)))
    if not any_fsdp:
        return None

    def gather(params):
        def one(p, spec):
            for d in dims_of(spec):
                p = lax.all_gather(p, FSDP_AXIS, axis=d, tiled=True)
            return p
        return jax.tree.map(one, params,
                            _broadcast_specs(param_specs, params))

    return gather


def _broadcast_specs(specs, tree):
    """Expand a single P into a per-leaf spec tree when needed."""
    if isinstance(specs, P):
        return jax.tree.map(lambda _: specs, tree)
    return specs


def _psum_axes(x, axes: Tuple[str, ...]):
    for a in axes:
        x = lax.psum(x, a)
    return x


def _pmean_axes(x, axes: Tuple[str, ...]):
    for a in axes:
        x = lax.pmean(x, a)
    return x


def infer_opt_state_specs(optimizer: optax.GradientTransformation,
                          example_params: Any, param_specs: Any) -> Any:
    """Derive PartitionSpecs for an optax state tree: any state leaf
    whose tree path ends with a parameter's path (optax stores moments
    as params-shaped subtrees) inherits that parameter's spec;
    everything else (counts, scalars) is replicated."""
    flat_params = jax.tree_util.tree_flatten_with_path(example_params)[0]
    flat_specs = jax.tree_util.tree_leaves(
        param_specs, is_leaf=lambda x: isinstance(x, P))
    if len(flat_specs) == 1:
        flat_specs = flat_specs * len(flat_params)
    by_path = {tuple(str(k) for k in path): (spec, tuple(p.shape))
               for (path, p), spec in zip(flat_params, flat_specs)}
    state_shape = jax.eval_shape(optimizer.init, example_params)

    def leaf_spec(path, leaf):
        keys = tuple(str(k) for k in path)
        for plen in range(len(keys), 0, -1):
            suffix = keys[-plen:]
            if suffix in by_path:
                spec, pshape = by_path[suffix]
                # only adopt if shapes agree — guards against key-name
                # collisions (e.g. scalar state stored under a
                # param-named key by inject_hyperparams/schedules).
                if tuple(leaf.shape) == pshape:
                    return spec
                return P()
        return P()

    return jax.tree_util.tree_map_with_path(leaf_spec, state_shape)


def _spec_named_axes(spec) -> set:
    """Mesh-axis names a PartitionSpec shards over."""
    named = set()
    if isinstance(spec, P):
        for entry in spec:
            if entry is None:
                continue
            for nm in (entry if isinstance(entry, tuple) else (entry,)):
                named.add(nm)
    return named


def _flag_carrier_group(groups, dtypes):
    """Index (into `groups`) of the per-dtype wire group whose packed
    psum the bucket's finite-flag rides, or None. Exact-count dtypes
    only (f32/f64): a 0/1 vote COUNT accumulated in bf16/fp16 stops
    being integer-exact past a few hundred ranks (the same rule that
    keeps the eager fused ride off lossy-compressed groups — see
    numerics.local_finite_flag); those buckets carry the veto via a
    separate exact f32 psum instead."""
    for gi, positions in enumerate(groups):
        if str(dtypes[positions[0]]) in ("float32", "float64"):
            return gi
    return None


def _make_bucket_tag(bucket_id: int, raxes: Tuple[str, ...],
                     all_axes: Tuple[str, ...],
                     shapes: Tuple, dtypes: Tuple, scale,
                     guard: bool, probe, wire_cast=None):
    """custom_vjp identity over one bucket of parameter leaves whose
    BACKWARD rule is the bucket's fused reduction: the cotangents are
    flattened and packed into one wire array per dtype (the in-jit
    MemcpyInFusionBuffer, mirroring dispatch._pack), psum'd over the
    bucket's reduce axes, and unpacked — emitted exactly where the
    cotangents are produced, so the reduction sits INSIDE the backward
    pass and XLA's async collectives can hide it under the remaining
    backprop (reference: the fusion-buffer + gradient-hook overlap of
    SURVEY.md §0/§2.1, compiled instead of threaded).

    The guard's finite-flag rides the same psum as one extra packed
    element (see _flag_carrier_group); its reduced count leaves the
    backward pass as the cotangent of a zero `dummy` scalar — the only
    way a value computed in a bwd rule can reach the caller of
    value_and_grad.

    The forward lifts each leaf to varying over the reduce axes
    (lax.pcast to='varying'), so no implicit pbroadcast (whose
    transpose would psum the cotangent BEFORE it reaches this bwd
    rule) is inserted downstream — the bucket psum here is the one
    and only reduction.

    `probe` (timeline verification only, off by default): host
    callbacks on the packed wire array (cotangents ready) and on the
    reduced array (reduction done) timestamp each bucket's reduce
    span against the surrounding backprop in real execution order.

    `wire_cast` (fp16/bf16 wire compression): floating wire arrays
    are cast to this dtype before the psum and back after — the
    reference's MemcpyInFusionBuffer cast, fused into the same XLA
    region as the pack. The finite-flag must NEVER ride a lossy
    carrier (a 0/1 vote COUNT in half precision stops being
    integer-exact, and the carrier itself is now lossy — HVD007
    check (e)), so under any cast the flag takes the separate exact
    f32 psum path below (`flag_gi is None`), the invariant the
    numerics PR carved out for exactly this case. None (the default)
    changes NOTHING in the traced program — the HLO-identity test
    pins compression=none to today's builder byte-for-byte.
    """
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    groups = split_by_dtype([jnp.dtype(d) for d in dtypes])
    flag_gi = (_flag_carrier_group(groups, dtypes)
               if guard and wire_cast is None else None)

    def _cast_dt(dt):
        """Wire dtype of one group under the cast (identity for
        non-floating and already-at-wire groups)."""
        if wire_cast is not None and jnp.issubdtype(
                jnp.dtype(dt), jnp.floating):
            return jnp.dtype(wire_cast)
        return jnp.dtype(dt)
    has_inexact = any(jnp.issubdtype(jnp.dtype(d), jnp.inexact)
                      for d in dtypes)
    # Axes the bucket's leaves are SHARDED over: the flag count must
    # still fold them (a NaN confined to one shard of a model-sharded
    # leaf would otherwise split the skip decision per device — see
    # _unanimity), so the scalar gets one extra tiny psum after the
    # ride.
    rem_axes = tuple(a for a in all_axes if a not in raxes)

    def _psum_r(x):
        for a in raxes:
            x = lax.psum(x, a)
        return x

    def _primal(xs):
        return tuple(lax.pcast(x, raxes, to="varying") for x in xs)

    @jax.custom_vjp
    def tag(dummy, *xs):
        return _primal(xs)

    def fwd(dummy, *xs):
        return _primal(xs), None

    def bwd(_, cts):
        # The all-reduce instruction carries its bucket's name into
        # a profiler trace (tracing.DEVICE_SCOPES).
        with bucket_scope(bucket_id):
            return reduce_bucket(cts)

    def reduce_bucket(cts):
        outs: list = [None] * len(cts)
        rflag = jnp.zeros((), jnp.float32)
        flag = None
        if guard and has_inexact:
            flag = _numerics.local_finite_flag(list(cts))
        for gi, positions in enumerate(groups):
            rides = flag is not None and gi == flag_gi
            if len(positions) == 1 and not rides:
                # Single-leaf wire group with nothing riding it (the
                # common shape for oversized leaves — the flagship's
                # 134 MB embed gets a bucket of its own): psum the
                # cotangent in its NATURAL shape. The packed path's
                # reshape(-1) -> slice -> reshape round trip buys
                # nothing here (there is no packing to do) and is
                # pure layout traffic the trace bills to
                # copy_reshape; this elides it.
                p = positions[0]
                ct = cts[p]
                wd = _cast_dt(ct.dtype)
                if wd != ct.dtype:
                    ct = ct.astype(wd)
                wire_nbytes = int(ct.size) * ct.dtype.itemsize
                if probe is not None:
                    jax.debug.callback(
                        lambda _t, b=bucket_id, nb=wire_nbytes:
                            probe(b, "ready", nb),
                        ct.reshape(-1)[0])
                red = _psum_r(ct)
                if wd != cts[p].dtype:
                    red = red.astype(cts[p].dtype)
                if probe is not None:
                    jax.debug.callback(
                        lambda _t, b=bucket_id, nb=wire_nbytes:
                            probe(b, "reduced", nb),
                        red.reshape(-1)[0])
                if scale is not None:
                    red = red * jnp.asarray(scale, red.dtype)
                outs[p] = red
                continue
            flats = [cts[p].reshape(-1) for p in positions]
            concat = (jnp.concatenate(flats) if len(flats) > 1
                      else flats[0])
            if rides:
                concat = jnp.concatenate(
                    [concat, flag.astype(concat.dtype).reshape(1)])
            gdt = concat.dtype
            wd = _cast_dt(gdt)
            if wd != gdt:
                concat = concat.astype(wd)
            wire_nbytes = int(concat.size) * concat.dtype.itemsize
            if probe is not None:
                # Data dependency on one element anchors the callback
                # at the pack's completion without copying the bucket
                # to the host; statics ride the closure.
                jax.debug.callback(
                    lambda _t, b=bucket_id, nb=wire_nbytes:
                        probe(b, "ready", nb),
                    concat[0])
            red = _psum_r(concat)
            if probe is not None:
                jax.debug.callback(
                    lambda _t, b=bucket_id, nb=wire_nbytes:
                        probe(b, "reduced", nb),
                    red[0])
            if wd != gdt:
                red = red.astype(gdt)
            if rides:
                rflag = red[-1].astype(jnp.float32)
                red = red[:-1]
            off = 0
            for p in positions:
                seg = red[off:off + sizes[p]].reshape(shapes[p])
                if scale is not None:
                    seg = seg * jnp.asarray(scale, seg.dtype)
                outs[p] = seg
                off += sizes[p]
        if flag is not None and flag_gi is None:
            # No exact-count wire group in this bucket: the veto
            # travels as its own (tiny, still-inline) f32 psum.
            rflag = _psum_r(flag)
        if flag is not None:
            for a in rem_axes:
                rflag = lax.psum(rflag, a)
        return (rflag,) + tuple(outs)

    tag.defvjp(fwd, bwd)
    return tag


def _make_powersgd_tag(bucket_id: int, raxes: Tuple[str, ...],
                       shapes: Tuple, dtypes: Tuple, scale,
                       guard: bool, probe,
                       rank: int, n_devices: int):
    """custom_vjp identity over one PowerSGD bucket: the backward
    rule runs the low-rank factor handshake of
    `ops.compression.powersgd_reduce` instead of the dense psum —
    compress (M @ Q), all-reduce the packed P factors, one
    Gram-matrix orthogonalization, all-reduce the packed Q' factors,
    decompress (P @ Q'^T) — all inside the same overlap boundary the
    dense tag occupies, so XLA schedules the (much smaller) factor
    psums under the remaining backprop exactly like dense buckets.

    Loop state rides autodiff's own channel: the warm Q factors and
    error-feedback residuals enter as extra primal inputs and the
    UPDATED factors/residuals leave as their cotangents (the same
    only-way-out-of-a-bwd-rule trick the finite-flag uses via its
    dummy), so `build_train_step` threads compression state through
    `jax.value_and_grad` with no second tracing mechanism.

    The numerics finite-flag vote stays EXACT (HVD007 check (e)):
    computed on the RAW cotangents and psum'd as its own f32 scalar —
    it never touches the factor wire. The vote also gates the state
    update: on a vetoed (non-finite) step the new Q/residual are the
    OLD Q/residual, so a poisoned step cannot corrupt the error
    memory (mirror of guard_non_finite freezing the inner optimizer
    state on skip).

    PowerSGD-eligible leaves are replicated over every live mesh axis
    (plan_overlap's eligibility gate), so `raxes` here is the full
    live set and no rem-axes flag fold is needed."""
    nleaves = len(shapes)
    mats = [matrix_shape(s) for s in shapes]
    ranks = [effective_rank(s, rank) for s in shapes]
    wire_total = 4 * sum(n * r + m * r
                         for (n, m), r in zip(mats, ranks))

    def _psum_r(x):
        for a in raxes:
            x = lax.psum(x, a)
        return x

    def _primal(xs):
        return tuple(lax.pcast(x, raxes, to="varying") for x in xs)

    @jax.custom_vjp
    def tag(dummy, *args):
        return _primal(args[2 * nleaves:])

    def fwd(dummy, *args):
        return (_primal(args[2 * nleaves:]),
                (args[:nleaves], args[nleaves:2 * nleaves]))

    def bwd(res, cts):
        with bucket_scope(bucket_id):
            return reduce_bucket(res, cts)

    def reduce_bucket(res, cts):
        qs, es = res
        flag = None
        if guard:
            flag = _numerics.local_finite_flag(list(cts))
        ms = [cts[i].astype(jnp.float32).reshape(mats[i])
              for i in range(nleaves)]
        calls = {"n": 0}

        def psum_fn(flat):
            first = calls["n"] == 0
            calls["n"] += 1
            if probe is not None and first:
                jax.debug.callback(
                    lambda _t, b=bucket_id, nb=wire_total:
                        probe(b, "ready", nb),
                    flat[0])
            red = _psum_r(flat)
            if probe is not None and not first:
                jax.debug.callback(
                    lambda _t, b=bucket_id, nb=wire_total:
                        probe(b, "reduced", nb),
                    red[0])
            return red

        outs, new_qs, new_es = powersgd_reduce(
            ms, list(qs), list(es), psum_fn, n_devices)
        rflag = jnp.zeros((), jnp.float32)
        if flag is not None:
            rflag = _psum_r(flag)
            ok = rflag > n_devices - 0.5
            new_qs = [jnp.where(ok, nq, q)
                      for nq, q in zip(new_qs, qs)]
            new_es = [jnp.where(ok, ne, e)
                      for ne, e in zip(new_es, es)]
        grads = []
        for i in range(nleaves):
            o = outs[i]
            if scale is not None:
                o = o * jnp.asarray(scale, o.dtype)
            grads.append(o.reshape(shapes[i]).astype(dtypes[i]))
        return (rflag,) + tuple(new_qs) + tuple(new_es) + tuple(grads)

    tag.defvjp(fwd, bwd)
    return tag


def build_train_step(
    loss_fn: Callable[..., Any],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    *,
    batch_spec: Optional[P] = None,
    param_specs: Any = None,
    opt_state_specs: Any = None,
    grad_reducer: Optional[Callable[[Any], Any]] = None,
    loss_has_aux: bool = False,
    donate: bool = True,
    check_vma: bool = True,
    overlap: Optional[bool] = None,
    overlap_threshold: Optional[int] = None,
    overlap_probe: Optional[Callable] = None,
    compression: Optional[str] = None,
    compression_rank: Optional[int] = None,
    compression_min_elements: Optional[int] = None,
) -> Callable:
    """Build `step(params, opt_state, batch) -> (params, opt_state,
    metrics)` as a single jitted shard_map over `mesh`.

    Gradient wire compression (`compression`, default = the
    HOROVOD_COMPRESSION knob family, "none"): a per-bucket transform
    inside the overlap boundary. "fp16"/"bf16" cast each bucket's
    wire; "powersgd[:r]" low-rank-compresses eligible dense matrices
    with error feedback and CHANGES THE STEP SIGNATURE to
    `step(params, opt_state, batch, compression_state) -> (params,
    opt_state, metrics, compression_state)` — build the state with
    `init_compression_state` (same config) and persist it in elastic
    `JaxState(compression_state=...)` so restarts keep the residual.
    compression="none" lowers BYTE-IDENTICAL HLO to today's builder
    (test-pinned); any compression requires the overlap path (the
    buckets are the carrier). HOROVOD_COMPRESSION_WARMUP_STEPS is a
    harness-level contract on this plane: run the compression="none"
    build for the first N steps, then switch programs (see the knob's
    registry doc).

    check_vma=False disables shard_map's static replication checker —
    required when the loss contains Pallas kernels whose pallas_call
    cannot declare varying-mesh-axes types (e.g. the TPU flash-
    attention kernel); out_specs correctness then rests on the
    explicit pmeans/psums, which this builder already emits.

    loss_fn(params, batch) -> loss (or (loss, aux) with
    loss_has_aux=True) computes the LOCAL loss on this device's batch
    shard; collectives inside loss_fn (tp/sp/ep) are allowed — the
    whole step runs under shard_map with all mesh axes manual.

    Gradient semantics: under shard_map's VMA typing the local-loss
    gradients arrive already psum'd over every axis a parameter is
    replicated across — including the batch axes. The default reducer
    therefore just scales by 1/n_batch to produce the mean (the
    hvd.DistributedOptimizer contract). A custom `grad_reducer`
    receives those SUMMED gradients and owns all scaling itself —
    do NOT pmean inside it (the values are already replicated across
    the batch axes, so a pmean is a no-op and the result stays
    n_batch× too large).

    Backprop-overlapped reduction (`overlap`, default = the
    HOROVOD_JIT_OVERLAP knob, on): gradient leaves pack into
    `overlap_threshold`-byte buckets (default HOROVOD_FUSION_THRESHOLD
    — the shared partitioner in ops/bucketing.py) in reverse
    (last-produced-first) order, and each bucket's fused psum is
    emitted inside the backward pass via a custom_vjp boundary the
    moment its cotangents exist, so XLA's async collectives hide the
    reduction under the remaining backprop — the jit-path mirror of
    the eager fusion-buffer overlap. Numerics are identical to the
    monolithic path (test-pinned), the numerics finite-flag rides each
    bucket's psum, and `overlap=False` lowers BYTE-IDENTICALLY to the
    pre-overlap builder (the HLO-identity test pins this too).
    `overlap_probe` (verification only) is a host callback
    `(bucket_id, phase, nbytes)` timestamping each bucket's
    ready/reduced edges — see tracing.OverlapProbe.
    """
    unknown = [a for a in mesh.axis_names if a not in AXIS_ORDER]
    if unknown:
        # The batch is sharded over data/fsdp/expert only; under an
        # axis outside the vocabulary every device would redo the
        # same batch and the two reduction paths disagree on what a
        # replicated loss's gradient is.
        raise ValueError(
            f"build_train_step: mesh axis {unknown[0]!r} is not one of "
            f"{AXIS_ORDER}; build the mesh with data_parallel_mesh() "
            "or make_mesh()")
    baxes = batch_axes(mesh)
    n_batch = 1
    for a in baxes:
        n_batch *= mesh.shape[a]
    batch_spec = batch_spec if batch_spec is not None else P(
        baxes if len(baxes) > 1 else (baxes[0] if baxes else None))

    if param_specs is None:
        param_specs = P()  # replicated params (pure DP)
    if opt_state_specs is None:
        opt_state_specs = param_specs if isinstance(param_specs, P) \
            else P()

    # Gradient semantics under shard_map VMA typing: each parameter is
    # unvarying (replicated) over every mesh axis its spec does not
    # name, so its local-loss gradient is automatically psum'd over
    # those axes by the transpose machinery — including the batch
    # axes. The true data-parallel MEAN gradient is therefore that
    # psum divided by the batch-axis product; one uniform scale is
    # correct for replicated AND model-sharded parameters alike.

    # Coordinated skip-step (numerics.py): decided once at build time
    # so a disabled guard changes NOTHING in the traced program (the
    # HLO-identity acceptance test pins this).
    guard = _numerics.guard_enabled()
    n_devices = 1
    for a in mesh.shape:
        n_devices *= mesh.shape[a]

    def _unanimity(flag):
        """Coordinated vote: psum the 0/1 finite-flag over EVERY mesh
        axis and demand all devices voted finite — the min-reduce
        riding the same XLA program as the data psums. A NaN confined
        to ONE shard of a model-sharded parameter yields a flag that
        differs across that axis, so a per-device decision would step
        some replicas and skip others (silently diverging replicated
        params); unanimity is the only safe decision. The flag's
        varying-type is inherited from the gradient leaves, and psum
        over an axis the flag is unvarying on is rejected by the
        typing — lift the missing axes first. EVERY axis is folded,
        size-1 ones included: the psum is what flips the flag's
        varying-type to unvarying, so a size-1 axis' psum is
        type-required (and wire-free — XLA elides it)."""
        axis_names = tuple(mesh.shape.keys())
        missing = tuple(a for a in axis_names
                        if a not in jax.typeof(flag).vma)
        if missing:
            flag = lax.pcast(flag, missing, to="varying")
        cnt = _psum_axes(flag, axis_names)
        return cnt > n_devices - 0.5

    def reduce_grads(grads):
        with device_scope("hvd.grad_reduce"):
            return scale_and_vote(grads)

    def scale_and_vote(grads):
        ok = None
        if guard:
            # Local finite-flag over the incoming gradients, then the
            # explicit all-axes unanimity vote (the automatic psums
            # only folded each leaf's REPLICATED axes, which is not
            # device-global for sharded leaves).
            flag = _numerics.local_finite_flag(
                jax.tree_util.tree_leaves(grads))
            ok = _unanimity(flag)
        if grad_reducer is not None:
            out = grad_reducer(grads)
        elif n_batch == 1:
            out = grads
        else:
            inv = 1.0 / n_batch
            out = jax.tree.map(
                lambda g: g * jnp.asarray(inv, g.dtype), grads)
        if guard:
            out = _numerics.imprint_non_finite(out, ok)
        return out

    # ZeRO-3 leg of the explicit path: gather fsdp-sharded params
    # inside the differentiated region (transpose = grad scatter).
    fsdp_gather = _fsdp_gather_fn(param_specs, mesh)
    eff_loss = (loss_fn if fsdp_gather is None else
                (lambda params, batch: loss_fn(fsdp_gather(params),
                                               batch)))

    # Bucketed backprop-overlapped reduction (the jit-path mirror of
    # the eager fusion-buffer overlap): resolved once at BUILD time —
    # like the numerics guard — so the off position changes NOTHING in
    # the traced program (the HLO-identity acceptance test pins that
    # overlap=off lowers byte-identically to the monolithic builder).
    use_overlap = (overlap_enabled() if overlap is None
                   else bool(overlap))
    bthresh = (overlap_threshold_bytes() if overlap_threshold is None
               else int(overlap_threshold))
    cspec = compression_spec(compression, compression_rank,
                             compression_min_elements)
    if cspec.kind != "none" and not use_overlap:
        raise ValueError(
            f"HOROVOD_COMPRESSION={cspec.tag()} requires the bucketed "
            "overlap path (the buckets are the compression carrier); "
            "enable HOROVOD_JIT_OVERLAP / overlap=True or set "
            "compression='none'")
    use_powersgd = cspec.kind == "powersgd"
    live_axes = _live_axes(mesh)
    # Bucketed-path scale: the 1/n_batch mean, unless a custom
    # reducer owns scaling.
    default_scale = (1.0 / n_batch
                     if grad_reducer is None and n_batch != 1 else None)

    def _bucketed_value_and_grad(params, batch, cstate=None):
        """value_and_grad with per-bucket custom_vjp boundaries: each
        bucket's fused psum is emitted INSIDE the backward pass, as
        soon as its cotangents exist (reverse topological bucket
        order), instead of as one end-of-step block — XLA's async
        collectives then hide the reduction under the remaining
        backprop. Returns (loss, aux, reduced_grads, new_cstate) —
        the guard's unanimity vote is already folded in via
        imprint_non_finite, and `new_cstate` is the updated PowerSGD
        compression state (warm Q factors + error-feedback residual,
        exiting the custom_vjp boundary as the cotangent of the state
        inputs; None unless compression is powersgd).

        The bucket assignment comes from `plan_overlap` — the same
        introspectable plan the HVD007 jaxpr verifier checks the
        traced program against. Leaves sharded over EVERY live mesh
        axis need no reduction; integer/bool leaves carry float0
        cotangents (zero-size — nothing to pack or reduce); and a
        leaf with no LIVE reduce axes has no wire at all — its psum
        is the identity, so packing it buys nothing and costs the
        full flatten/concat/psum/unpack round trip (the r08
        attribution: +41 dead instructions incl. 5 pack all-reduces
        on the world-1 transformer step, +5.4% jit ResNet throughput
        from eliding them). All three stay outside the buckets and
        pass through exactly as on the monolithic path; a single-chip
        program therefore lowers with no bucket machinery whatsoever,
        and a size-1 mesh axis never appears in any bucket's reduce
        set (r10: the verifier caught the numerics/multi-axis paths
        still shipping size-1-axis psums; _live_axes now gates every
        leg)."""
        leaves, treedef = jax.tree_util.tree_flatten(params)
        plan = plan_overlap(params, mesh, param_specs,
                            overlap_threshold=bthresh, guard=guard,
                            compression=cspec.tag(),
                            compression_min_elements=cspec.min_elements)
        bucket_idx = plan.bucket_leaf_indices
        comp_tags = plan.bucket_compression
        raw_bytes = int(sum(plan.bucket_nbytes))
        wire_bytes = int(sum(
            g.n * jnp.dtype(g.dtype).itemsize
            for groups in plan.wire for g in groups))
        _last_overlap_info.clear()
        _last_overlap_info.update(
            enabled=True, traced=True, threshold=bthresh,
            buckets=len(bucket_idx),
            bucket_bytes=list(plan.bucket_nbytes),
            bucket_leaves=[len(idxs) for idxs in bucket_idx],
            n_leaves=len(leaves), digest=plan.digest,
            compression=cspec.tag(), raw_bucket_bytes=raw_bytes,
            wire_bucket_bytes=wire_bytes)
        if cspec.kind != "none" and raw_bytes:
            # Per-program wire accounting at trace time (the jit
            # plane's wire is static per compile — the per-step
            # counters live on the eager plane): one record per
            # compiled program states what the wire costs.
            from ..metrics import record_wire
            record_wire(cspec.tag(), raw_bytes, wire_bytes)
        tags = []
        for bid, idxs in enumerate(bucket_idx):
            bshapes = tuple(tuple(leaves[i].shape) for i in idxs)
            bdtypes = tuple(leaves[i].dtype for i in idxs)
            ctag = comp_tags[bid]
            if ctag.startswith("powersgd"):
                tags.append(_make_powersgd_tag(
                    bid, plan.bucket_raxes[bid], bshapes, bdtypes,
                    default_scale, guard, overlap_probe,
                    int(ctag.split(":", 1)[1]), n_devices))
            else:
                tags.append(_make_bucket_tag(
                    bid, plan.bucket_raxes[bid], live_axes,
                    bshapes, bdtypes,
                    default_scale, guard, overlap_probe,
                    wire_cast=(jnp.dtype(jnp.float16)
                               if ctag == "fp16" else
                               jnp.dtype(jnp.bfloat16)
                               if ctag == "bf16" else None)))
        dummies = tuple(jnp.zeros((), jnp.float32) for _ in bucket_idx)
        lowrank_leaves = [i for bid, idxs in enumerate(bucket_idx)
                         if comp_tags[bid].startswith("powersgd")
                         for i in idxs]
        if use_powersgd:
            have = set() if cstate is None else set(cstate["q"])
            want = {str(i) for i in lowrank_leaves}
            if have != want:
                raise ValueError(
                    "compression_state does not match the compressed "
                    f"leaf set (state has {sorted(have)}, plan "
                    f"compresses {sorted(want)}); build it with "
                    "init_compression_state under the SAME mesh/"
                    "specs/threshold/compression config — a mismatch "
                    "would silently zero the error-feedback residual")

        def apply_tags(lvs, dummies_t, cstate_t):
            for bid, (tag, idxs, d) in enumerate(
                    zip(tags, bucket_idx, dummies_t)):
                if comp_tags[bid].startswith("powersgd"):
                    qs = [cstate_t["q"][str(i)] for i in idxs]
                    es = [cstate_t["e"][str(i)] for i in idxs]
                    ys = tag(d, *qs, *es, *[lvs[i] for i in idxs])
                else:
                    ys = tag(d, *[lvs[i] for i in idxs])
                for i, y in zip(idxs, ys):
                    lvs[i] = y
            return lvs

        if use_powersgd:
            def wrapped(leaves_t, dummies_t, cstate_t, batch):
                lvs = apply_tags(list(leaves_t), dummies_t, cstate_t)
                p = jax.tree_util.tree_unflatten(treedef, lvs)
                return eff_loss(p, batch)

            vg = jax.value_and_grad(wrapped, argnums=(0, 1, 2),
                                    has_aux=loss_has_aux)
            if loss_has_aux:
                (loss, aux), (glvs, gflags, new_cstate) = vg(
                    tuple(leaves), dummies, cstate, batch)
            else:
                loss, (glvs, gflags, new_cstate) = vg(
                    tuple(leaves), dummies, cstate, batch)
                aux = None
        else:
            def wrapped(leaves_t, dummies_t, batch):
                lvs = apply_tags(list(leaves_t), dummies_t, None)
                p = jax.tree_util.tree_unflatten(treedef, lvs)
                return eff_loss(p, batch)

            vg = jax.value_and_grad(wrapped, argnums=(0, 1),
                                    has_aux=loss_has_aux)
            if loss_has_aux:
                (loss, aux), (glvs, gflags) = vg(tuple(leaves),
                                                 dummies, batch)
            else:
                loss, (glvs, gflags) = vg(tuple(leaves), dummies,
                                          batch)
                aux = None
            new_cstate = None
        glvs = list(glvs)
        bucketed = {i for idxs in bucket_idx for i in idxs}
        # Un-bucketed inexact leaves: same treatment the monolithic
        # path gives them — no psum (their spec names every axis),
        # uniform scale. float0 (int-leaf) cotangents pass through.
        if default_scale is not None:
            for i in range(len(glvs)):
                if i not in bucketed and jnp.issubdtype(
                        leaves[i].dtype, jnp.inexact):
                    glvs[i] = glvs[i] * jnp.asarray(
                        default_scale, glvs[i].dtype)
        ok = None
        if guard:
            # Fold the per-bucket reduced vote counts (each already a
            # device-global count — the bwd rule lifts its flag over
            # the bucket's non-reduce axes too) into one unanimity
            # decision, exactly the semantics of _unanimity on the
            # monolithic path: any rank's non-finite veto skips the
            # step on EVERY rank.
            votes = []
            for bid, idxs in enumerate(bucket_idx):
                if any(jnp.issubdtype(leaves[i].dtype, jnp.inexact)
                       for i in idxs):
                    votes.append(gflags[bid] > n_devices - 0.5)
            loose = [glvs[i] for i in range(len(glvs))
                     if i not in bucketed
                     and jnp.issubdtype(leaves[i].dtype, jnp.inexact)]
            if loose:
                votes.append(_unanimity(
                    _numerics.local_finite_flag(loose)))
            if votes:
                ok = votes[0]
                for v in votes[1:]:
                    ok = jnp.logical_and(ok, v)
        grads = jax.tree_util.tree_unflatten(treedef, glvs)
        if grad_reducer is not None:
            grads = grad_reducer(grads)
        if ok is not None:
            grads = _numerics.imprint_non_finite(grads, ok)
        return loss, aux, grads, new_cstate

    def _replicate_metric(x):
        """Average a metric over every batch axis (size-1 ones too:
        the psum inside pmean is what makes the value unvarying so it
        satisfies the replicated P() out_spec) and over any model
        axis it is still typed varying on — the overlap tags lift
        replicated params to varying, which can leave an
        equal-valued loss varying-typed there."""
        vma = jax.typeof(x).vma
        return _pmean_axes(x, tuple(a for a in mesh.shape
                                    if a in baxes or a in vma))

    def _finish_step(loss, aux, grads, params, opt_state):
        with device_scope("hvd.optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state,
                                                  params)
            params = optax.apply_updates(params, updates)
        metrics = {"loss": _replicate_metric(loss)}
        if aux is not None:
            # aux is device-varying; average it so metrics satisfy the
            # replicated (P()) out_spec.
            metrics["aux"] = jax.tree.map(_replicate_metric, aux)
        return params, opt_state, metrics

    if use_powersgd:
        # PowerSGD threads explicit loop state: the step takes and
        # returns the compression state (warm Q + error-feedback
        # residual) as a 4th argument/result, the same way the
        # optimizer state rides the step. Q is replicated; the
        # residual is the stacked per-rank error memory, sharded
        # over the live reduce axes so each rank feeds back exactly
        # the error ITS compressed contribution left behind.
        def local_step(params, opt_state, batch, cstate):
            loss, aux, grads, new_cstate = _bucketed_value_and_grad(
                params, batch, cstate)
            params, opt_state, metrics = _finish_step(
                loss, aux, grads, params, opt_state)
            return params, opt_state, metrics, new_cstate
    else:
        def local_step(params, opt_state, batch):
            if use_overlap:
                loss, aux, grads, _ = _bucketed_value_and_grad(
                    params, batch)
            else:
                if loss_has_aux:
                    (loss, aux), grads = jax.value_and_grad(
                        eff_loss, has_aux=True)(params, batch)
                else:
                    loss, grads = jax.value_and_grad(eff_loss)(
                        params, batch)
                    aux = None
                grads = reduce_grads(grads)
            return _finish_step(loss, aux, grads, params, opt_state)

    # Reset the introspection dict at BUILD time on both branches so
    # last_overlap_info() never reports a previous builder's bucket
    # plan for a step that has not traced yet (traced=False flips
    # when the overlap-on step records its real plan at first trace).
    _last_overlap_info.clear()
    _last_overlap_info.update(enabled=use_overlap, threshold=bthresh,
                              traced=False)

    if use_powersgd:
        cstate_specs = {
            "q": P(),
            "e": P(tuple(live_axes)) if live_axes else P(),
        }
        step = shard_map(
            local_step, mesh=mesh,
            in_specs=(param_specs, opt_state_specs, batch_spec,
                      cstate_specs),
            out_specs=(param_specs, opt_state_specs, P(),
                       cstate_specs),
            check_vma=check_vma,
        )
        donate_argnums = (0, 1, 3) if donate else ()
    else:
        step = shard_map(
            local_step, mesh=mesh,
            in_specs=(param_specs, opt_state_specs, batch_spec),
            out_specs=(param_specs, opt_state_specs, P()),
            check_vma=check_vma,
        )
        donate_argnums = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)


def build_gspmd_train_step(
    loss_fn: Callable[..., Any],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    *,
    param_shardings: Any = None,
    batch_sharding: Optional[NamedSharding] = None,
    loss_has_aux: bool = False,
    donate: bool = True,
) -> Callable:
    """Constraint-based variant: plain jit; XLA's SPMD partitioner
    derives every collective from the in/out shardings. loss_fn sees
    GLOBAL arrays.

    Backprop overlap on this path is XLA-SCHEDULED by design: the
    partitioner inserts the gradient reduces where the cotangents are
    produced and the latency-hiding scheduler overlaps them — the
    compiler already holds the whole-program schedule that the
    explicit-collective builder reconstructs manually with its
    reverse-order buckets (HOROVOD_JIT_OVERLAP), so no manual bucket
    hints are added here; HOROVOD_FUSION_THRESHOLD does not apply
    (XLA's own collective-combiner thresholds govern fusion)."""
    baxes = batch_axes(mesh)
    if batch_sharding is None:
        batch_sharding = NamedSharding(
            mesh, P(baxes if len(baxes) > 1 else
                    (baxes[0] if baxes else None)))
    if param_shardings is None:
        param_shardings = replicated(mesh)

    def step(params, opt_state, batch):
        batch = lax.with_sharding_constraint(batch, batch_sharding)
        if loss_has_aux:
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            metrics = {"loss": loss, "aux": aux}
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            metrics = {"loss": loss}
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, metrics

    donate_argnums = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)


# Introspectable builder registry: the step builders whose traced
# programs carry the framework's collective contract. The HVD007
# jaxpr verifier (analysis/jaxpr_verify.py) enumerates THIS — plus
# `plan_overlap` for the expected wire schedule — instead of
# hardcoding test-private knowledge of which builders exist and what
# they promise. "explicit" builders emit their own collectives (the
# verifier checks them against the plan); "compiler" builders
# delegate collective insertion to XLA's SPMD partitioner (nothing to
# verify at the jaxpr tier — the partitioner runs below it).
STEP_BUILDERS = {
    "shard_map": {"build": build_train_step, "collectives": "explicit"},
    "gspmd": {"build": build_gspmd_train_step,
              "collectives": "compiler"},
}
