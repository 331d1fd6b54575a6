"""HVD007 — jaxpr-tier SPMD collective verifier: the invariant
checkers.

This is the SEMANTIC tier of hvdlint: where HVD001–HVD006 are pure
AST (they never import the code under analysis), HVD007 inspects the
*traced training program* — the closed jaxprs `jax.make_jaxpr`
produces for the repo's real step builders under `Mesh` contexts
(zero FLOPs, no accelerator needed). Everything `jax.jit` hides from
the AST tier — which collectives actually lower, over which axes, in
which order, carrying what — is exactly what this tier sees.

The module has two halves:

  * a generic jaxpr WALKER (`collect_collectives`) that recurses
    through pjit/shard_map/scan/cond/custom-call sub-jaxprs and
    returns every collective primitive in trace order, annotated with
    liveness (does its result reach any output?) and a reduced-axes
    dataflow fact (which axes its operand was ALREADY psum'd over);
  * the INVARIANT checks over that stream — axis names exist in the
    ambient mesh, no reduce over a size-1 axis (the r08 wire-gate bug
    class), no dead collectives, no double reduction over the same
    axis (the r08 legacy psum-transpose over-count class), the traced
    wire psums match `parallel.train.plan_overlap`'s bucket plan in
    emission order, and the numerics finite-flag contract holds.

Checks return plain message strings; `analysis.jaxpr_verify` (the
tracing harness) owns the config matrix, anchors messages into
`Finding`s, and routes them through the standard report/baseline/
suppression machinery. The checkers themselves are pure functions of
the collected collective stream — unit-testable without building a
train step.

Approximations (documented, deliberate): the reduced-axes dataflow
propagates through every primitive (union of operand facts) with no
loop fixpoint, so a psum whose operand merely DEPENDS on an earlier
psum over the same axis counts as a double reduction — sound for the
straight-line gradient programs this tier verifies, and exactly the
shape of the legacy transpose over-count it exists to catch. Wire
matching treats scalar reduces as vote/metric traffic and non-scalar
reduces as gradient wire.
"""

from __future__ import annotations

from typing import Any, Dict, FrozenSet, List, NamedTuple, Optional, \
    Sequence, Set, Tuple

from . import Rule

COLLECTIVE_PRIMS = frozenset((
    "psum", "pmin", "pmax", "all_gather", "all_to_all", "ppermute",
    "psum_scatter", "pbroadcast", "psum2", "psum_invariant",
))
# Primitives that REDUCE over their named axes (identity when the
# axis has size 1 — the wire-gate class).
REDUCE_PRIMS = frozenset(("psum", "pmin", "pmax", "psum2",
                          "psum_invariant"))


class CollectiveOp(NamedTuple):
    """One collective primitive from the traced program, in trace
    order (`pos`), with the dataflow facts the checks consume."""
    pos: int
    prim: str
    axes: Tuple[str, ...]
    shape: Tuple[int, ...]
    dtype: str
    dead: bool                      # result reaches no live output
    in_reduced: FrozenSet[str]      # axes the operand was already
                                    # reduced over (transitively)
    out_reduced: FrozenSet[str]
    out_id: int                     # identity of the result var
    in_ids: Tuple[int, ...]         # identities of operand vars

    @property
    def scalar(self) -> bool:
        return self.shape == ()


def _axes_of(params: Dict[str, Any]) -> Tuple[str, ...]:
    raw = params.get("axes", params.get("axis_name"))
    if raw is None:
        return ()
    if not isinstance(raw, (tuple, list)):
        raw = (raw,)
    return tuple(str(a) for a in raw)


def _sub_jaxprs(eqn) -> List[Tuple[Any, Optional[int]]]:
    """(sub_jaxpr, invar_offset) pairs for every jaxpr-valued param.
    `invar_offset` maps eqn.invars[offset:] onto the sub-jaxpr's
    invars positionally; None means no mapping is attempted (the sub
    runs with empty incoming dataflow facts — a sound
    under-approximation)."""
    out: List[Tuple[Any, Optional[int]]] = []
    for _k, v in sorted(eqn.params.items()):
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            if not _is_jaxpr(item):
                continue
            n_in = len(_open(item).invars)
            if n_in == len(eqn.invars):
                out.append((item, 0))
            elif n_in == len(eqn.invars) - 1:
                out.append((item, 1))    # cond: invars[0] = predicate
            else:
                out.append((item, None))
    return out


def _is_jaxpr(v) -> bool:
    return (hasattr(v, "eqns") or
            (hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns")))


def _open(j):
    """The open Jaxpr of either a Jaxpr or a ClosedJaxpr."""
    return j.jaxpr if hasattr(j, "jaxpr") else j


def _is_var(v) -> bool:
    # Literals carry a `val`; vars do not.
    return not hasattr(v, "val")


def _is_drop(v) -> bool:
    return type(v).__name__ == "DropVar"


def _live_outvars(jaxpr, live_in: Set[int]) -> Set[int]:
    """Transitive liveness: var ids that (directly or through later
    equations) reach the jaxpr's outvars in `live_in`, or feed an
    effectful equation. One backward sweep — jaxprs are already
    topologically ordered."""
    live = set(live_in)
    for eqn in reversed(jaxpr.eqns):
        out_live = any(_is_var(v) and not _is_drop(v) and id(v) in live
                       for v in eqn.outvars)
        if out_live or getattr(eqn, "effects", None):
            for v in eqn.invars:
                if _is_var(v):
                    live.add(id(v))
    return live


def _walk(jaxpr, env: Dict[int, FrozenSet[str]], live: Set[int],
          dead_ctx: bool, ops: List[CollectiveOp],
          counter: List[int]) -> None:
    for eqn in jaxpr.eqns:
        in_sets = [env.get(id(v), frozenset()) for v in eqn.invars
                   if _is_var(v)]
        in_red: FrozenSet[str] = frozenset().union(*in_sets) \
            if in_sets else frozenset()
        name = eqn.primitive.name
        axes = _axes_of(eqn.params)
        out_red = (in_red | frozenset(axes)
                   if name in REDUCE_PRIMS else in_red)
        if name == "pvary" and all(
                getattr(v.aval, "shape", None) == ()
                for v in eqn.invars):
            # A scalar lifted back to varying is a vote/count being
            # prepared for its own fold (train.py _unanimity), not a
            # gradient: re-reducing it is the point.
            out_red = in_red - frozenset(axes)
        subs = _sub_jaxprs(eqn)
        if subs:
            for sub, off in subs:
                sub_open = _open(sub)
                sub_env = dict(env)
                if off is not None:
                    invars = [v for v in eqn.invars][off:]
                    for outer, inner in zip(invars, sub_open.invars):
                        if _is_var(outer):
                            sub_env[id(inner)] = env.get(
                                id(outer), frozenset())
                eqn_dead = dead_ctx or (
                    not any(_is_var(v) and not _is_drop(v)
                            and id(v) in live for v in eqn.outvars)
                    and not getattr(eqn, "effects", None))
                sub_live = _live_outvars(
                    sub_open, {id(v) for v in sub_open.outvars
                               if _is_var(v)})
                _walk(sub_open, sub_env, sub_live, eqn_dead, ops,
                      counter)
                # map sub outvar facts back onto the eqn outvars
                for outer, inner in zip(eqn.outvars,
                                        sub_open.outvars):
                    if _is_var(outer):
                        got = sub_env.get(id(inner), frozenset()) \
                            if _is_var(inner) else frozenset()
                        env[id(outer)] = env.get(
                            id(outer), frozenset()) | got
            for v in eqn.outvars:
                if _is_var(v) and id(v) not in env:
                    env[id(v)] = in_red
            continue
        if name in COLLECTIVE_PRIMS:
            opnd = None
            for v in eqn.invars:
                if hasattr(v, "aval") and hasattr(v.aval, "shape"):
                    opnd = v
                    break
            shape = tuple(opnd.aval.shape) if opnd is not None else ()
            dtype = (str(opnd.aval.dtype)
                     if opnd is not None else "unknown")
            is_dead = dead_ctx or not any(
                _is_var(v) and not _is_drop(v) and id(v) in live
                for v in eqn.outvars)
            first_out = next((v for v in eqn.outvars if _is_var(v)),
                             None)
            ops.append(CollectiveOp(
                pos=counter[0], prim=name, axes=axes, shape=shape,
                dtype=dtype, dead=is_dead, in_reduced=in_red,
                out_reduced=out_red,
                out_id=id(first_out) if first_out is not None else 0,
                in_ids=tuple(id(v) for v in eqn.invars
                             if _is_var(v))))
            counter[0] += 1
        for v in eqn.outvars:
            if _is_var(v):
                env[id(v)] = out_red


def collect_collectives(closed_jaxpr) -> List[CollectiveOp]:
    """Every collective primitive in `closed_jaxpr` (recursively, in
    trace order) with liveness and reduced-axes facts attached."""
    j = _open(closed_jaxpr)
    live = _live_outvars(j, {id(v) for v in j.outvars if _is_var(v)})
    ops: List[CollectiveOp] = []
    _walk(j, {}, live, False, ops, [0])
    return ops


def signature(ops: Sequence[CollectiveOp]) -> Tuple:
    """The ordered collective signature sequence — the thing that
    must be a pure function of config for the cross-rank agreement
    contract to hold. Byte-comparable."""
    return tuple((o.prim, o.axes, o.shape, o.dtype) for o in ops)


def _chain_internal(ops: Sequence[CollectiveOp]) -> Set[int]:
    """Positions of reduce ops whose result feeds another reduce op —
    the inner links of a multi-axis psum chain (train.py's _psum_axes
    emits one psum per axis). Only the chain TERMINAL carries the
    cumulative reduced-axes fact wire matching keys on."""
    consumed: Set[int] = set()
    by_out = {o.out_id: o.pos for o in ops if o.prim in REDUCE_PRIMS}
    for o in ops:
        if o.prim not in REDUCE_PRIMS:
            continue
        for iid in o.in_ids:
            if iid in by_out:
                consumed.add(by_out[iid])
    return consumed


# ---------------------------------------------------------------------------
# invariant checks — each returns a list of finding messages
# ---------------------------------------------------------------------------

def check_axes(ops: Sequence[CollectiveOp],
               mesh_shape: Dict[str, int]) -> List[str]:
    """(a) every collective's axis names exist in the ambient mesh,
    and no reduce runs over a size-1 axis (identity wire — the r08
    wire-gate regression class). Scalar reduces are exempt: there the
    psum is what flips a flag's varying-type, so a size-1 axis' psum
    is type-required (and wire-free)."""
    msgs = []
    for op in ops:
        unknown = [a for a in op.axes if a not in mesh_shape]
        if unknown:
            msgs.append(
                f"collective '{op.prim}' over axis "
                f"{unknown[0]!r} which is not in the ambient mesh "
                f"axes {sorted(mesh_shape)}")
        if op.prim in REDUCE_PRIMS:
            size1 = [a for a in op.axes
                     if mesh_shape.get(a, 0) == 1]
            if size1 and not op.scalar:
                msgs.append(
                    f"'{op.prim}' reduces over size-1 mesh axis "
                    f"{size1[0]!r}: identity wire (the r08 wire-gate "
                    f"bug class — pack/reduce round trip with no "
                    f"bytes to move)")
    return msgs


def check_dead(ops: Sequence[CollectiveOp]) -> List[str]:
    """(d1) collectives whose results reach no output: dead wire the
    program should never emit (the r08 world-1 shape: 12 dead
    size-1-axis all-reduces shipped in every step)."""
    return [
        f"dead collective: '{op.prim}' over {op.axes} on "
        f"{op.dtype}{list(op.shape)} reaches no program output"
        for op in ops if op.dead]


def check_double_reduce(ops: Sequence[CollectiveOp]) -> List[str]:
    """(d2) psum-of-psum over the same axis: the operand was already
    reduced over an axis this reduce names again — the r08 legacy
    psum-transpose over-count shape (gradients arrive exactly
    |axis|x too large)."""
    msgs = []
    for op in ops:
        if op.prim not in REDUCE_PRIMS:
            continue
        again = sorted(set(op.axes) & op.in_reduced)
        if again:
            msgs.append(
                f"double reduction: '{op.prim}' over axis "
                f"{again[0]!r} whose operand was already reduced "
                f"over that axis (the legacy psum-transpose "
                f"over-count shape: gradient arrives |axis|x too "
                f"large)")
    return msgs


def _match_wire(ops: Sequence[CollectiveOp], want_shape, want_dtype,
                raxes: FrozenSet[str], used: Set[int],
                internal: Set[int]) -> Optional[CollectiveOp]:
    """First unused chain-terminal reduce matching one expected wire:
    same shape+dtype, each chain link's own axes inside the expected
    reduce set, cumulative reduction covering all of it."""
    for op in ops:
        if (op.pos in used or op.pos in internal
                or op.prim not in REDUCE_PRIMS):
            continue
        if op.shape != tuple(want_shape) or op.dtype != want_dtype:
            continue
        if not set(op.axes) <= raxes:
            continue
        if not raxes <= op.out_reduced:
            # the chain ending here (one psum per axis on the legacy
            # leg) must cumulatively cover every expected reduce axis
            continue
        used.add(op.pos)
        return op
    return None


def check_plan(ops: Sequence[CollectiveOp], plan,
               mesh_shape: Dict[str, int]) -> List[str]:
    """(b) the traced wire psums match the introspectable bucket plan
    (`parallel.train.plan_overlap`) — every bucket's per-dtype wire
    group appears exactly once with the planned payload size (flag
    ride included), buckets are emitted in plan order (reverse
    topological — bucket 0's reduction can start while the bulk of
    backprop still runs), and no non-scalar gradient reduce exists
    outside the plan. The plan's `digest`
    (bucketing.assignment_digest) is therefore machine-tied to the
    program XLA actually sees."""
    msgs: List[str] = []
    internal = _chain_internal(ops)
    used: Set[int] = set()
    first_pos: List[Optional[int]] = []
    for b, groups in enumerate(plan.wire):
        raxes = frozenset(plan.bucket_raxes[b])
        bucket_first: Optional[int] = None
        for g in groups:
            want_shape = (g.natural_shape if g.natural_shape
                          is not None else (g.n,))
            got = _match_wire(ops, want_shape, g.dtype, raxes, used,
                              internal)
            if got is None:
                msgs.append(
                    f"bucket {b} wire group ({g.dtype}, {g.n} "
                    f"elements{', flag rides' if g.rides_flag else ''})"
                    f" has no matching psum over {sorted(raxes)} in "
                    f"the traced program — the emitted schedule "
                    f"drifted from the agreed plan (digest "
                    f"{plan.digest!r})")
            elif bucket_first is None or got.pos < bucket_first:
                bucket_first = got.pos
        first_pos.append(bucket_first)
    seq = [p for p in first_pos if p is not None]
    if seq != sorted(seq):
        msgs.append(
            "bucket psums are not emitted in plan (reverse "
            "topological) order inside the backward — "
            "the agreed cross-rank collective order and the "
            "traced order disagree")
    for op in ops:
        if (op.prim in REDUCE_PRIMS and not op.scalar
                and op.pos not in used and op.pos not in internal
                and not op.dead):
            msgs.append(
                f"unplanned gradient reduce: '{op.prim}' over "
                f"{op.axes} on {op.dtype}{list(op.shape)} matches no "
                f"bucket wire group of the agreed plan (digest "
                f"{plan.digest!r})")
    return msgs


def check_numerics(ops: Sequence[CollectiveOp], plan,
                   mesh_shape: Dict[str, int],
                   guard: bool) -> List[str]:
    """(c) when the numerics guard is on, every bucketed reduction
    carries its finite-flag — either riding an exact-count wire group
    (f32/f64 payload +1) or as its own exact f32 scalar psum over the
    bucket's reduce axes — and the unanimity vote covers ALL live
    mesh axes, so a NaN confined to one shard can never split the
    skip decision per-device."""
    if not guard:
        return []
    live = {a for a, s in mesh_shape.items() if s > 1}
    msgs: List[str] = []
    scalar_reduces = [o for o in ops
                      if o.prim in REDUCE_PRIMS and o.scalar]
    covered: Set[str] = set()
    if plan is not None:
        for b, groups in enumerate(plan.wire):
            raxes = frozenset(plan.bucket_raxes[b])
            rides = any(g.rides_flag for g in groups)
            if rides:
                covered |= raxes
                continue
            sep = [o for o in scalar_reduces
                   if o.dtype in ("float32", "float64")
                   and set(o.axes) <= raxes
                   and raxes <= o.out_reduced]
            if not sep:
                msgs.append(
                    f"numerics: bucket {b} ({plan.wire[b][0].dtype} "
                    f"wire) has neither an exact-count flag carrier "
                    f"nor a separate exact f32 vote psum over "
                    f"{sorted(raxes)} — a non-finite gradient on one "
                    f"rank would not veto the step everywhere")
            else:
                covered |= raxes
    for o in scalar_reduces:
        covered |= set(o.axes)
    if plan is None or plan.loose_inexact or plan.wire:
        missing = live - covered
        if missing:
            msgs.append(
                f"numerics: the unanimity vote never reduces over "
                f"live mesh axis {sorted(missing)[0]!r} — replicas "
                f"along it could disagree on the skip decision and "
                f"silently diverge")
    return msgs


def check_compression(ops: Sequence[CollectiveOp], plan,
                      mesh_shape: Dict[str, int],
                      guard: bool) -> List[str]:
    """(e) cast buckets and the finite-flag vote: a bucket whose
    wire is lossy (fp16/bf16 cast) must NEVER plan the flag riding
    its carrier — a veto count accumulated in a lossy dtype rounds
    n-1 up to n past a few hundred ranks — and, guard on, each cast
    bucket owes a separate exact f32 scalar vote psum covering its
    reduce axes in the traced program."""
    comp = tuple(getattr(plan, "bucket_compression", ()) or ())
    if not comp or all(t == "none" for t in comp):
        return []
    msgs: List[str] = []
    scalar_votes = [o for o in ops
                    if o.prim in REDUCE_PRIMS and o.scalar
                    and o.dtype in ("float32", "float64")]
    for b, tag in enumerate(comp):
        if tag == "none":
            continue
        riders = [g for g in plan.wire[b] if g.rides_flag]
        if riders:
            msgs.append(
                f"compression: bucket {b} ({tag}) plans the finite-"
                f"flag riding its lossy wire carrier "
                f"({riders[0].dtype}, {riders[0].n} elements) — the "
                f"vote must be a separate exact f32 psum (a lossy-"
                f"dtype veto count rounds away)")
        if guard:
            raxes = frozenset(plan.bucket_raxes[b])
            sep = [o for o in scalar_votes
                   if set(o.axes) <= raxes and raxes <= o.out_reduced]
            if not sep:
                msgs.append(
                    f"compression: bucket {b} ({tag}) has no separate "
                    f"exact f32 vote psum over {sorted(raxes)} in the "
                    f"traced program — a non-finite gradient on one "
                    f"rank could not veto the step without riding the "
                    f"lossy carrier")
    return msgs


def check_determinism(sig_a: Tuple, sig_b: Tuple) -> List[str]:
    """(b) the ordered collective signature sequence must be a pure
    function of config: two independent builds of the same config
    must trace to the identical sequence — the 'identical on every
    rank by construction' contract, machine-checked."""
    if sig_a == sig_b:
        return []
    n = min(len(sig_a), len(sig_b))
    at = next((i for i in range(n) if sig_a[i] != sig_b[i]), n)
    return [
        f"non-deterministic collective schedule: two builds of the "
        f"same config diverge at collective #{at} "
        f"({sig_a[at] if at < len(sig_a) else '<missing>'} vs "
        f"{sig_b[at] if at < len(sig_b) else '<missing>'}) — ranks "
        f"deriving the schedule independently would disagree"]


class JaxprVerifierRule(Rule):
    """Catalog entry for the semantic tier. The AST `run()` is a
    no-op by design: HVD007 runs via `--jaxpr`
    (analysis/jaxpr_verify.py), which imports jax and the code under
    analysis — the opposite of the AST tier's purity contract, which
    is why the two tiers never share a pass."""

    id = "HVD007"
    summary = ("jaxpr-tier SPMD collective verifier: traces the real "
               "step builders across the config matrix and checks "
               "mesh-axis validity, wire-gate (size-1) cleanliness, "
               "dead/double reductions, plan agreement and the "
               "numerics flag contract (run via --jaxpr)")

    def run(self, project) -> List:
        return []
