"""HVD007 — jaxpr-tier SPMD collective verifier: the tracing harness.

The AST tiers (HVD001–HVD006) stop at the `jax.jit` boundary; the
user guide's "what the analyzer cannot see" section conceded that gap
and round 8 proved it real twice (dead size-1-axis psums shipped at
world 1; the legacy psum-transpose gradient over-count — both
IR-level defects no AST pass can express). This module closes it: it
builds the repo's REAL step builders (`parallel.train.STEP_BUILDERS`)
across a config matrix — world size 1/2/8 x numerics on/off, plus a
multi-axis mesh, a trivial-axis mesh, a bf16 separate-vote config, a
bf16 wire cast, and the eager grouped-allreduce plan — traces
each to a closed jaxpr with `jax.make_jaxpr` under a `Mesh` context
(optimizer state shapes via `jax.eval_shape`; zero FLOPs, no
accelerator needed, works on a laptop), and walks the jaxprs with the
`rules.jaxpr_rules` checkers:

  (a) collective axis names exist in the ambient mesh; no reduce over
      a size-1 axis (the r08 wire-gate regression, machine-checked
      for every config instead of one pinned HLO test);
  (b) the ordered collective signature sequence is a pure function of
      config (two independent builds must agree — the cross-rank
      agreement contract) and the traced wire psums match
      `parallel.train.plan_overlap`'s bucket plan (payloads, flag
      rides, reverse-topological emission order, digest-tied);
  (c) numerics on: every bucketed reduction carries its finite-flag
      (exact-count carrier or separate exact f32 psum) and the
      unanimity vote covers every live mesh axis;
  (d) no dead collectives; no double reduction over the same axis
      (the r08 legacy over-count shape).

Findings flow through the standard `Finding`/report/baseline/
suppression machinery, anchored at the builder's definition site with
the config name in the context, so text/JSON/GitHub renderers,
fingerprints and the exit 0/1/2 contract come for free.

Unlike the AST tiers this module IMPORTS jax and the code under
analysis — that is the point (it verifies what the tracer produces,
not what the source says), and why it runs as its own `--jaxpr` CLI
mode rather than inside the pure-AST pass. A source-hash-keyed cache
(`.hvdlint-jaxpr-cache.json`) makes warm re-runs O(file hashing):
the key folds the builder/bucketing/numerics sources, the verifier
itself, the jax version, the device count and the x64 flag.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .model import Finding, collect_files

# Keep the default matrix small enough to trace inside the tier-1
# gate's budget but wide enough that every leg of the builder is
# exercised: the threshold packs the 4-layer chain model (80 B/layer)
# into one bucket per layer.
_THRESHOLD = 96
_WORLDS = (1, 2, 8)


@dataclasses.dataclass(frozen=True)
class StepConfig:
    """One cell of the verification matrix."""
    name: str
    kind: str = "jit"                 # "jit" | "eager-plan"
    mesh_axes: Tuple[Tuple[str, int], ...] = (("data", 1),)
    numerics: bool = False
    dtype: str = "float32"
    threshold: int = _THRESHOLD
    # Per-bucket wire cast ("none"/"fp16"/"bf16").
    compression: str = "none"

    @property
    def world(self) -> int:
        n = 1
        for _a, s in self.mesh_axes:
            n *= s
        return n


def default_matrix() -> List[StepConfig]:
    """The builder matrix: every (world, numerics) cell plus
    the shapes that historically hid bugs — a multi-axis mesh (chained
    per-axis psums), a mesh carrying a trivial (size-1) axis (the
    wire-gate class), a bf16 model (flag cannot ride a lossy-count
    wire: the separate exact f32 vote psum leg), a bf16 wire cast
    (check (e)), and the eager grouped-allreduce plan."""
    out: List[StepConfig] = []
    for world in _WORLDS:
        for numerics in (False, True):
            out.append(StepConfig(
                name=(f"world={world},numerics="
                      f"{'on' if numerics else 'off'}"),
                mesh_axes=(("data", world),), numerics=numerics))
    out.append(StepConfig(
        name="world=8,mesh=data4xseq2,numerics=on",
        mesh_axes=(("data", 4), ("seq", 2)), numerics=True))
    out.append(StepConfig(
        name="world=2,mesh=data2xtensor1,numerics=on",
        mesh_axes=(("data", 2), ("tensor", 1)), numerics=True))
    out.append(StepConfig(
        name="world=2,numerics=on,dtype=bfloat16",
        mesh_axes=(("data", 2),), numerics=True, dtype="bfloat16"))
    # Cast-wire cell (check (e)): the finite-flag vote must be a
    # separate exact f32 psum — never ride a lossy carrier — and the
    # cast wire groups must still match the plan in
    # reverse-topological order.
    out.append(StepConfig(
        name="world=2,numerics=on,compression=bf16",
        mesh_axes=(("data", 2),), numerics=True, compression="bf16"))
    out.append(StepConfig(name="eager-plan,threshold=80",
                          kind="eager-plan", threshold=80))
    out.append(StepConfig(name="eager-plan,threshold=0",
                          kind="eager-plan", threshold=0))
    return out


# ---------------------------------------------------------------------------
# abstract tracing of the real builders
# ---------------------------------------------------------------------------

def _ensure_devices(n: int = 8) -> int:
    """Best-effort: give this process `n` virtual CPU devices. Only
    effective before the jax backend initializes (the CLI path); under
    pytest the conftest already forced 8. Returns the live count —
    configs needing more are skipped and reported."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    import jax
    return len(jax.devices("cpu"))


def _chain_params(dtype: str):
    """4-layer chain MLP, 8 leaves, 80 B/layer at f32: small enough
    to trace in milliseconds, deep enough that reverse-topological
    bucket emission is observable (the last layer's cotangents exist
    first, so bucket 0 must psum first)."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    return {f"layer{i}": {"b": jax.ShapeDtypeStruct((4,), dt),
                          "w": jax.ShapeDtypeStruct((4, 4), dt)}
            for i in range(4)}


def _chain_loss(params, batch):
    import jax.numpy as jnp
    x = batch
    for i in range(4):
        lyr = params[f"layer{i}"]
        x = jnp.tanh(x @ lyr["w"] + lyr["b"])
    return jnp.mean(jnp.square(x))


def _build_mesh(mesh_axes):
    import jax
    import numpy as np
    from jax.sharding import Mesh
    names = tuple(a for a, _s in mesh_axes)
    dims = tuple(s for _a, s in mesh_axes)
    ndev = 1
    for s in dims:
        ndev *= s
    devs = np.array(jax.devices("cpu")[:ndev]).reshape(dims)
    return Mesh(devs, axis_names=names)


def _trace_once(cfg: StepConfig, mesh):
    """One independent build+trace of `cfg`: returns (collective ops,
    plan). The numerics guard is pinned through the same resolution
    point the builder reads (numerics.guard_enabled), restored after."""
    import jax
    import optax

    from .. import numerics as _numerics
    from ..parallel.train import build_train_step, plan_overlap
    from .rules import jaxpr_rules as R

    params = _chain_params(cfg.dtype)
    batch = jax.ShapeDtypeStruct((8, 4), params["layer0"]["w"].dtype)
    opt = optax.sgd(0.1)
    opt_state = jax.eval_shape(opt.init, params)
    saved = _numerics.guard_enabled
    _numerics.guard_enabled = lambda: cfg.numerics
    try:
        step = build_train_step(
            _chain_loss, opt, mesh, donate=False,
            overlap_threshold=cfg.threshold,
            compression=cfg.compression)
        jaxpr = jax.make_jaxpr(step)(params, opt_state, batch)
    finally:
        _numerics.guard_enabled = saved
    plan = plan_overlap(params, mesh,
                        overlap_threshold=cfg.threshold,
                        guard=cfg.numerics,
                        compression=cfg.compression)
    return R.collect_collectives(jaxpr), plan


def verify_step_config(cfg: StepConfig) -> List[str]:
    """Trace one jit config twice and run every invariant check;
    returns finding messages."""
    from .rules import jaxpr_rules as R

    mesh = _build_mesh(cfg.mesh_axes)
    mesh_shape = {a: s for a, s in cfg.mesh_axes}
    ops_a, plan = _trace_once(cfg, mesh)
    ops_b, _ = _trace_once(cfg, mesh)
    # A reduce whose every axis has size 1 carries no wire: under
    # shard_map's VMA typing the AD transpose inserts them as type
    # conversions (varying -> invariant) and XLA elides them. The
    # world-1 wire gate is pinned by the HLO byte-identity tests.
    ops_a, ops_b = ([op for op in ops
                     if any(mesh_shape.get(a, 0) != 1 for a in op.axes)]
                    for ops in (ops_a, ops_b))
    msgs: List[str] = []
    msgs += R.check_determinism(R.signature(ops_a),
                                R.signature(ops_b))
    msgs += R.check_axes(ops_a, mesh_shape)
    msgs += R.check_dead(ops_a)
    msgs += R.check_double_reduce(ops_a)
    msgs += R.check_plan(ops_a, plan, mesh_shape)
    msgs += R.check_compression(ops_a, plan, mesh_shape, cfg.numerics)
    msgs += R.check_numerics(ops_a, plan, mesh_shape, cfg.numerics)
    return msgs


def verify_eager_plan(threshold: int) -> List[str]:
    """The eager grouped-allreduce plan
    (optim/distributed_optimizer.py routes submissions through
    `partition_cached`): the cached partition must agree
    byte-for-byte with a fresh `partition_buckets` walk, twice (the
    purity the SPMD contract rests on), and the emission order must
    be last-produced-first."""
    import jax

    from ..ops.bucketing import (assignment_digest, partition_cached,
                                 partition_digest)

    leaves = jax.tree_util.tree_leaves(_chain_params("float32"))
    msgs: List[str] = []
    fresh = partition_digest(leaves, threshold)
    again = partition_digest(leaves, threshold)
    cached = assignment_digest(partition_cached(leaves, threshold))
    if fresh != again:
        msgs.append(
            f"eager plan (threshold={threshold}): two fresh "
            f"partitions of the same tree disagree ({fresh!r} vs "
            f"{again!r}) — the partition is not a pure function of "
            f"the tree")
    if cached != fresh:
        msgs.append(
            f"eager plan (threshold={threshold}): the signature-"
            f"cached partition ({cached!r}) disagrees with a fresh "
            f"walk ({fresh!r}) — processes with warm vs cold caches "
            f"would submit different fusion schedules")
    n = len(leaves)
    from ..ops.bucketing import partition_buckets
    flat = [i for b in partition_buckets(leaves, threshold)
            for i in b.indices]
    if flat != list(range(n - 1, -1, -1)):
        msgs.append(
            f"eager plan (threshold={threshold}): emission order is "
            f"not last-produced-first (got {flat})")
    return msgs


# ---------------------------------------------------------------------------
# public API for fixtures / tests
# ---------------------------------------------------------------------------

def verify_traced(fn, example_args: Sequence[Any],
                  mesh_shape: Dict[str, int], *,
                  numerics_guard: bool = False,
                  plan=None) -> List[str]:
    """Run the HVD007 invariant checks over an arbitrary traced
    callable — the entry point `TestHistoricalRegressions` uses to
    pin the round-8 bug reconstructions, and the hook for verifying
    builders outside the default matrix."""
    import jax

    from .rules import jaxpr_rules as R

    ops = R.collect_collectives(jax.make_jaxpr(fn)(*example_args))
    msgs: List[str] = []
    msgs += R.check_axes(ops, mesh_shape)
    msgs += R.check_dead(ops)
    msgs += R.check_double_reduce(ops)
    if plan is not None:
        msgs += R.check_plan(ops, plan, mesh_shape)
        msgs += R.check_compression(ops, plan, mesh_shape,
                                    numerics_guard)
    msgs += R.check_numerics(ops, plan, mesh_shape, numerics_guard)
    return msgs


# ---------------------------------------------------------------------------
# cache + the full run
# ---------------------------------------------------------------------------

def _pkg_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dependency_files() -> List[str]:
    """Sources whose change invalidates a cached verification: the
    builders, the plan layer, numerics, the
    verifier and its checkers."""
    root = _pkg_root()
    rels = [
        ("parallel", "train.py"), ("parallel", "mesh.py"),
        ("parallel", "sharding.py"), ("ops", "bucketing.py"),
        ("ops", "compression.py"),
        ("numerics.py",),
        ("common", "config.py"), ("optim", "distributed_optimizer.py"),
        ("analysis", "jaxpr_verify.py"),
        ("analysis", "rules", "jaxpr_rules.py"),
    ]
    return [os.path.join(root, *r) for r in rels]


def source_cache_key() -> str:
    """sha256 over every dependency source plus the runtime identity
    (jax version, device count, x64) and the matrix itself."""
    import jax
    h = hashlib.sha256()
    for path in _dependency_files():
        h.update(path.encode())
        try:
            with open(path, "rb") as fh:
                h.update(fh.read())
        except OSError:
            h.update(b"<missing>")
    h.update(jax.__version__.encode())
    h.update(str(len(jax.devices("cpu"))).encode())
    h.update(str(bool(jax.config.jax_enable_x64)).encode())
    h.update(repr(default_matrix()).encode())
    return h.hexdigest()


DEFAULT_CACHE = ".hvdlint-jaxpr-cache.json"

_CACHE_STATS = {"hits": 0, "misses": 0}


def cache_stats() -> Dict[str, int]:
    return dict(_CACHE_STATS)


def _anchor(cwd: str) -> Dict[str, Tuple[str, int]]:
    """Finding anchors: (rel path, line) of the artifacts each config
    kind verifies."""
    import inspect

    from ..ops import bucketing as bucketing_mod
    from ..parallel import train as train_mod

    def rel_of(mod):
        p = os.path.abspath(mod.__file__)
        try:
            r = os.path.relpath(p, cwd)
        except ValueError:
            return p.replace(os.sep, "/")
        return (p if r.startswith("..") else r).replace(os.sep, "/")

    return {
        "jit": (rel_of(train_mod),
                inspect.getsourcelines(train_mod.build_train_step)[1]),
        "eager-plan": (
            rel_of(bucketing_mod),
            inspect.getsourcelines(
                bucketing_mod.partition_buckets)[1]),
    }


def run_matrix(configs: Optional[List[StepConfig]] = None,
               cwd: Optional[str] = None) -> Tuple[List[Finding],
                                                   Dict[str, Any]]:
    """Trace and verify every config; returns (findings, meta). Meta
    records verified/skipped config names and wall time — the gate
    test and the CLI both surface it."""
    cwd = cwd or os.getcwd()
    t0 = time.perf_counter()
    ndev = _ensure_devices(8)
    configs = default_matrix() if configs is None else configs
    anchors = _anchor(cwd)
    findings: List[Finding] = []
    verified: List[str] = []
    skipped: List[str] = []
    for cfg in configs:
        if cfg.kind == "jit" and cfg.world > ndev:
            skipped.append(
                f"{cfg.name} (needs {cfg.world} devices, have {ndev})")
            continue
        if cfg.kind == "eager-plan":
            msgs = verify_eager_plan(cfg.threshold)
        else:
            msgs = verify_step_config(cfg)
        path, line = anchors[cfg.kind]
        ctx = ("build_train_step" if cfg.kind == "jit"
               else "partition_buckets")
        for msg in msgs:
            findings.append(Finding(
                "HVD007", path, line, 1, msg, f"{ctx}[{cfg.name}]"))
        verified.append(cfg.name)
    meta = {
        "configs_verified": verified,
        "configs_skipped": skipped,
        "devices": ndev,
        "elapsed_s": round(time.perf_counter() - t0, 3),
    }
    return findings, meta


def run_jaxpr_analysis(cwd: Optional[str] = None,
                       baseline: Optional[Dict[str, dict]] = None,
                       use_cache: bool = True,
                       cache_path: Optional[str] = None):
    """The `--jaxpr` entry point: run (or cache-load) the full matrix
    and route findings through the SAME suppression + baseline
    filtering the AST tiers use, returning an `AnalysisResult` whose
    `file_count` is the number of configs verified (the CLI's
    scanned-nothing guard).

    An inline `# hvdlint: disable=HVD007 (reason)` on the anchored
    builder line suppresses exactly like any other rule; baseline
    fingerprints are line-insensitive as usual."""
    from . import AnalysisResult

    cwd = cwd or os.getcwd()
    cache_path = cache_path or os.environ.get(
        "HVDLINT_JAXPR_CACHE", os.path.join(cwd, DEFAULT_CACHE))
    t0 = time.perf_counter()
    # Must run before ANY backend touch (source_cache_key counts
    # devices): the first jax.devices() call freezes XLA_FLAGS.
    _ensure_devices(8)
    key = source_cache_key()
    raw: Optional[List[Finding]] = None
    meta: Dict[str, Any] = {}
    if use_cache and os.path.exists(cache_path):
        try:
            with open(cache_path, "r", encoding="utf-8") as fh:
                doc = json.load(fh)
            if doc.get("key") == key:
                raw = [Finding(f["rule"], f["path"], f["line"],
                               f["col"], f["message"], f["context"])
                       for f in doc.get("findings", [])]
                meta = doc.get("meta", {})
                meta["cache"] = "hit"
                _CACHE_STATS["hits"] += 1
        except (OSError, ValueError, KeyError, TypeError):
            raw = None
    if raw is None:
        _CACHE_STATS["misses"] += 1
        raw, meta = run_matrix(cwd=cwd)
        meta["cache"] = "miss"
        if use_cache:
            doc = {
                "key": key,
                "meta": {k: v for k, v in meta.items()
                         if k != "cache"},
                "findings": [
                    {"rule": f.rule, "path": f.path, "line": f.line,
                     "col": f.col, "message": f.message,
                     "context": f.context} for f in raw],
            }
            try:
                with open(cache_path, "w", encoding="utf-8") as fh:
                    json.dump(doc, fh, indent=1, sort_keys=True)
            except OSError:
                pass
    # Suppression filtering via the anchored files' inline comments —
    # the same mechanics (and audit trail) as every AST rule.
    by_path: Dict[str, Any] = {}
    for sf in collect_files(sorted({os.path.join(cwd, f.path)
                                    for f in raw}), cwd=cwd):
        by_path[sf.rel] = sf
    kept: List[Finding] = []
    suppressed = 0
    for f in raw:
        sf = by_path.get(f.path)
        if sf is not None and sf.suppressions.covers(f.rule, f.line):
            suppressed += 1
        else:
            kept.append(f)
    baselined = 0
    if baseline:
        fresh = []
        for f in kept:
            if f.fingerprint in baseline:
                baselined += 1
            else:
                fresh.append(f)
        kept = fresh
    kept.sort(key=Finding.sort_key)
    result = AnalysisResult(
        kept, suppressed, baselined,
        time.perf_counter() - t0, [],
        file_count=len(meta.get("configs_verified", [])))
    result.meta = meta
    return result


def main(argv: Optional[List[str]] = None) -> int:
    """Tiny standalone entry (`python -m
    horovod_tpu.analysis.jaxpr_verify`); the full CLI contract lives
    in `python -m horovod_tpu.analysis --jaxpr`."""
    result = run_jaxpr_analysis()
    from .report import render_text
    sys.stdout.write(render_text(result.findings,
                                 suppressed=result.suppressed,
                                 baselined=result.baselined))
    print(f"hvdlint --jaxpr: {result.file_count} config(s) verified "
          f"({result.meta.get('cache', '?')} cache, "
          f"{result.meta.get('elapsed_s', '?')}s trace time)",
          file=sys.stderr)
    return 1 if result.findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
