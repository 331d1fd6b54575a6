"""hvdlint: the analyzer's own tests + the tier-1 repo gate.

Layout:
  * TestRepoGate — `horovod_tpu/` must be lint-clean (zero
    unsuppressed findings) and the run must stay fast (< 10 s), so
    the gate never becomes tier-1's slow step.
  * TestFixtureCorpus — every seeded positive in
    tests/lint_fixtures/ (marked `# EXPECT: HVD00x`) is reported at
    exactly that file:line, and nothing else is: positives, negatives
    and anchor accuracy in one assertion.
  * determinism / baseline round-trip / suppression parsing / CLI
    exit-code contract / config.env_value unit tests.
"""

import ast
import json
import os
import re
import time

import pytest

from horovod_tpu.analysis import run_analysis
from horovod_tpu.analysis import baseline as baseline_mod
from horovod_tpu.analysis import dataflow
from horovod_tpu.analysis import graph as graph_mod
from horovod_tpu.analysis import model as model_mod
from horovod_tpu.analysis.cli import main as cli_main
from horovod_tpu.analysis.model import (Project, Suppressions,
                                        collect_files)
from horovod_tpu.analysis.report import render_json, render_text
from horovod_tpu.common import config as hconfig

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO_ROOT, "horovod_tpu")
FIXTURES = os.path.join(REPO_ROOT, "tests", "lint_fixtures")

_EXPECT_RE = re.compile(r"#\s*EXPECT:\s*(HVD\d+)")


def _expected_findings():
    """{(relpath, line, rule), ...} from the fixture EXPECT markers."""
    expected = set()
    for name in sorted(os.listdir(FIXTURES)):
        if not name.endswith(".py"):
            continue
        rel = f"tests/lint_fixtures/{name}"
        path = os.path.join(FIXTURES, name)
        with open(path, "r", encoding="utf-8") as fh:
            for lineno, line in enumerate(fh, start=1):
                m = _EXPECT_RE.search(line)
                if m:
                    expected.add((rel, lineno, m.group(1)))
    return expected


class TestRepoGate:
    def test_repo_is_lint_clean(self):
        """The tier-1 gate: no unsuppressed findings in the package."""
        t0 = time.perf_counter()
        result = run_analysis([PKG], cwd=REPO_ROOT)
        elapsed = time.perf_counter() - t0
        assert result.parse_errors == []
        assert result.findings == [], (
            "new hvdlint findings (fix them or add a justified "
            "suppression):\n"
            + render_text(result.findings))
        # The gate must never become the slow step of tier-1.
        assert elapsed < 10.0, f"hvdlint took {elapsed:.1f}s (>10s)"

    def test_repo_suppressions_are_counted(self):
        """The audited benign findings are suppressed, not invisible —
        if this number drifts, someone added or removed a suppression
        and the PR should say why."""
        result = run_analysis([PKG], cwd=REPO_ROOT)
        assert result.suppressed >= 5


class TestFixtureCorpus:
    def test_seeded_positives_and_negatives(self):
        """Exactly the EXPECT-marked (file, line, rule) triples are
        reported — anchors included — and nothing else."""
        result = run_analysis([FIXTURES], cwd=REPO_ROOT)
        got = {(f.path, f.line, f.rule) for f in result.findings}
        expected = _expected_findings()
        missing = expected - got
        extra = got - expected
        assert not missing, f"seeded violations not caught: {missing}"
        assert not extra, f"false positives: {extra}"

    def test_each_rule_has_positives(self):
        result = run_analysis([FIXTURES], cwd=REPO_ROOT)
        rules = {f.rule for f in result.findings}
        assert rules == {"HVD001", "HVD002", "HVD003", "HVD004",
                         "HVD005", "HVD006", "HVD008", "HVD009"}

    def test_fixture_suppressions_filtered(self):
        result = run_analysis([FIXTURES], cwd=REPO_ROOT)
        assert result.suppressed == 8


class TestDeterminism:
    def test_json_report_byte_stable(self):
        r1 = run_analysis([FIXTURES], cwd=REPO_ROOT)
        r2 = run_analysis([FIXTURES], cwd=REPO_ROOT)
        j1 = render_json(r1.findings, suppressed=r1.suppressed)
        j2 = render_json(r2.findings, suppressed=r2.suppressed)
        assert j1 == j2
        # and it parses back with stable ordering
        doc = json.loads(j1)
        keys = [(f["path"], f["line"], f["col"], f["rule"])
                for f in doc["findings"]]
        assert keys == sorted(keys)


class TestBaseline:
    def test_round_trip_filters_everything(self, tmp_path):
        result = run_analysis([FIXTURES], cwd=REPO_ROOT)
        assert result.findings
        text = baseline_mod.render(result.findings)
        # render -> parse -> filter: a committed baseline silences
        # exactly the findings it records
        baseline = baseline_mod.parse(text)
        again = run_analysis([FIXTURES], baseline=baseline,
                             cwd=REPO_ROOT)
        assert again.findings == []
        assert again.baselined == len(result.findings)

    def test_new_finding_still_fails(self):
        result = run_analysis([FIXTURES], cwd=REPO_ROOT)
        partial = baseline_mod.parse(
            baseline_mod.render(result.findings[1:]))
        again = run_analysis([FIXTURES], baseline=partial,
                             cwd=REPO_ROOT)
        assert len(again.findings) == 1
        assert (again.findings[0].fingerprint
                == result.findings[0].fingerprint)

    def test_render_is_stable(self):
        result = run_analysis([FIXTURES], cwd=REPO_ROOT)
        assert (baseline_mod.render(result.findings)
                == baseline_mod.render(list(result.findings)))


class TestSuppressions:
    def test_same_line(self):
        sup = Suppressions.parse(
            "x = 1  # hvdlint: disable=HVD002 (reason)\n")
        assert sup.covers("HVD002", 1)
        assert not sup.covers("HVD001", 1)
        assert not sup.covers("HVD002", 2)

    def test_disable_next_skips_comment_lines(self):
        sup = Suppressions.parse(
            "# hvdlint: disable-next=HVD001 (a reason that wraps\n"
            "# over several comment lines)\n"
            "do_thing()\n")
        assert sup.covers("HVD001", 3)
        assert not sup.covers("HVD001", 1)

    def test_multiple_rules_and_file_wide(self):
        sup = Suppressions.parse(
            "x  # hvdlint: disable=HVD001,HVD003\n"
            "# hvdlint: disable-file=HVD004\n")
        assert sup.covers("HVD001", 1)
        assert sup.covers("HVD003", 1)
        assert sup.covers("HVD004", 999)
        assert not sup.covers("HVD002", 1)

    def test_marker_inside_string_is_ignored(self):
        sup = Suppressions.parse(
            's = "# hvdlint: disable=HVD001"\n')
        assert not sup.covers("HVD001", 1)


class TestCli:
    def test_exit_codes_and_write_baseline(self, tmp_path, capsys,
                                           monkeypatch):
        monkeypatch.chdir(REPO_ROOT)
        bl = tmp_path / "bl.json"
        # findings without a baseline -> 1
        assert cli_main([FIXTURES, "--no-baseline"]) == 1
        capsys.readouterr()
        # write-baseline -> 0, then the same run against it -> 0
        assert cli_main([FIXTURES, "--write-baseline",
                         "--baseline", str(bl)]) == 0
        capsys.readouterr()
        assert cli_main([FIXTURES, "--baseline", str(bl)]) == 0
        out = capsys.readouterr().out
        assert "0 finding(s)" in out
        # unknown rule -> usage error 2
        assert cli_main([FIXTURES, "--select", "HVD999"]) == 2
        capsys.readouterr()
        # a gate that scans nothing must fail loudly, not exit 0
        assert cli_main(["no/such/dir"]) == 2

    def test_github_format(self, capsys, monkeypatch):
        monkeypatch.chdir(REPO_ROOT)
        rc = cli_main([FIXTURES, "--no-baseline", "-f", "github"])
        assert rc == 1
        out = capsys.readouterr().out
        assert "::error file=tests/lint_fixtures/" in out
        assert ",line=" in out

    def test_list_rules(self, capsys):
        assert cli_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in ("HVD001", "HVD002", "HVD003", "HVD004",
                    "HVD005", "HVD006", "HVD007", "HVD008",
                    "HVD009"):
            assert rid in out

    def test_jaxpr_mode_exit_contract(self, tmp_path, capsys,
                                      monkeypatch):
        """`--jaxpr` runs the semantic tier through the same CLI
        contract: clean repo -> exit 0, cache file written next to
        the cwd."""
        monkeypatch.chdir(tmp_path)
        assert cli_main(["--jaxpr"]) == 0
        out = capsys.readouterr()
        assert "0 finding(s)" in out.out
        assert "config(s) verified" in out.err
        assert (tmp_path / ".hvdlint-jaxpr-cache.json").exists()


def _fixture_project():
    return Project(collect_files([FIXTURES], cwd=REPO_ROOT))


class TestCallGraph:
    """analysis/graph.py: resolution and the thread-entry index, run
    over the fixture corpus (no synthetic trees: the corpus is the
    contract)."""

    def test_self_method_resolution_and_thread_roots(self):
        g = graph_mod.get_call_graph(_fixture_project())
        rel = "tests/lint_fixtures/hvd006_lockset.py"
        pace = f"{rel}::DisjointLocks._pace"
        assert pace in g.funcs
        assert pace in g.thread_roots
        assert g.thread_roots[pace].kind == "thread"
        # signal handlers are entry points too
        sig = f"{rel}::_on_usr1"
        assert sig in g.thread_roots
        assert g.thread_roots[sig].kind == "signal"

    def test_entries_fold_main_and_roots(self):
        g = graph_mod.get_call_graph(_fixture_project())
        rel = "tests/lint_fixtures/hvd006_lockset.py"
        # the pacer body is thread-only; the public method is main-only
        assert g.entries(f"{rel}::DisjointLocks._pace") == frozenset(
            {f"{rel}::DisjointLocks._pace"})
        assert graph_mod.MAIN_ENTRY in g.entries(
            f"{rel}::DisjointLocks.bump")
        # a helper called from both sides carries both entries
        both = g.entries(f"{rel}::LockHeldAtEveryCallSite._bump_locked")
        assert graph_mod.MAIN_ENTRY in both
        assert f"{rel}::LockHeldAtEveryCallSite._pace" in both

    def test_cross_module_import_resolution(self):
        # hvd005 fixture calls collective_ops.synchronize through a
        # `from horovod_tpu.ops import collective_ops` alias; the
        # project must include that module for the edge to resolve.
        proj = Project(collect_files(
            [FIXTURES, os.path.join(PKG, "ops", "collective_ops.py")],
            cwd=REPO_ROOT))
        g = graph_mod.get_call_graph(proj)
        caller = ("tests/lint_fixtures/hvd005_protocol.py"
                  "::drained_on_one_branch_only")
        callees = g.edges.get(caller, set())
        assert ("horovod_tpu/ops/collective_ops.py::synchronize"
                in callees)

    def test_propagate_to_callers_is_bounded(self):
        g = graph_mod.get_call_graph(_fixture_project())
        rel = "tests/lint_fixtures/hvd005_protocol.py"
        seeds = {f"{rel}::_helper_submits": "allreduce"}
        out = g.propagate_to_callers(seeds, depth=2)
        assert f"{rel}::interprocedural_partial_protocol" in out
        assert out[f"{rel}::_helper_submits"] == "allreduce"


class TestDataflow:
    """CFG construction invariants the HVD005 detectors lean on."""

    @staticmethod
    def _fn(src):
        tree = ast.parse(src)
        return tree.body[0]

    def test_finally_is_cloned_onto_return_route(self):
        fn = self._fn(
            "def f(h):\n"
            "    try:\n"
            "        return 1\n"
            "    finally:\n"
            "        drain(h)\n")
        cfg = dataflow.build_cfg(fn)
        drain_stmt = fn.body[0].finalbody[0]
        # the finally body exists once on the normal path and once
        # cloned onto the return route
        assert len(cfg.nodes_of(drain_stmt)) >= 2

    def test_exit_avoiding_blocks_on_mentions(self):
        fn = self._fn(
            "def f(x):\n"
            "    h = go(x)\n"
            "    sync(h)\n"
            "    return x\n")
        cfg = dataflow.build_cfg(fn)
        assign, sync, ret = fn.body
        starts = [s for i in cfg.nodes_of(assign)
                  for s in cfg.nodes[i].succs]
        avoid = set(cfg.nodes_of(sync))
        assert not cfg.exit_reachable_avoiding(starts, avoid)
        assert cfg.exit_reachable_avoiding(starts, set())

    def test_while_true_has_no_fall_through(self):
        fn = self._fn(
            "def f():\n"
            "    while True:\n"
            "        step()\n"
            "    after()\n")
        cfg = dataflow.build_cfg(fn)
        after = fn.body[1]
        # `after()` is unreachable: no edges lead into it
        targets = {s for n in cfg.nodes for s in n.succs}
        assert all(i not in targets
                   for i in cfg.nodes_of(after))

    def test_always_raises(self):
        h = ast.parse(
            "try:\n    x()\nexcept E:\n    log()\n    raise\n")
        handler = h.body[0].handlers[0]
        assert dataflow.always_raises(handler.body)
        h2 = ast.parse(
            "try:\n    x()\nexcept E:\n    log()\n")
        assert not dataflow.always_raises(h2.body[0].handlers[0].body)


class TestHistoricalRegressions:
    """The bugs this repo actually shipped (PR 1 race, PR 4
    Popen-under-lock, PR 6 handle leak, PR 18's schema drift and
    byte-identity flake; PR 8's two jaxpr-level defects)
    reconstructed in tests/lint_fixtures/hvd_regressions.py must
    each be caught by the tier that owns them."""

    def test_ast_tier_regressions_are_flagged(self):
        result = run_analysis([FIXTURES], cwd=REPO_ROOT)
        rel = "tests/lint_fixtures/hvd_regressions.py"
        got = {(f.rule, f.context) for f in result.findings
               if f.path == rel}
        assert ("HVD006",
                "Pr1BytesProcessedRace._dispatch_loop") in got
        assert ("HVD003", "Pr4PopenUnderLock.spawn") in got
        assert ("HVD005", "Pr6HandleLeak.step") in got
        # PR 18 schema drift: the doctor read a misspelled watermark
        # field and silently counted nothing.
        assert ("HVD008", "pr18_watermark_field_drift") in got
        # PR 18 byte-identity flake: unsorted glob in the trajectory
        # consolidation walk.
        assert ("HVD009", "pr18_trajectory_consolidate") in got

    @staticmethod
    def _fixture_module():
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "hvd_regressions_fixture",
            os.path.join(FIXTURES, "hvd_regressions.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_round8_wire_gate_bug_is_flagged(self):
        """PR 8 bug #1 (size-1-axis psum at world 1) as a traced
        program: invisible to every AST rule, caught by HVD007."""
        from horovod_tpu.analysis.jaxpr_verify import verify_traced
        mod = self._fixture_module()
        step, args, mesh_shape = mod.pr8_wire_gate_builder()
        msgs = verify_traced(step, args, mesh_shape)
        assert any("size-1" in m for m in msgs), msgs

    def test_round8_double_reduce_bug_is_flagged(self):
        """PR 8 bug #2 (legacy psum-transpose over-count) as a traced
        program: HVD007's reduced-axes dataflow names the axis."""
        from horovod_tpu.analysis.jaxpr_verify import verify_traced
        mod = self._fixture_module()
        step, args, mesh_shape = mod.pr8_legacy_double_reduce_builder()
        msgs = verify_traced(step, args, mesh_shape)
        assert any("double reduction" in m for m in msgs), msgs

    def test_round13_flag_on_lossy_carrier_is_flagged(self):
        """PR 13 (first compression draft) as a traced program: the
        finite-flag riding the fp16 wire carrier. HVD007's check (e)
        must flag both the planned ride and the absent exact f32
        vote."""
        from horovod_tpu.analysis.jaxpr_verify import verify_traced
        mod = self._fixture_module()
        (step, args, mesh_shape,
         plan) = mod.pr13_flag_rides_compressed_carrier_builder()
        msgs = verify_traced(step, args, mesh_shape,
                             numerics_guard=True, plan=plan)
        assert any("riding its lossy wire carrier" in m
                   for m in msgs), msgs
        assert any("no separate exact f32 vote" in m
                   for m in msgs), msgs


class TestChangedOnly:
    def test_focus_restricts_findings_to_neighbors(self):
        changed = {"tests/lint_fixtures/hvd006_lockset.py"}
        result = run_analysis([FIXTURES], cwd=REPO_ROOT,
                              focus_from=changed)
        assert result.findings  # the lockset positives survive
        assert {f.path for f in result.findings} <= {
            "tests/lint_fixtures/hvd006_lockset.py"}
        full = run_analysis([FIXTURES], cwd=REPO_ROOT)
        assert len(result.findings) < len(full.findings)

    def test_neighbors_include_callees(self):
        proj = _fixture_project()
        out = graph_mod.focus_neighbors(
            proj, {"tests/lint_fixtures/hvd005_protocol.py"})
        assert "tests/lint_fixtures/hvd005_protocol.py" in out
        # hvd006 fixture has no call edges to hvd005: not a neighbor
        assert "tests/lint_fixtures/hvd006_lockset.py" not in out

    def test_empty_changed_set_reports_nothing(self):
        result = run_analysis([FIXTURES], cwd=REPO_ROOT,
                              focus_from=set())
        assert result.findings == []
        assert result.file_count > 0


class TestOverheadGuard:
    """The interprocedural pass must not make the gate the slow step:
    parsed modules and call graphs are cached on content hashes, so a
    re-run over an unchanged tree re-parses nothing."""

    def test_second_run_is_all_cache_hits(self):
        run_analysis([FIXTURES], cwd=REPO_ROOT)  # warm
        before = model_mod.cache_stats()
        g_before = graph_mod.cache_stats()
        result = run_analysis([FIXTURES], cwd=REPO_ROOT)
        after = model_mod.cache_stats()
        g_after = graph_mod.cache_stats()
        assert after["misses"] == before["misses"], \
            "unchanged sources were re-parsed"
        assert after["hits"] >= before["hits"] + result.file_count
        assert g_after["misses"] == g_before["misses"], \
            "unchanged project re-indexed its call graph"

    def test_repo_gate_budget_with_interprocedural_pass(self):
        # cold-ish path is covered by TestRepoGate's <10 s assert;
        # the warm path must be far cheaper than the budget
        run_analysis([PKG], cwd=REPO_ROOT)  # warm
        t0 = time.perf_counter()
        result = run_analysis([PKG], cwd=REPO_ROOT)
        elapsed = time.perf_counter() - t0
        assert result.file_count > 0
        assert elapsed < 5.0, (
            f"warm interprocedural run took {elapsed:.1f}s")


class TestEnvValue:
    def test_declared_typed_read(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", "1024")
        assert hconfig.env_value("HOROVOD_FUSION_THRESHOLD") == 1024

    def test_default_on_unset_and_empty(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_ELASTIC_TIMEOUT", raising=False)
        assert hconfig.env_value("HOROVOD_ELASTIC_TIMEOUT") == 600.0
        monkeypatch.setenv("HOROVOD_ELASTIC_TIMEOUT", "")
        assert hconfig.env_value("HOROVOD_ELASTIC_TIMEOUT") == 600.0

    def test_undeclared_raises(self):
        with pytest.raises(KeyError):
            hconfig.env_value("HOROVOD_NOT_A_KNOB")

    def test_bad_value_raises(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", "bogus")
        with pytest.raises(ValueError):
            hconfig.env_value("HOROVOD_FUSION_THRESHOLD")

    def test_explicit_env_dict(self):
        assert hconfig.env_value(
            "HOROVOD_ELASTIC_EPOCH", env={"HOROVOD_ELASTIC_EPOCH":
                                          "7"}) == 7


class TestJaxprTier:
    """HVD007 — the semantic tier's tier-1 gate: the full builder
    matrix must verify clean inside a wall-clock budget, the
    source-hash cache must make warm runs free, and the matrix must
    actually cover the advertised cells."""

    def test_repo_is_hvd007_clean_across_full_matrix(self):
        from horovod_tpu.analysis import jaxpr_verify
        t0 = time.perf_counter()
        result = jaxpr_verify.run_jaxpr_analysis(cwd=REPO_ROOT,
                                                 use_cache=False)
        elapsed = time.perf_counter() - t0
        assert result.findings == [], (
            "HVD007 findings on the repo's builders:\n"
            + render_text(result.findings))
        # the acceptance floor: the full (world x numerics) grid plus
        # the shape extras and the eager plan
        assert result.file_count >= 12, result.meta
        assert result.meta["configs_skipped"] == [], result.meta
        # time budget: tracing is zero-FLOP, this must never become
        # tier-1's slow step
        assert elapsed < 120.0, f"jaxpr tier took {elapsed:.1f}s"

    def test_matrix_covers_required_cells(self):
        from horovod_tpu.analysis.jaxpr_verify import default_matrix
        names = [c.name for c in default_matrix()]
        for world in (1, 2, 8):
            for nm in ("on", "off"):
                assert f"world={world},numerics={nm}" in names
        assert sum("eager-plan" in n for n in names) >= 2
        assert any("tensor1" in n for n in names)   # trivial axis
        assert any("bfloat16" in n for n in names)  # separate vote

    def test_cache_hit_and_source_key_invalidation(self, tmp_path,
                                                   monkeypatch):
        from horovod_tpu.analysis import jaxpr_verify
        cache = tmp_path / "jaxpr-cache.json"
        r1 = jaxpr_verify.run_jaxpr_analysis(cwd=REPO_ROOT,
                                             cache_path=str(cache))
        assert cache.exists()
        before = jaxpr_verify.cache_stats()
        r2 = jaxpr_verify.run_jaxpr_analysis(cwd=REPO_ROOT,
                                             cache_path=str(cache))
        after = jaxpr_verify.cache_stats()
        assert after["hits"] == before["hits"] + 1, (before, after)
        assert r2.file_count == r1.file_count
        assert r2.meta["cache"] == "hit"
        # key must move when a dependency source changes
        dep = tmp_path / "fake_dep.py"
        dep.write_text("a = 1\n")
        real = jaxpr_verify._dependency_files()
        monkeypatch.setattr(jaxpr_verify, "_dependency_files",
                            lambda: real + [str(dep)])
        k1 = jaxpr_verify.source_cache_key()
        dep.write_text("a = 2\n")
        k2 = jaxpr_verify.source_cache_key()
        assert k1 != k2

    def test_plan_digest_ties_builder_to_introspection(self):
        """The digest the traced builder records at trace time is the
        digest plan_overlap computes offline — one authority for the
        SPMD cross-process contract."""
        import jax
        import numpy as np
        import optax
        from jax.sharding import Mesh

        from horovod_tpu.parallel.train import (build_train_step,
                                                last_overlap_info,
                                                plan_overlap)

        mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
        params = {"a": np.zeros((4, 4), np.float32),
                  "b": np.zeros((3,), np.float32)}
        opt = optax.sgd(0.1)
        st = opt.init(params)

        def loss(p, batch):
            import jax.numpy as jnp
            return jnp.mean((batch[:, None] * p["a"]).sum(-1)
                            + p["b"].sum())

        s = build_train_step(loss, opt, mesh, donate=False,
                             overlap_threshold=32)
        s.lower(params, st, np.zeros((8, 4), np.float32))
        info = last_overlap_info()
        plan = plan_overlap(params, mesh, overlap_threshold=32,
                            guard=False)
        assert info["digest"] == plan.digest
        assert info["buckets"] == len(plan.bucket_leaf_indices)

    def test_wire_groups_account_flag_ride(self):
        """Numerics on: the plan's exact-count carrier group grows by
        exactly one element; bf16-only buckets never ride."""
        import numpy as np
        from jax.sharding import Mesh
        import jax

        from horovod_tpu.parallel.train import plan_overlap

        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        f32 = {"w": np.zeros((4,), np.float32)}
        p = plan_overlap(f32, mesh, overlap_threshold=1 << 20,
                         guard=True)
        (wg,) = p.wire[0]
        assert wg.rides_flag and wg.n == 5  # 4 payload + flag
        bf16 = {"w": jax.ShapeDtypeStruct((4,), jax.numpy.bfloat16)}
        p2 = plan_overlap(bf16, mesh, overlap_threshold=1 << 20,
                          guard=True)
        (wg2,) = p2.wire[0]
        assert not wg2.rides_flag and wg2.n == 4


class TestDocsDrift:
    """HVD002 invariant 4: the user_guide knob tables vs the
    registry."""

    @staticmethod
    def _project(tmp_path, doc_rows, registry_dir="pkg/common"):
        reg_dir = tmp_path / registry_dir
        reg_dir.mkdir(parents=True)
        (reg_dir / "config.py").write_text(
            "KNOBS = [\n"
            "    Knob('HOROVOD_ALPHA', int, 64 * 1024, 'doc'),\n"
            "    Knob('HOROVOD_BETA', _parse_bool, True, 'doc'),\n"
            "    Knob('HOROVOD_GAMMA', str, '', 'doc'),\n"
            "]\n"
            # uses, so the unused-knob check stays quiet
            "_ATTR_MAP = {}\n")
        (tmp_path / "docs").mkdir(exist_ok=True)
        (tmp_path / "docs" / "user_guide.md").write_text(
            "| Knob | Default | What |\n|---|---|---|\n"
            + "\n".join(doc_rows) + "\n")
        root = str(tmp_path / registry_dir.split("/")[0])
        return run_analysis([root], cwd=str(tmp_path))

    def test_stale_row_and_default_drift_flagged(self, tmp_path):
        result = self._project(tmp_path, [
            "| `HOROVOD_ALPHA` | 9999 | wrong default |",
            "| `HOROVOD_BETA` | 1 | agrees (bool spellings) |",
            "| `HOROVOD_GAMMA` | (launcher-set) | empty default: "
            "not checkable |",
            "| `HOROVOD_GONE` | 3 | stale row |",
        ])
        doc = [f for f in result.findings
               if f.path == "docs/user_guide.md"]
        msgs = [f.message for f in doc]
        assert any("HOROVOD_GONE" in m and "stale" in m
                   for m in msgs), msgs
        assert any("HOROVOD_ALPHA" in m and "drift" in m
                   for m in msgs), msgs
        assert not any("HOROVOD_BETA" in m for m in msgs), msgs
        assert not any("HOROVOD_GAMMA" in m for m in msgs), msgs

    def test_arith_default_spellings_accepted(self, tmp_path):
        result = self._project(tmp_path, [
            "| `HOROVOD_ALPHA` | 65536 | folded 64 * 1024 |",
        ])
        assert not [f for f in result.findings
                    if f.path == "docs/user_guide.md"]

    def test_non_common_registry_skips_docs(self, tmp_path):
        """A registry outside a common/ dir (e.g. the lint fixtures)
        must never scan a docs tree it does not own."""
        result = self._project(tmp_path, [
            "| `HOROVOD_GONE` | 3 | would be stale |",
        ], registry_dir="pkg/lint_fixtures")
        assert not [f for f in result.findings
                    if f.path == "docs/user_guide.md"]
