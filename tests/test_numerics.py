"""Numerical-integrity subsystem tests (numerics.py): finite-flag
computation and its ride through the reduction paths, the coordinated
skip-step wrapper (incl. the disabled-is-identity contract, the HLO
no-op acceptance check, and escalation), the distributed loss scaler's
backoff/growth schedule, digest determinism for the replica-divergence
sentinel, the numerics.grad/numerics.param chaos seams, and — behind
the multiproc capability probe — the fixed-seed 2-rank chaos runs:
rank-local NaN => one coordinated skip everywhere with bitwise-equal
replicas, and a single bit-flip => ReplicaDivergenceError naming the
corrupted rank, recovered through elastic restore."""

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu import faults, numerics
from horovod_tpu.common.exceptions import (HorovodInternalError,
                                           ReplicaDivergenceError)
from horovod_tpu.metrics import REGISTRY

from tests.test_elastic import (REPO, launch, make_env, read_logs,
                                write_discovery)

_NO_MULTIPROC = ("this jaxlib's CPU backend cannot run cross-process "
                 "collectives (affects every multiprocess "
                 "integration test)")


@pytest.fixture(autouse=True)
def disarm_faults():
    faults.configure(None)
    yield
    faults.configure(None)


@pytest.fixture(scope="module")
def multiproc_backend():
    """Cheap capability probe (same gate as test_chaos.py)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         sys.executable, "-c",
         "import jax.numpy as jnp; import horovod_tpu as hvd; "
         "hvd.init(); hvd.allreduce(jnp.ones(4), name='probe'); "
         "hvd.shutdown()"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=180)
    if "Multiprocess computations aren't implemented" in (
            r.stdout + r.stderr):
        pytest.skip(_NO_MULTIPROC)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr


def _skip_if_no_multiproc(out, returncode):
    if returncode != 0 and \
            "Multiprocess computations aren't implemented" in out:
        pytest.skip(_NO_MULTIPROC)


# ---------------------------------------------------------------------------
# finite flags
# ---------------------------------------------------------------------------

class TestFiniteFlags:
    def test_all_finite_basic(self):
        assert bool(numerics.all_finite({"a": jnp.ones(3)}))
        assert not bool(numerics.all_finite(
            {"a": jnp.array([1.0, jnp.nan])}))
        assert not bool(numerics.all_finite(
            {"a": jnp.ones(2), "b": jnp.array([jnp.inf])}))

    def test_integer_leaves_ignored_and_empty_tree_finite(self):
        assert bool(numerics.all_finite({"i": jnp.array([1, 2])}))
        assert bool(numerics.all_finite({}))

    def test_local_finite_flag_wire_form(self):
        f = numerics.local_finite_flag([jnp.ones(2)])
        assert f.dtype == jnp.float32 and float(f) == 1.0
        f = numerics.local_finite_flag([jnp.array([jnp.nan])])
        assert float(f) == 0.0

    def test_imprint_poisons_only_on_veto(self):
        t = {"a": jnp.ones(3), "i": jnp.array([1, 2])}
        ok = numerics.imprint_non_finite(t, True)
        np.testing.assert_array_equal(np.asarray(ok["a"]), 1.0)
        bad = numerics.imprint_non_finite(t, False)
        assert np.isnan(np.asarray(bad["a"])).all()
        # integer leaves are left alone (finite by construction)
        np.testing.assert_array_equal(np.asarray(bad["i"]), [1, 2])


# ---------------------------------------------------------------------------
# guard_non_finite
# ---------------------------------------------------------------------------

class TestGuard:
    def test_disabled_returns_inner_unchanged(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_NUMERICS_GUARD", raising=False)
        inner = optax.sgd(0.1)
        assert numerics.guard_non_finite(inner) is inner

    def test_finite_step_matches_inner(self):
        g = numerics.guard_non_finite(optax.sgd(0.1), enabled=True)
        params = {"w": jnp.arange(4.0)}
        st = g.init(params)
        up, st = g.update({"w": jnp.ones(4)}, st, params)
        np.testing.assert_allclose(np.asarray(up["w"]), -0.1)
        assert numerics.consecutive_skips(st) == 0

    def test_skip_zeroes_update_and_freezes_inner_state(self):
        g = numerics.guard_non_finite(optax.adam(0.1), enabled=True)
        params = {"w": jnp.ones(4)}
        st = g.init(params)
        up, st1 = g.update({"w": jnp.ones(4)}, st, params)
        inner_before = jax.tree_util.tree_map(np.asarray,
                                              st1.inner_state)
        up, st2 = g.update({"w": jnp.array([1.0, jnp.nan, 1, 1])},
                           st1, params)
        assert np.all(np.asarray(up["w"]) == 0)
        assert numerics.consecutive_skips(st2) == 1
        assert int(st2.total_skips) == 1
        # Adam's moments/count did NOT advance on the skipped step
        for a, b in zip(jax.tree_util.tree_leaves(inner_before),
                        jax.tree_util.tree_leaves(
                            jax.tree_util.tree_map(
                                np.asarray, st2.inner_state))):
            np.testing.assert_array_equal(a, b)
        # a clean step resets the consecutive counter
        up, st3 = g.update({"w": jnp.ones(4)}, st2, params)
        assert numerics.consecutive_skips(st3) == 0
        assert int(st3.total_skips) == 1

    def test_skip_counted_in_metrics(self):
        before = sum((REGISTRY.snapshot().get(
            "hvd_skipped_steps_total") or {}).values())
        g = numerics.guard_non_finite(optax.sgd(0.1), enabled=True)
        params = {"w": jnp.ones(2)}
        st = g.init(params)
        g.update({"w": jnp.array([jnp.nan, 1.0])}, st, params)
        after = REGISTRY.snapshot()["hvd_skipped_steps_total"]
        assert sum(after.values()) == before + 1
        assert after[("non_finite",)] >= 1

    def test_escalation_raises_horovod_internal_error(self):
        g = numerics.guard_non_finite(optax.sgd(0.1), enabled=True,
                                      max_consecutive=2)
        params = {"w": jnp.ones(2)}
        st = g.init(params)
        bad = {"w": jnp.array([jnp.nan, 1.0])}
        _, st = g.update(bad, st, params)
        with pytest.raises(HorovodInternalError, match="consecutive"):
            g.update(bad, st, params)

    def test_jit_path_counts_in_state_and_check_escalation(self):
        g = numerics.guard_non_finite(optax.sgd(0.1), enabled=True)
        params = {"w": jnp.ones(2)}
        st = g.init(params)
        upd = jax.jit(lambda u, s, p: g.update(u, s, p))
        bad = {"w": jnp.array([jnp.nan, 1.0])}
        _, st = upd(bad, st, params)
        _, st = upd(bad, st, params)
        assert numerics.consecutive_skips(st) == 2
        numerics.check_escalation(st, max_consecutive=3)  # below: ok
        with pytest.raises(HorovodInternalError):
            numerics.check_escalation(st, max_consecutive=2)

    def test_dgt_eager_ride_skips_and_recovers(self, hvd_single,
                                               monkeypatch):
        """The eager fused flag ride end to end at world size 1: NaN
        grads => zeroed update + counted skip; clean grads => exact
        SGD update (the flag leaf must not leak into the output)."""
        monkeypatch.setenv("HOROVOD_NUMERICS_GUARD", "1")
        opt = hvd.DistributedOptimizer(
            numerics.guard_non_finite(optax.sgd(0.1), enabled=True))
        params = {"w": jnp.arange(4.0), "b": jnp.ones(2)}
        st = opt.init(params)
        up, st = opt.update(
            {"w": jnp.ones(4), "b": jnp.ones(2)}, st, params)
        np.testing.assert_allclose(np.asarray(up["w"]), -0.1)
        up, st = opt.update(
            {"w": jnp.array([1.0, jnp.nan, 1, 1]), "b": jnp.ones(2)},
            st, params)
        assert np.all(np.asarray(up["w"]) == 0)
        assert np.all(np.asarray(up["b"]) == 0)
        assert numerics.consecutive_skips(st) == 1

    def test_dgt_compressed_reduction_still_vetoes(self, hvd_single,
                                                   monkeypatch):
        """With lossy fp16/bf16 compression the vote must NOT ride the
        compressed group (a summed count stops being integer-exact at
        scale); the exact Min allreduce carries it instead — the skip
        still happens."""
        monkeypatch.setenv("HOROVOD_NUMERICS_GUARD", "1")
        opt = hvd.DistributedOptimizer(
            numerics.guard_non_finite(optax.sgd(0.1), enabled=True),
            compression=hvd.Compression.fp16)
        params = {"w": jnp.arange(4.0, dtype=jnp.float32)}
        st = opt.init(params)
        up, st = opt.update(
            {"w": jnp.array([1.0, jnp.nan, 1, 1], jnp.float32)},
            st, params)
        assert np.all(np.asarray(up["w"]) == 0)
        assert numerics.consecutive_skips(st) == 1
        up, st = opt.update({"w": jnp.ones(4, jnp.float32)}, st,
                            params)
        assert np.all(np.asarray(up["w"]) != 0)
        assert numerics.consecutive_skips(st) == 0

    def test_grad_seam_fires_without_guard(self, hvd_single,
                                           monkeypatch):
        """Negative control: an armed numerics.grad spec injects (and
        counts the fire) even with the guard OFF — the poison then
        propagates, demonstrating what the guard prevents. An armed
        spec must never be an unlogged no-op."""
        monkeypatch.delenv("HOROVOD_NUMERICS_GUARD", raising=False)
        faults.configure("numerics.grad:nan:at=1", seed=1)
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))
        params = {"w": jnp.ones(4)}
        st = opt.init(params)
        up, st = opt.update({"w": jnp.ones(4)}, st, params)
        assert not bool(numerics.all_finite(up))   # poison propagated
        fired = REGISTRY.snapshot().get("hvd_faults_fired_total", {})
        assert fired.get(("numerics.grad", "nan"), 0) >= 1

    def test_dgt_sum_op_ride(self, hvd_single, monkeypatch):
        monkeypatch.setenv("HOROVOD_NUMERICS_GUARD", "1")
        opt = hvd.DistributedOptimizer(
            numerics.guard_non_finite(optax.sgd(1.0), enabled=True),
            op=hvd.Sum)
        params = {"w": jnp.zeros(3)}
        st = opt.init(params)
        up, st = opt.update({"w": jnp.ones(3)}, st, params)
        np.testing.assert_allclose(np.asarray(up["w"]), -1.0)
        up, st = opt.update({"w": jnp.full(3, jnp.inf)}, st, params)
        assert np.all(np.asarray(up["w"]) == 0)


class TestTrainStepGuard:
    def _loss(self, params, batch):
        return jnp.mean((batch * params["w"]) ** 2)

    def _mesh(self):
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices()[:8]), axis_names=("data",))

    def test_guarded_step_skips_nan_batch(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_NUMERICS_GUARD", "1")
        from horovod_tpu.parallel.train import build_train_step
        g = numerics.guard_non_finite(optax.sgd(0.1), enabled=True)
        step = build_train_step(self._loss, g, self._mesh(),
                                donate=False)
        params = {"w": jnp.ones(())}
        st = g.init(params)
        p2, o2, _ = step(params, st, jnp.arange(8.0))
        assert float(p2["w"]) != 1.0
        assert numerics.consecutive_skips(o2) == 0
        bad = jnp.arange(8.0).at[3].set(jnp.nan)
        p3, o3, _ = step(params, st, bad)
        assert float(p3["w"]) == 1.0          # coordinated skip
        assert numerics.consecutive_skips(o3) == 1

    def test_disabled_guard_lowers_to_identical_hlo(self, monkeypatch):
        """Acceptance: with no numerics knobs set, wrapping the
        optimizer in guard_non_finite changes NOTHING in the lowered
        program — byte-identical HLO text."""
        monkeypatch.delenv("HOROVOD_NUMERICS_GUARD", raising=False)
        from horovod_tpu.parallel.train import build_train_step
        mesh = self._mesh()
        inner = optax.sgd(0.1)
        s1 = build_train_step(self._loss,
                              numerics.guard_non_finite(inner),
                              mesh, donate=False)
        s2 = build_train_step(self._loss, inner, mesh, donate=False)
        params = {"w": jnp.ones(())}
        st = inner.init(params)
        batch = jnp.arange(8.0)
        assert s1.lower(params, st, batch).as_text() == \
            s2.lower(params, st, batch).as_text()


# ---------------------------------------------------------------------------
# DistributedLossScaler
# ---------------------------------------------------------------------------

class TestLossScaler:
    def test_defaults_from_knobs(self):
        sc = hvd.DistributedLossScaler()
        assert sc.init_scale == 65536.0
        assert sc.growth_interval == 2000

    def test_backoff_on_overflow(self):
        sc = hvd.DistributedLossScaler(init_scale=16.0,
                                       growth_interval=4)
        st = sc.init()
        st = sc.update(st, False)
        assert float(st.scale) == 8.0 and int(st.growth_count) == 0
        st = sc.update(st, False)
        assert float(st.scale) == 4.0

    def test_growth_after_interval_clean_steps(self):
        sc = hvd.DistributedLossScaler(init_scale=8.0,
                                       growth_interval=3)
        st = sc.init()
        for _ in range(2):
            st = sc.update(st, True)
            assert float(st.scale) == 8.0
        st = sc.update(st, True)   # 3rd clean step: grow + reset
        assert float(st.scale) == 16.0
        assert int(st.growth_count) == 0

    def test_backoff_resets_growth_count_and_floors(self):
        sc = hvd.DistributedLossScaler(init_scale=2.0,
                                       growth_interval=10,
                                       min_scale=1.0)
        st = sc.init()
        st = sc.update(st, True)
        assert int(st.growth_count) == 1
        st = sc.update(st, False)
        assert int(st.growth_count) == 0
        st = sc.update(st, False)
        assert float(st.scale) == 1.0   # floored, never 0

    def test_scale_unscale_roundtrip_and_jit(self):
        sc = hvd.DistributedLossScaler(init_scale=1024.0)
        st = sc.init()
        loss = jnp.float32(3.0)
        assert float(sc.scale(loss, st)) == 3072.0
        grads = {"w": jnp.full(3, 2048.0)}
        out = sc.unscale(grads, st)
        np.testing.assert_allclose(np.asarray(out["w"]), 2.0)
        st2 = jax.jit(sc.update)(st, jnp.asarray(False))
        assert float(st2.scale) == 512.0

    def test_invalid_factors_rejected(self):
        with pytest.raises(ValueError):
            hvd.DistributedLossScaler(growth_factor=1.0)
        with pytest.raises(ValueError):
            hvd.DistributedLossScaler(backoff_factor=1.5)


# ---------------------------------------------------------------------------
# digests / divergence sentinel
# ---------------------------------------------------------------------------

class TestDigest:
    def test_deterministic_across_recomputation(self):
        t = {"w": jnp.arange(16.0), "b": jnp.ones((2, 3))}
        assert numerics.params_digest(t) == numerics.params_digest(
            {"w": jnp.arange(16.0), "b": jnp.ones((2, 3))})

    def test_sensitive_to_value_dtype_shape_and_path(self):
        w = jnp.arange(4.0, dtype=jnp.float32)
        base = numerics.params_digest({"w": w})
        assert base != numerics.params_digest(
            {"w": w.at[2].add(1e-6)})
        assert base != numerics.params_digest(
            {"w": w.astype(jnp.float64)})
        assert base != numerics.params_digest(
            {"w": w.reshape(2, 2)})
        assert base != numerics.params_digest({"v": w})

    def test_check_noop_at_world_size_one(self, hvd_single):
        numerics.check_replica_divergence({"w": jnp.ones(4)})

    def test_replica_divergence_error_is_restorable(self):
        err = ReplicaDivergenceError("boom", divergent_ranks=(1,))
        assert isinstance(err, HorovodInternalError)
        assert err.divergent_ranks == (1,)

    def _check_with_world(self, monkeypatch, digests):
        """Run check_replica_divergence against a faked allgather
        (the wire is 8 bytes/rank; the consensus logic is pure)."""
        from horovod_tpu.common import basics
        from horovod_tpu.optim import functions
        monkeypatch.setattr(basics, "is_initialized", lambda: True)
        monkeypatch.setattr(basics, "size", lambda: len(digests))
        monkeypatch.setattr(
            functions, "allgather_object",
            lambda obj, name=None, process_set=None: list(digests))
        numerics.check_replica_divergence({"w": jnp.ones(2)})

    def test_agreeing_replicas_pass(self, monkeypatch):
        self._check_with_world(monkeypatch, [7, 7, 7])

    def test_divergent_minority_named(self, monkeypatch):
        with pytest.raises(ReplicaDivergenceError) as ei:
            self._check_with_world(monkeypatch, [7, 7, 9, 7])
        assert ei.value.divergent_ranks == (2,)
        assert "divergent ranks [2]" in str(ei.value)

    def test_two_rank_tie_blames_higher_rank(self, monkeypatch):
        """1-vs-1 split: consensus ties break toward the group holding
        rank 0 (whose state elastic sync re-broadcasts), so the
        corrupted higher rank is the one named."""
        with pytest.raises(ReplicaDivergenceError) as ei:
            self._check_with_world(monkeypatch, [7, 9])
        assert ei.value.divergent_ranks == (1,)
        # a 1-vs-1 split cannot PROVE which side is corrupted; the
        # error must say so instead of claiming a clean recovery
        assert "AMBIGUOUS" in str(ei.value)

    def test_strict_majority_is_not_flagged_ambiguous(self,
                                                      monkeypatch):
        with pytest.raises(ReplicaDivergenceError) as ei:
            self._check_with_world(monkeypatch, [7, 7, 9])
        assert "AMBIGUOUS" not in str(ei.value)

    def test_rank0_divergent_fails_hard_not_restorable(self,
                                                       monkeypatch):
        """When rank 0 — the elastic sync broadcast root — holds the
        minority digest, restore + sync would re-broadcast the
        CORRUPTED state onto healthy ranks (laundering the SDC). That
        case must NOT be a HorovodInternalError the elastic loop
        swallows: it fails hard."""
        with pytest.raises(RuntimeError, match="broadcast root") as ei:
            self._check_with_world(monkeypatch, [9, 7, 7, 7])
        assert not isinstance(ei.value, HorovodInternalError)


# ---------------------------------------------------------------------------
# chaos seams
# ---------------------------------------------------------------------------

class TestSeams:
    def test_grammar_accepts_new_points(self):
        rules = faults.parse(
            "numerics.grad:nan:at=3,rank=1;numerics.param:flip:at=5")
        assert [(r.point, r.action) for r in rules] == [
            ("numerics.grad", "nan"), ("numerics.param", "flip")]

    @pytest.mark.parametrize("bad", [
        "numerics.grad:flip",      # flip is a param-seam action
        "numerics.param:nan",      # nan is a grad-seam action
        "wire.send:nan",           # numerics actions stay at numerics
    ])
    def test_grammar_rejects_cross_seam_actions(self, bad):
        with pytest.raises(ValueError):
            faults.parse(bad)

    def test_corrupt_grads_nan_and_inf(self):
        for act, pred in (("nan", np.isnan), ("inf", np.isinf)):
            faults.configure(f"numerics.grad:{act}", seed=1)
            leaves = [jnp.array([5, 6]), jnp.ones(4)]
            out = numerics.maybe_corrupt_grads(leaves)
            # first INEXACT leaf poisoned in exactly one element
            assert pred(np.asarray(out[1])).sum() == 1
            np.testing.assert_array_equal(np.asarray(out[0]), [5, 6])

    def test_corrupt_grads_disarmed_is_identity(self):
        leaves = [jnp.ones(4)]
        assert numerics.maybe_corrupt_grads(leaves) is leaves

    def test_corrupt_grads_skips_sparse_leaves(self):
        """A BCOO leaf in the gradient list must be passed over, not
        crash the seam — and ANY armed plan reaches this code when
        the guard is on (faults.active() is plan-global), so a
        non-numerics spec must be harmless too."""
        from jax.experimental import sparse as jsparse
        bcoo = jsparse.BCOO.fromdense(jnp.zeros((4, 2)).at[1].set(1.0))
        # armed, but with a rule at a DIFFERENT point
        faults.configure("wire.send:drop:p=0.0", seed=1)
        out = numerics.maybe_corrupt_grads([bcoo, jnp.ones(3)])
        assert out[0] is bcoo
        np.testing.assert_array_equal(np.asarray(out[1]), 1.0)
        # a firing nan rule poisons the first DENSE leaf only
        faults.configure("numerics.grad:nan", seed=1)
        out = numerics.maybe_corrupt_grads([bcoo, jnp.ones(3)])
        assert out[0] is bcoo
        assert np.isnan(np.asarray(out[1])).sum() == 1

    def test_flip_param_changes_one_bit(self):
        faults.configure("numerics.param:flip:times=1", seed=1)
        t = {"w": jnp.arange(8.0)}
        before = numerics.params_digest(t)
        out = numerics.maybe_flip_param(t)
        assert numerics.params_digest(out) != before
        a, b = np.asarray(t["w"]), np.asarray(out["w"])
        assert (a.view(np.int32) != b.view(np.int32)).sum() == 1
        # times=1 exhausted: second call is a no-op
        assert numerics.maybe_flip_param(out) is out

    def test_on_commit_runs_flip_and_counts_commits(self, monkeypatch):
        faults.configure("numerics.param:flip:at=1", seed=1)
        monkeypatch.setenv("HOROVOD_NUMERICS_CHECK_EVERY", "2")

        class FakeState:
            params = {"w": jnp.arange(4.0)}

        st = FakeState()
        before = numerics.params_digest(st.params)
        numerics.on_commit(st)
        assert numerics.params_digest(st.params) != before
        assert st._numerics_commit_count == 1
        numerics.on_commit(st)   # 2nd commit: divergence check runs
        assert st._numerics_commit_count == 2  # (no-op pre-init)

    def test_on_commit_registers_cadence_counter_as_elastic_state(self):
        """The digest allgather is collective, so the cadence counter
        must ride commit/restore/sync like any elastic attr — on a
        real ObjectState it self-registers into _known_attrs (synced
        to joiners, rolled back in lockstep on restore)."""
        hvd.init(config_overrides={"HOROVOD_NUMERICS_CHECK_EVERY": 5})
        try:
            from horovod_tpu.elastic.state import JaxState
            st = JaxState(params={"w": jnp.ones(2)}, step=0)
            st.commit()
            assert "_numerics_commit_count" in st._known_attrs
            assert st._numerics_commit_count == 1
            st.commit()
            st.sync()   # size 1 broadcast; the counter round-trips
            assert st._numerics_commit_count == 2
            st._numerics_commit_count = 99
            st.restore()   # rolls back with the rest of the state
            assert st._numerics_commit_count == 2
        finally:
            hvd.shutdown()

    def test_on_commit_disarmed_fast_path_overhead(self, monkeypatch):
        """Tier-1 perf guard mirroring faults.fire's: with no knobs
        and faults disarmed, the per-commit numerics hook is a few
        lookups. Generous bound for a loaded CI host."""
        monkeypatch.delenv("HOROVOD_NUMERICS_GUARD", raising=False)
        monkeypatch.delenv("HOROVOD_NUMERICS_CHECK_EVERY",
                           raising=False)

        class FakeState:
            params = None

        st = FakeState()
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            numerics.on_commit(st)
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 50e-6, f"{per_call * 1e6:.2f} us/call"


# ---------------------------------------------------------------------------
# lazy-flax satellite (rides this PR)
# ---------------------------------------------------------------------------

def test_flax_loads_lazily_not_at_import_time():
    """`import horovod_tpu` must not drag the external flax package
    in (it is an opt-in frontend like horovod_tpu.torch); hvd.flax
    still resolves on first touch."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; import horovod_tpu as hvd; "
         "assert 'flax' not in sys.modules, 'flax imported eagerly'; "
         "assert 'horovod_tpu.flax' not in sys.modules; "
         "_ = hvd.flax.DistributedTrainState; "
         "import horovod_tpu.flax as hf; "
         "assert hf is hvd.flax"],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr


# ---------------------------------------------------------------------------
# 2-rank chaos (tier-1, fixed seed, behind the capability probe)
# ---------------------------------------------------------------------------

@pytest.mark.integration
class TestNumericsChaos:
    def test_rank_local_nan_one_coordinated_skip(self, tmp_path,
                                                 multiproc_backend):
        """numerics.grad:nan:at=3,rank=1 — one rank's gradient goes
        NaN once, pre-reduction. Every rank must skip exactly that one
        step (each asserts hvd_skipped_steps_total == 1 locally) and
        finish with bitwise-identical parameters (digest allgather
        asserted inside the worker)."""
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get(
            "PYTHONPATH", "")
        env["HOROVOD_NUMERICS_GUARD"] = "1"
        env["HOROVOD_FAULTS"] = "numerics.grad:nan:at=3,rank=1"
        env["HOROVOD_FAULTS_SEED"] = "7"
        env["NUMERICS_TEST_STEPS"] = "6"
        env["NUMERICS_TEST_EXPECT_SKIPS"] = "1"
        r = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
             sys.executable, os.path.join("tests",
                                          "mp_worker_numerics.py")],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=300)
        out = r.stdout + r.stderr
        _skip_if_no_multiproc(out, r.returncode)
        assert r.returncode == 0, out
        assert "faults: firing nan at numerics.grad" in out, out
        assert "numerics ok rank 0 skips 1" in out, out
        assert "numerics ok rank 1 skips 1" in out, out

    def test_param_bitflip_divergence_detected_and_restored(
            self, tmp_path, multiproc_backend):
        """numerics.param:flip:at=4,rank=1 under the elastic worker
        with the sentinel armed (CHECK_EVERY=2): the flip at commit 4
        is caught by that commit's digest check, the raised
        ReplicaDivergenceError names rank 1, and the elastic retry
        loop restores + rank-0-syncs — the job completes with both
        ranks done."""
        script = write_discovery(tmp_path, "echo localhost:2")
        latch = str(tmp_path / "flip.latch")
        env = make_env(tmp_path, steps=10, sleep=0.1)
        env["HOROVOD_FAULTS"] = \
            f"numerics.param:flip:at=4,rank=1,once={latch}"
        env["HOROVOD_FAULTS_SEED"] = "7"
        env["HOROVOD_NUMERICS_CHECK_EVERY"] = "2"
        env["HOROVOD_LOG_LEVEL"] = "info"
        # The divergence error dumps a postmortem; without this it
        # lands in the working directory (the checkout).
        env["HOROVOD_TRACE_POSTMORTEM_DIR"] = str(tmp_path)
        p = launch(script, env, extra=("--reset-limit", "3"))
        out, _ = p.communicate(timeout=420)
        _skip_if_no_multiproc(out, p.returncode)
        assert p.returncode == 0, out
        lines = read_logs(tmp_path)
        assert sum("done" in ln for ln in lines) == 2, (lines, out)
        assert "faults: firing flip at numerics.param" in out, out
        assert os.path.exists(latch), "flip latch never created"
        assert "replica divergence" in out, out
        assert "divergent ranks [1]" in out, out
        # recovered through the elastic restore path, not a crash
        assert "restoring" in out, out
        assert "worker failure" not in out, out
