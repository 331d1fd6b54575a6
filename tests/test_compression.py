"""Gradient compression (ops/compression.py) and the bucketing-layer
transform it feeds (parallel/train.py compression=..., the eager
DistributedGradientTransformation PowerSGD path): registry parsing,
the balanced matrix fold, cast round-trip bounds, PowerSGD round-trip
quality + full-rank exactness, warm-start determinism across fresh
interpreters (the SPMD purity contract), the error-feedback residual
surviving a simulated elastic restart via `JaxState`, bypass
exactness for ineligible leaves, and the HLO identity pins:
compression="none" lowers BYTE-IDENTICAL to the plain builder, and
powersgd genuinely changes the program. The 2-rank crash/restore leg
lives behind the same multiproc capability probe test_chaos.py uses
(tests/mp_worker_compression.py)."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.ops import compression as C

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Registry / spec parsing
# ---------------------------------------------------------------------------

class TestRegistry:
    @pytest.mark.parametrize("raw,kind,rank", [
        ("none", "none", 4), ("fp16", "fp16", 4), ("bf16", "bf16", 4),
        ("powersgd", "powersgd", 4), ("powersgd:2", "powersgd", 2),
        ("powersgd(rank=8)", "powersgd", 8), ("POWERSGD:1",
                                              "powersgd", 1),
    ])
    def test_accepted_spellings(self, raw, kind, rank):
        spec = C.resolve_compression(raw)
        assert (spec.kind, spec.rank) == (kind, rank)

    def test_typo_raises_not_silently_uncompressed(self):
        with pytest.raises(ValueError, match="unknown"):
            C.resolve_compression("powersdg")
        with pytest.raises(ValueError, match="unparseable"):
            C.resolve_compression("powersgdx")
        with pytest.raises(ValueError, match="rank"):
            C.resolve_compression("powersgd:0")

    def test_knob_defaults_match_docs(self, monkeypatch):
        """The registry defaults the user guide's knob table states:
        none / rank 4 / warmup 0 / min_elements 4096."""
        for k in ("HOROVOD_COMPRESSION", "HOROVOD_COMPRESSION_RANK",
                  "HOROVOD_COMPRESSION_WARMUP_STEPS",
                  "HOROVOD_COMPRESSION_MIN_ELEMENTS"):
            monkeypatch.delenv(k, raising=False)
        spec = C.resolve_compression()
        assert spec == C.CompressionSpec("none", 4, 4096, 0)

    def test_tags(self):
        assert C.resolve_compression("powersgd:4").tag() == "powersgd:4"
        assert C.resolve_compression("bf16").tag() == "bf16"
        assert C.tag_of(C.Compression.none) == "none"
        assert C.tag_of(C.Compression.fp16) == "fp16"
        assert C.tag_of(C.Compression.powersgd(rank=2)) == "powersgd:2"

    def test_spec_of_every_eager_value(self):
        assert C.spec_of(C.Compression.bf16).kind == "bf16"
        assert C.spec_of("powersgd:3").rank == 3
        assert C.spec_of(C.Compression.powersgd(rank=5)).rank == 5
        s = C.CompressionSpec("fp16", 1, 2, 3)
        assert C.spec_of(s) is s
        with pytest.raises(ValueError):
            C.spec_of(object())


# ---------------------------------------------------------------------------
# Matrix fold + eligibility
# ---------------------------------------------------------------------------

class TestMatrixFold:
    def test_2d_is_identity(self):
        assert C.matrix_shape((128, 256)) == (128, 256)
        assert C.matrix_shape((3, 1024)) == (3, 1024)

    def test_scan_stacked_block_folds_balanced(self):
        """The load-bearing case: a scan-stacked transformer block
        must NOT fold to (layers, d*d) — rank-r across layers with
        factors a third the raw bytes — but to the balanced
        (layers*d, d) view."""
        assert C.matrix_shape((24, 1024, 1024)) == (24 * 1024, 1024)
        assert C.matrix_shape((2, 64, 64)) == (2 * 64, 64)

    def test_fold_is_axis_boundary_only(self):
        # (4, 4, 4): boundaries give (4,16) and (16,4); the first
        # minimizer wins deterministically.
        assert C.matrix_shape((4, 4, 4)) == (4, 16)

    def test_wire_elements_track_fold(self):
        p, q = C.powersgd_wire_elements((24, 1024, 1024), 4)
        assert (p, q) == (24 * 1024 * 4, 1024 * 4)
        # and the factor wire actually beats raw by a lot
        raw = 24 * 1024 * 1024
        assert raw / (p + q) > 100

    def test_effective_rank_caps_at_both_dims(self):
        assert C.effective_rank((2, 4096), 4) == 2
        assert C.effective_rank((512, 512), 4) == 4
        assert C.effective_rank((64, 3), 8) == 3

    def test_eligibility(self):
        assert C.powersgd_eligible((64, 64), jnp.float32, 1024)
        assert not C.powersgd_eligible((4096,), jnp.float32, 1024)
        assert not C.powersgd_eligible((64, 64), jnp.int32, 1024)
        assert not C.powersgd_eligible((16, 16), jnp.float32, 1024)
        # degenerate matrix view: (1, n) compresses nothing
        assert not C.powersgd_eligible((1, 4096), jnp.float32, 1024)


# ---------------------------------------------------------------------------
# Cast compressors: round-trip bounds
# ---------------------------------------------------------------------------

class TestCastRoundTrip:
    @pytest.mark.parametrize("comp,wire,rtol", [
        (C.Compression.fp16, jnp.float16, 1e-3),
        (C.Compression.bf16, jnp.bfloat16, 8e-3),
    ])
    def test_round_trip_relative_error(self, comp, wire, rtol):
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(256,)), jnp.float32)
        c, ctx = comp.compress(x)
        assert c.dtype == wire and ctx == jnp.float32
        back = comp.decompress(c, ctx)
        assert back.dtype == jnp.float32
        assert float(jnp.max(jnp.abs(back - x)
                             / (jnp.abs(x) + 1e-12))) < rtol

    def test_integer_leaves_pass_through(self):
        x = jnp.arange(8, dtype=jnp.int32)
        c, ctx = C.Compression.fp16.compress(x)
        assert c.dtype == jnp.int32 and ctx is None
        assert (C.Compression.fp16.decompress(c, ctx) == x).all()

    def test_bf16_survives_fp16_overflow_range(self):
        """The TPU-native wire choice: 1e5 overflows fp16 to inf but
        bf16 keeps the exponent (the no-overflow-cliff rationale)."""
        x = jnp.asarray([1e5], jnp.float32)
        cf, _ = C.Compression.fp16.compress(x)
        cb, _ = C.Compression.bf16.compress(x)
        assert bool(jnp.isinf(cf.astype(jnp.float32))[0])
        assert float(cb.astype(jnp.float32)[0]) == pytest.approx(
            1e5, rel=0.01)


# ---------------------------------------------------------------------------
# PowerSGD math
# ---------------------------------------------------------------------------

class TestPowerSGDMath:
    def test_gram_orthogonalize_columns_orthonormal(self):
        p = jnp.asarray(np.random.default_rng(1).normal(
            size=(64, 4)), jnp.float32)
        q = C.gram_orthogonalize(p)
        gram = np.asarray(q.T @ q, np.float64)
        assert np.allclose(gram, np.eye(4), atol=1e-4)

    def test_gram_orthogonalize_zero_matrix_no_nans(self):
        """First-step all-zero cotangents: the jitter keeps Cholesky
        positive-definite — scaled basis out, never NaNs."""
        q = C.gram_orthogonalize(jnp.zeros((16, 2), jnp.float32))
        assert bool(jnp.isfinite(q).all())

    def test_full_rank_round_trip_is_exact(self):
        """rank >= min(n, m) reproduces the exact sum: PowerSGD's
        error is purely the rank deficit."""
        rng = np.random.default_rng(2)
        m = jnp.asarray(rng.normal(size=(8, 6)), jnp.float32)
        q0 = C.init_q((8, 6), 6, 0)
        outs, _, es = C.powersgd_reduce(
            [m], [q0], [jnp.zeros((8, 6), jnp.float32)],
            lambda x: x, 1)
        assert np.allclose(np.asarray(outs[0]), np.asarray(m),
                           atol=1e-4)
        assert float(jnp.abs(es[0]).max()) < 1e-4

    def test_error_feedback_returns_the_residual(self):
        """The EF telescoping identity: out_t = m + e_{t-1} - e_t, so
        after T rounds on the SAME gradient the cumulative
        communicated signal is exactly T*m - e_T. With the residual
        bounded (it is — the feedback loop has a fixed point), the
        RELATIVE error of what crossed the wire shrinks with T:
        compression error is deferred, never lost. The target is
        what PowerSGD is built for — a low-rank-dominant gradient
        (rank-1 signal + small dense noise); on a full-rank Gaussian
        rank-r tracking has nothing to grab and the residual grows
        for many steps (that regime is the min_elements/rank
        knob's problem, not EF's)."""
        rng = np.random.default_rng(3)
        m = jnp.asarray(
            rng.normal(size=(32, 1)) @ rng.normal(size=(1, 16))
            + 0.05 * rng.normal(size=(32, 16)), jnp.float32)
        qs = [C.init_q((32, 16), 2, 0)]
        es = [jnp.zeros((32, 16), jnp.float32)]
        total = jnp.zeros_like(m)
        norms, rels = [], []
        m_norm = float(jnp.linalg.norm(m))
        for t in range(1, 11):
            outs, qs, es = C.powersgd_reduce([m], qs, es,
                                             lambda x: x, 1)
            total = total + outs[0]
            # telescoping: cumulative error IS the current residual
            assert np.allclose(np.asarray(t * m - total),
                               np.asarray(es[0]), atol=1e-3)
            norms.append(float(jnp.linalg.norm(es[0])))
            rels.append(norms[-1] / (t * m_norm))
        # residual stays small vs the signal => the relative wire
        # error decreases (measured: 0.040 -> 0.027 over 10 rounds)
        assert max(norms) < m_norm
        assert rels[-1] < 0.75 * rels[0]

    def test_multi_leaf_packing_matches_single(self):
        """Two leaves through one packed wire == each alone: the
        pack/slice bookkeeping is transparent."""
        rng = np.random.default_rng(4)
        a = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
        qa, qb = C.init_q((16, 8), 2, 0), C.init_q((8, 8), 2, 1)
        za = jnp.zeros_like(a)
        zb = jnp.zeros_like(b)
        packed, _, _ = C.powersgd_reduce([a, b], [qa, qb], [za, zb],
                                         lambda x: x, 1)
        solo_a, _, _ = C.powersgd_reduce([a], [qa], [za],
                                         lambda x: x, 1)
        solo_b, _, _ = C.powersgd_reduce([b], [qb], [zb],
                                         lambda x: x, 1)
        assert np.allclose(np.asarray(packed[0]),
                           np.asarray(solo_a[0]), atol=1e-5)
        assert np.allclose(np.asarray(packed[1]),
                           np.asarray(solo_b[0]), atol=1e-5)

    def test_init_q_deterministic_across_interpreters(self):
        """A fresh interpreter derives bit-identical warm-start
        factors — the cross-process SPMD purity contract (every rank
        computes Q locally; divergent factors would compress
        different subspaces on different ranks)."""
        code = (
            "import numpy as np\n"
            "from horovod_tpu.ops.compression import init_q\n"
            "q = np.asarray(init_q((24, 64, 64), 4, 7), np.float32)\n"
            "print(q.tobytes().hex())\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        env.pop("XLA_FLAGS", None)
        outs = {subprocess.run(
            [sys.executable, "-c", code], cwd=REPO, env=env,
            capture_output=True, text=True, timeout=120,
            check=True).stdout.strip() for _ in range(2)}
        assert len(outs) == 1
        here = np.asarray(C.init_q((24, 64, 64), 4, 7),
                          np.float32).tobytes().hex()
        assert outs == {here}


# ---------------------------------------------------------------------------
# The jit plane: build_train_step(compression=...)
# ---------------------------------------------------------------------------

def _mesh():
    return Mesh(np.array(jax.devices()[:8]), axis_names=("data",))


def _loss(params, batch):
    h = jnp.tanh(batch[:, None] * params["w1"][None, :])
    return jnp.mean((h @ params["w2"]) ** 2) + jnp.mean(params["b"] ** 2)


def _params():
    # w2 (32x16 f32, 512 elements) is the one powersgd-eligible leaf
    # at min_elements=256; w1/b bypass (1-D / too small).
    return {"w1": jnp.arange(32.0) / 32.0,
            "w2": jnp.ones((32, 16)) * 0.1 + jnp.arange(
                32.0 * 16).reshape(32, 16) * 1e-3,
            "b": jnp.zeros(3)}


def _batch(mesh):
    return jax.device_put(jnp.arange(8.0),
                          NamedSharding(mesh, P("data")))


class TestJitPlane:
    def test_none_is_byte_identical_hlo(self, monkeypatch):
        """compression="none" (explicit AND knob-default) lowers the
        IDENTICAL program to a build that never heard of compression
        — the transform is free when off. powersgd must genuinely
        change the program, or the knob is theater."""
        from horovod_tpu.parallel.train import build_train_step
        for k in ("HOROVOD_COMPRESSION", "HOROVOD_NUMERICS_GUARD"):
            monkeypatch.delenv(k, raising=False)
        mesh = _mesh()
        opt = optax.sgd(0.1)
        params = _params()
        st = opt.init(params)
        batch = _batch(mesh)
        base = build_train_step(_loss, opt, mesh, donate=False,
                                overlap=True, overlap_threshold=512)
        expl = build_train_step(_loss, opt, mesh, donate=False,
                                overlap=True, overlap_threshold=512,
                                compression="none")
        hlo_base = base.lower(params, st, batch).as_text()
        assert expl.lower(params, st, batch).as_text() == hlo_base
        monkeypatch.setenv("HOROVOD_COMPRESSION", "none")
        knob = build_train_step(_loss, opt, mesh, donate=False,
                                overlap=True, overlap_threshold=512)
        assert knob.lower(params, st, batch).as_text() == hlo_base
        monkeypatch.setenv("HOROVOD_COMPRESSION", "bf16")
        cast = build_train_step(_loss, opt, mesh, donate=False,
                                overlap=True, overlap_threshold=512)
        assert cast.lower(params, st, batch).as_text() != hlo_base

    def test_powersgd_bypass_leaves_stay_exact(self, monkeypatch):
        """Under powersgd only eligible leaves go lossy: w1 and b
        (bypass family) update bit-identically to the uncompressed
        step, while w2 (the compressed leaf) differs — the bypass is
        real, per-leaf, and doesn't leak."""
        from horovod_tpu.parallel.train import (build_train_step,
                                                init_compression_state)
        monkeypatch.delenv("HOROVOD_COMPRESSION", raising=False)
        mesh = _mesh()
        opt = optax.sgd(0.1)
        params = _params()
        st = opt.init(params)
        batch = _batch(mesh)
        exact = build_train_step(_loss, opt, mesh, donate=False,
                                 overlap=True, overlap_threshold=512)
        p_e, _, _ = exact(params, st, batch)
        comp = build_train_step(_loss, opt, mesh, donate=False,
                                overlap=True, overlap_threshold=512,
                                compression="powersgd:2",
                                compression_min_elements=256)
        cstate, _ = init_compression_state(
            params, mesh, compression="powersgd:2",
            compression_min_elements=256)
        assert set(cstate["q"]) == set(cstate["e"])
        assert len(cstate["q"]) == 1  # exactly w2
        p_c, _, _, _ = comp(params, st, batch, cstate)
        np.testing.assert_array_equal(np.asarray(p_e["w1"]),
                                      np.asarray(p_c["w1"]))
        np.testing.assert_array_equal(np.asarray(p_e["b"]),
                                      np.asarray(p_c["b"]))
        assert not np.allclose(np.asarray(p_e["w2"]),
                               np.asarray(p_c["w2"]), atol=1e-9)

    def test_everything_ineligible_matches_exact(self, monkeypatch):
        """min_elements above every leaf: the powersgd build must
        reduce to the exact path for the whole tree (all-bypass), and
        the state is empty."""
        from horovod_tpu.parallel.train import (build_train_step,
                                                init_compression_state,
                                                plan_overlap)
        monkeypatch.delenv("HOROVOD_COMPRESSION", raising=False)
        mesh = _mesh()
        opt = optax.sgd(0.1)
        params = _params()
        st = opt.init(params)
        batch = _batch(mesh)
        plan = plan_overlap(params, mesh, overlap_threshold=512,
                            compression="powersgd",
                            compression_min_elements=1 << 20)
        assert set(plan.bucket_compression) == {"none"}
        cstate, _ = init_compression_state(
            params, mesh, compression="powersgd",
            compression_min_elements=1 << 20)
        assert cstate == {"q": {}, "e": {}}
        exact = build_train_step(_loss, opt, mesh, donate=False,
                                 overlap=True, overlap_threshold=512)
        comp = build_train_step(_loss, opt, mesh, donate=False,
                                overlap=True, overlap_threshold=512,
                                compression="powersgd",
                                compression_min_elements=1 << 20)
        p_e, _, _ = exact(params, st, batch)
        p_c, _, _, _ = comp(params, st, batch, cstate)
        for k in params:
            np.testing.assert_array_equal(np.asarray(p_e[k]),
                                          np.asarray(p_c[k]))

    def test_residual_survives_simulated_elastic_restart(self,
                                                         monkeypatch):
        """The first-class compression_state through `JaxState`:
        3 steps -> commit -> 2 more steps must equal 3 steps ->
        commit -> CRASH (state clobbered) -> restore -> 2 more steps,
        bit-for-bit. A restart that silently reset the residual would
        diverge immediately — accumulated error is gradient signal."""
        from horovod_tpu.elastic.state import JaxState
        from horovod_tpu.parallel.train import (build_train_step,
                                                init_compression_state)
        monkeypatch.delenv("HOROVOD_COMPRESSION", raising=False)
        mesh = _mesh()
        opt = optax.adam(1e-2)
        params = _params()
        batch = _batch(mesh)
        step = build_train_step(_loss, opt, mesh, donate=False,
                                overlap=True, overlap_threshold=512,
                                compression="powersgd:2",
                                compression_min_elements=256)

        def run(p, s, c, n):
            for _ in range(n):
                p, s, _, c = step(p, s, batch, c)
            return p, s, c

        cstate0, _ = init_compression_state(
            params, mesh, compression="powersgd:2",
            compression_min_elements=256)
        p3, s3, c3 = run(params, opt.init(params), cstate0, 3)
        (e_key,) = c3["e"]
        assert float(jnp.abs(c3["e"][e_key]).max()) > 0  # EF is live

        state = JaxState(params=p3, opt_state=s3,
                         compression_state=c3, step=3)
        state.save()  # the commit
        # the crash: everything in device memory is lost/garbage
        state.params = jax.tree.map(jnp.zeros_like, p3)
        state.opt_state = jax.tree.map(jnp.zeros_like, s3)
        state.compression_state = jax.tree.map(jnp.zeros_like, c3)
        state.restore()
        p_r, _, _ = run(state.params, state.opt_state,
                        state.compression_state, 2)
        p_u, _, _ = run(p3, s3, c3, 2)  # uninterrupted
        for k in params:
            np.testing.assert_array_equal(np.asarray(p_u[k]),
                                          np.asarray(p_r[k]))


# ---------------------------------------------------------------------------
# 2-rank crash/restore chaos leg (real subprocesses)
# ---------------------------------------------------------------------------

_NO_MULTIPROC = ("this jaxlib's CPU backend cannot run cross-process "
                 "collectives (affects every multiprocess "
                 "integration test)")


@pytest.fixture(scope="module")
def multiproc_backend():
    """Same cheap capability probe as test_chaos.py: one tiny 2-rank
    allreduce before burning restarts on an incapable backend."""
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


@pytest.mark.integration
def test_two_rank_powersgd_crash_restore(tmp_path, multiproc_backend):
    """Eager-plane PowerSGD across two REAL processes: phase `ref`
    trains 6 uninterrupted steps; phase `a` trains 3, commits, and
    hard-exits; phase `b` restores the commit (PowerSGD Q/residual
    ride inside opt_state, exactly what elastic JaxState snapshots)
    and finishes — the resumed loss must match the uninterrupted run
    to float tolerance, proving the error memory crossed the crash."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["COMPRESSION_WORKER_DIR"] = str(tmp_path)

    def run(phase, check=True):
        e = dict(env, COMPRESSION_WORKER_PHASE=phase)
        r = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
             sys.executable,
             os.path.join(REPO, "tests", "mp_worker_compression.py")],
            cwd=REPO, env=e, capture_output=True, text=True,
            timeout=300)
        if check:
            assert r.returncode == 0, r.stdout + "\n" + r.stderr
        return r

    run("ref")
    ra = run("a", check=False)
    assert ra.returncode != 0, "phase a is supposed to crash"
    assert "COMPRESSION WORKER COMMITTED" in ra.stdout, (
        ra.stdout + "\n" + ra.stderr)
    run("b")
    import json
    ref = json.loads((tmp_path / "ref.json").read_text())
    res = json.loads((tmp_path / "resumed.json").read_text())
    assert res["loss"] == pytest.approx(ref["loss"], abs=1e-5), (
        ref, res)
    assert res["residual_norm"] == pytest.approx(
        ref["residual_norm"], abs=1e-4)
    assert ref["residual_norm"] > 0  # EF engaged in both runs
