"""Shared bucketing layer (ops/bucketing.py) + the jit step's bucketed
reduction it feeds (parallel/train.py): partition determinism (SPMD
safety — byte-identical assignment for the same tree + threshold,
in-process and across a fresh interpreter), the reverse-order
property, threshold edge cases (oversized leaf, empty tree, zero
threshold, mixed dtypes via key_fn), and what the step's builder
decides from the mesh: no bucket and no collective on one device,
buckets covering every gradient byte on eight. The step against an
independent reference is tests/test_step_reference.py's."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from horovod_tpu.ops.bucketing import (Bucket, assignment_digest,
                                       leaf_nbytes, partition_buckets,
                                       partition_tree, split_by_dtype)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _leaves():
    return [jnp.zeros(s, d) for s, d in
            [((8,), jnp.float32),      # 32 B
             ((4, 4), jnp.float32),    # 64 B
             ((2,), jnp.float32),      # 8 B
             ((16,), jnp.float32),     # 64 B
             ((3,), jnp.float32)]]     # 12 B


class TestPartition:
    def test_reverse_order_property(self):
        """Buckets walk the leaves last-first: bucket 0 starts at the
        LAST leaf, indices within a bucket strictly decrease, and the
        concatenation of all buckets is exactly reversed(range(n))."""
        buckets = partition_buckets(_leaves(), 80)
        flat = [i for b in buckets for i in b.indices]
        assert flat == list(range(len(_leaves()) - 1, -1, -1))
        for b in buckets:
            assert list(b.indices) == sorted(b.indices, reverse=True)

    def test_threshold_respected_and_bytes_accounted(self):
        buckets = partition_buckets(_leaves(), 80)
        for b in buckets:
            assert b.nbytes <= 80 or len(b.indices) == 1
            assert b.nbytes == sum(leaf_nbytes(_leaves()[i])
                                   for i in b.indices)

    def test_oversized_leaf_travels_alone(self):
        leaves = [jnp.zeros(4, jnp.float32),     # 16 B
                  jnp.zeros(100, jnp.float32),   # 400 B >> threshold
                  jnp.zeros(4, jnp.float32)]
        buckets = partition_buckets(leaves, 64)
        by_size = {b.indices: b.nbytes for b in buckets}
        assert (1,) in by_size and by_size[(1,)] == 400

    def test_empty_tree(self):
        assert partition_buckets([], 1024) == []
        assert partition_tree({}, 1024) == []

    def test_zero_threshold_disables_fusion(self):
        buckets = partition_buckets(_leaves(), 0)
        assert all(len(b.indices) == 1 for b in buckets)
        assert len(buckets) == len(_leaves())

    def test_scalar_leaf_counts_itemsize(self):
        assert leaf_nbytes(jnp.zeros((), jnp.float32)) == 4
        b = partition_buckets([jnp.zeros((), jnp.float64)], 1024)
        assert b == [Bucket(indices=(0,), nbytes=8)]

    def test_key_fn_families_never_share_a_bucket(self):
        leaves = [jnp.zeros(4, jnp.float32), jnp.zeros(4, jnp.int32),
                  jnp.zeros(4, jnp.float32), jnp.zeros(4, jnp.int32)]
        buckets = partition_buckets(
            leaves, 1 << 20, key_fn=lambda i, leaf: str(leaf.dtype))
        for b in buckets:
            assert len({str(leaves[i].dtype) for i in b.indices}) == 1
        # emission order still last-produced-first ACROSS families
        assert buckets[0].indices[0] == 3

    def test_split_by_dtype_preserves_order(self):
        xs = [jnp.zeros(1, jnp.float32), jnp.zeros(1, jnp.bfloat16),
              jnp.zeros(2, jnp.float32)]
        groups = split_by_dtype(xs)
        assert sorted(i for g in groups for i in g) == [0, 1, 2]
        assert [0, 2] in groups and [1] in groups

    def test_determinism_in_process(self):
        """Same shapes/dtypes/threshold => byte-identical digest, for
        independently constructed trees."""
        a = assignment_digest(partition_buckets(_leaves(), 80))
        b = assignment_digest(partition_buckets(_leaves(), 80))
        assert a == b
        # golden pin: the assignment itself is part of the SPMD
        # contract — a silent partitioner change would compile
        # different programs on different processes mid-rollout.
        assert a == "4,3:76;2,1:72;0:32"

    def test_determinism_across_processes(self):
        """A fresh interpreter derives the identical assignment — the
        SPMD-safety contract for cross-process compilation."""
        code = (
            "import jax.numpy as jnp\n"
            "from horovod_tpu.ops.bucketing import (partition_buckets,"
            " assignment_digest)\n"
            "leaves = [jnp.zeros(s, d) for s, d in"
            " [((8,), jnp.float32), ((4, 4), jnp.float32),"
            " ((2,), jnp.float32), ((16,), jnp.float32),"
            " ((3,), jnp.float32)]]\n"
            "print(assignment_digest(partition_buckets(leaves, 80)))\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == assignment_digest(
            partition_buckets(_leaves(), 80))


# ---------------------------------------------------------------------------
# bucketed overlap in build_train_step
# ---------------------------------------------------------------------------

def _mesh():
    return Mesh(np.array(jax.devices()[:8]), axis_names=("data",))


def _loss(params, batch):
    h = jnp.tanh(batch[:, None] * params["w1"][None, :])
    return jnp.mean((h @ params["w2"]) ** 2) + jnp.mean(params["b"] ** 2)


def _params():
    return {"w1": jnp.arange(4.0), "w2": jnp.ones((4, 2)),
            "b": jnp.zeros(3)}


class TestBucketedTrainStep:
    def test_mesh_axis_outside_the_vocabulary_is_an_error(self):
        """The batch shards over data/fsdp/expert only; on any other
        axis name every device would redo the same batch and the two
        reduction paths disagree, so the builder refuses the mesh."""
        from horovod_tpu.parallel.train import build_train_step
        mesh = Mesh(np.array(jax.devices()[:8]), axis_names=("proc",))
        with pytest.raises(ValueError, match="'proc' is not one of"):
            build_train_step(_loss, optax.sgd(0.1), mesh)

    def test_world1_wire_gate_no_buckets(self, monkeypatch):
        """r08 wire gate: on a single-device mesh every leaf's reduce
        axes multiply out to 1 — the psum is the identity — so the
        step must build ZERO buckets (what stays are the size-1
        psums the typing asks for, which XLA elides). This pins the fix for the single-chip copy tax the r08
        attribution caught (+41 dead pack/psum/unpack instructions on
        the world-1 transformer step,
        benchmarks/PROFILE_transformer_r08.json): the bucket
        machinery may never again ship wire-less copies."""
        from horovod_tpu.parallel.train import (build_train_step,
                                                last_overlap_info)
        monkeypatch.delenv("HOROVOD_NUMERICS_GUARD", raising=False)
        mesh = Mesh(np.array(jax.devices()[:1]), axis_names=("data",))
        opt = optax.sgd(0.1)
        params = _params()
        st = opt.init(params)
        batch = jnp.arange(8.0)
        s_one = build_train_step(_loss, opt, mesh, donate=False,
                                 overlap_threshold=16)
        one = s_one.lower(params, st, batch).as_text()
        info = last_overlap_info()
        assert info["traced"] and info["buckets"] == 0, info
        # and on a REAL multi-device mesh the gate must NOT fire: the
        # buckets hold every gradient byte
        s_multi = build_train_step(_loss, opt, _mesh(), donate=False,
                                   overlap_threshold=16)
        multi = s_multi.lower(params, st, batch).as_text()
        info = last_overlap_info()
        assert info["buckets"] >= 2 and multi != one
        assert sum(info["bucket_bytes"]) == sum(
            leaf_nbytes(v) for v in jax.tree_util.tree_leaves(params))

    def test_mixed_dtype_bucket_and_bf16_flag_routing(self,
                                                      monkeypatch):
        """bf16+f32 leaves share buckets (per-dtype wire arrays); the
        guard veto still lands even when a NaN hits only the bf16
        group (whose wire cannot carry an exact vote count)."""
        from horovod_tpu import numerics
        from horovod_tpu.parallel.train import build_train_step
        monkeypatch.setenv("HOROVOD_NUMERICS_GUARD", "1")
        mesh = _mesh()

        def loss2(params, batch):
            h = jnp.tanh(batch[:, None].astype(jnp.bfloat16)
                         * params["wb"][None, :])
            return jnp.mean((h.astype(jnp.float32) @ params["wf"])
                            ** 2)

        params = {"wb": jnp.ones(4, jnp.bfloat16),
                  "wf": jnp.ones((4, 2), jnp.float32)}
        g = numerics.guard_non_finite(optax.sgd(0.1), enabled=True)
        st = g.init(params)
        s = build_train_step(loss2, g, mesh, donate=False,
                             overlap_threshold=1 << 20)
        p, o, _ = s(params, st, jnp.arange(8.0))
        assert numerics.consecutive_skips(o) == 0
        assert float(jnp.abs(p["wf"] - params["wf"]).max()) > 0
        bad = dict(params, wb=params["wb"].at[0].set(jnp.nan))
        p2, o2, _ = s(bad, st, jnp.arange(8.0))
        assert numerics.consecutive_skips(o2) == 1
        np.testing.assert_array_equal(
            np.asarray(p2["wf"]), np.asarray(params["wf"]))

    def test_custom_grad_reducer_gets_summed_grads(self):
        """grad_reducer contract: it receives SUMMED gradients and
        owns scaling."""
        from horovod_tpu.parallel.train import build_train_step
        mesh = _mesh()
        opt = optax.sgd(1.0)
        params = {"w": jnp.zeros(3)}

        def loss(params, batch):
            return jnp.mean(batch) + jnp.sum(params["w"])

        seen = {}

        def reducer(grads):
            seen["called"] = True
            return jax.tree_util.tree_map(lambda g: g / 8.0, grads)

        st = opt.init(params)
        s = build_train_step(loss, opt, mesh, donate=False,
                             overlap_threshold=4,
                             grad_reducer=reducer)
        p, _, _ = s(params, st, jnp.arange(8.0))
        assert seen.get("called")
        # d(sum w)/dw = 1 per device, psum'd to 8, reducer /8 => step
        # of exactly -1.0 under sgd(1.0)
        np.testing.assert_allclose(np.asarray(p["w"]), -1.0,
                                   rtol=1e-6)
