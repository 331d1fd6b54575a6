"""Parallelism-layer numerics: every strategy is checked against a
single-device oracle on the 8-virtual-device CPU mesh (SURVEY.md §4
technique 2 — fake devices instead of a cluster)."""

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.parallel import (
    MeshSpec, build_mesh, build_train_step, dense_attention, moe_ffn,
    pipeline_apply, ring_attention, stack_stage_params,
    ulysses_attention,
)
from horovod_tpu.parallel.mesh import data_parallel_mesh


def seq_mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), axis_names=("seq",))


# ---------------------------------------------------------------------------
# MeshSpec
# ---------------------------------------------------------------------------

class TestMeshSpec:
    def test_auto_data(self):
        s = MeshSpec(tensor=2).resolve(8)
        assert s.data == 4 and s.tensor == 2 and s.total == 8

    def test_fixed_mismatch(self):
        with pytest.raises(ValueError):
            MeshSpec(data=3, tensor=2).resolve(8)

    def test_indivisible(self):
        with pytest.raises(ValueError):
            MeshSpec(tensor=3).resolve(8)

    def test_build_mesh_axes(self):
        m = build_mesh(MeshSpec(tensor=2, seq=2))
        assert m.shape["tensor"] == 2 and m.shape["seq"] == 2
        assert m.shape["data"] == 2
        m2 = build_mesh(MeshSpec(tensor=2), keep_trivial_axes=False)
        assert "seq" not in m2.shape and m2.shape["data"] == 4


# ---------------------------------------------------------------------------
# Ring attention
# ---------------------------------------------------------------------------

class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full(self, causal):
        B, L, H, D = 2, 32, 4, 16
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B, L, H, D), jnp.float32)
        k = jax.random.normal(kk, (B, L, H, D), jnp.float32)
        v = jax.random.normal(kv, (B, L, H, D), jnp.float32)

        oracle = dense_attention(q, k, v, causal=causal)

        mesh = seq_mesh(4)
        ring = jax.jit(shard_map(
            lambda q, k, v: ring_attention(q, k, v, "seq", causal=causal),
            mesh=mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq")))
        out = ring(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                                   rtol=2e-5, atol=2e-5)

    def test_grad_matches(self):
        B, L, H, D = 1, 16, 2, 8
        key = jax.random.PRNGKey(1)
        q, k, v = (jax.random.normal(kk, (B, L, H, D))
                   for kk in jax.random.split(key, 3))
        mesh = seq_mesh(4)

        def loss_ring(q, k, v):
            f = shard_map(
                lambda q, k, v: ring_attention(q, k, v, "seq"),
                mesh=mesh, in_specs=(P(None, "seq"),) * 3,
                out_specs=P(None, "seq"))
            return jnp.sum(f(q, k, v) ** 2)

        def loss_full(q, k, v):
            return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

        g1 = jax.grad(loss_ring)(q, k, v)
        g2 = jax.grad(loss_full)(q, k, v)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-4)


class TestUlysses:
    def test_matches_full(self):
        B, L, H, D = 2, 32, 8, 16
        key = jax.random.PRNGKey(2)
        q, k, v = (jax.random.normal(kk, (B, L, H, D))
                   for kk in jax.random.split(key, 3))
        oracle = dense_attention(q, k, v, causal=True)
        mesh = seq_mesh(4)
        f = jax.jit(shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, "seq"),
            mesh=mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq")))
        np.testing.assert_allclose(np.asarray(f(q, k, v)),
                                   np.asarray(oracle),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# MoE expert parallelism
# ---------------------------------------------------------------------------

class TestMoE:
    def test_ep_matches_single(self):
        T, Dm, E, F = 64, 16, 4, 32
        key = jax.random.PRNGKey(3)
        k1, k2, k3, k4 = jax.random.split(key, 4)
        tokens = jax.random.normal(k1, (T, Dm))
        router = jax.random.normal(k2, (Dm, E)) * 0.1
        w_in = jax.random.normal(k3, (E, Dm, F)) * 0.1
        w_out = jax.random.normal(k4, (E, F, Dm)) * 0.1

        out1, aux1 = moe_ffn(tokens, router, w_in, w_out,
                             capacity_factor=4.0, axis_name=None)

        ep = 2
        mesh = Mesh(np.array(jax.devices()[:ep]), axis_names=("expert",))
        # tokens replicated per-device would double T; instead shard
        # tokens over expert axis too (each device routes its half).
        f = jax.jit(shard_map(
            lambda t, r, wi, wo: moe_ffn(t, r, wi, wo,
                                         capacity_factor=4.0,
                                         axis_name="expert")[0],
            mesh=mesh,
            in_specs=(P("expert"), P(), P("expert"), P("expert")),
            out_specs=P("expert"),
        ))
        out2 = f(tokens, router, w_in, w_out)
        # Same routing decisions, different capacity bucketing: with
        # generous capacity, outputs must match.
        np.testing.assert_allclose(np.asarray(out2), np.asarray(out1),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Pipeline
# ---------------------------------------------------------------------------

class TestPipeline:
    def test_matches_sequential(self):
        S, Lps, D = 4, 2, 8      # 4 stages, 2 layers per stage
        n_micro, mb = 4, 4
        L = S * Lps
        key = jax.random.PRNGKey(4)
        w = jax.random.normal(key, (L, D, D)) * (1.0 / np.sqrt(D))
        x = jax.random.normal(jax.random.PRNGKey(5), (n_micro, mb, D))

        def layer(wi, h):
            return jnp.tanh(h @ wi)

        # oracle: sequential through all L layers
        def seq_apply(x):
            h = x
            for i in range(L):
                h = layer(w[i], h)
            return h
        oracle = jax.vmap(seq_apply)(x)

        mesh = Mesh(np.array(jax.devices()[:S]), axis_names=("pipe",))
        staged = stack_stage_params({"w": w}, S)["w"]  # (S, Lps, D, D)

        def stage_fn(pw, h):
            def body(h, wi):
                return layer(wi, h), None
            h, _ = lax.scan(body, h, pw)
            return h

        f = jax.jit(shard_map(
            # shard_map keeps the sharded leading dim (size 1): squeeze
            lambda pw, x: pipeline_apply(stage_fn, pw[0], x, "pipe"),
            mesh=mesh, in_specs=(P("pipe"), P()), out_specs=P()))
        out = f(staged, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                                   rtol=1e-5, atol=1e-5)

    def test_pipeline_grads_flow(self):
        S, D = 2, 4
        mesh = Mesh(np.array(jax.devices()[:S]), axis_names=("pipe",))
        w = jax.random.normal(jax.random.PRNGKey(6), (S, 1, D, D)) * 0.5
        x = jax.random.normal(jax.random.PRNGKey(7), (2, 3, D))

        def stage_fn(pw, h):
            return jnp.tanh(h @ pw[0])

        def loss(w):
            f = shard_map(
                lambda pw, x: pipeline_apply(stage_fn, pw[0], x, "pipe"),
                mesh=mesh, in_specs=(P("pipe"), P()), out_specs=P())
            return jnp.sum(f(w, x) ** 2)

        g = jax.grad(loss)(w)
        assert not np.allclose(np.asarray(g), 0.0)

        # oracle grads
        def loss2(w):
            h = x
            for s in range(S):
                h = stage_fn(w[s], h)
            return jnp.sum(h ** 2)
        g2 = jax.grad(loss2)(w)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g2),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# DP train step
# ---------------------------------------------------------------------------

class TestTrainStep:
    def test_dp_matches_full_batch(self):
        import optax
        from horovod_tpu.models import init_mlp, mlp_loss_fn

        mesh = data_parallel_mesh()
        n = mesh.shape["data"]
        params = init_mlp(jax.random.PRNGKey(0), (16, 32, 4))
        opt = optax.sgd(0.1)
        opt_state = opt.init(params)

        B = 8 * n
        images = jax.random.normal(jax.random.PRNGKey(1), (B, 16))
        labels = jax.random.randint(jax.random.PRNGKey(2), (B,), 0, 4)
        batch = {"images": images, "labels": labels}

        step = build_train_step(mlp_loss_fn, opt, mesh, donate=False)
        new_params, _, metrics = step(params, opt_state, batch)

        # oracle: single-device full-batch step
        loss, grads = jax.value_and_grad(mlp_loss_fn)(params, batch)
        updates, _ = opt.update(grads, opt.init(params), params)
        import optax as _o
        oracle = _o.apply_updates(params, updates)

        np.testing.assert_allclose(float(metrics["loss"]), float(loss),
                                   rtol=1e-5)
        for kk in params:
            np.testing.assert_allclose(
                np.asarray(new_params[kk]), np.asarray(oracle[kk]),
                rtol=1e-5, atol=1e-6)
