"""`parallel/grouped_matmul.py`: the three kernels in Pallas's
interpreter against `lax.ragged_dot` over the same layout, forward and
both gradients; dead tiles cost nothing and are never written; the
layout helpers; the engagement rule; under `shard_map` with the
replication checker on, where replicated weights get their gradient
summed."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.parallel import grouped_matmul as gm

TILE = 128


def operands(m, k, n, groups, dtype=jnp.float32):
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (groups, k, n),
                          jnp.float32) * 0.1
    return x.astype(dtype), w.astype(dtype)


def masked_loss(fn, live_rows):
    def loss(x, w):
        out = fn(x, w)
        out = jnp.where((jnp.arange(out.shape[0]) < live_rows)[:, None],
                        out.astype(jnp.float32), 0.0)
        return jnp.sum(out * jnp.cos(out)), out
    return jax.value_and_grad(loss, (0, 1), has_aux=True)


@pytest.mark.parametrize("rows, k, n", [
    ([256, 128, 128, 384], 256, 384),     # 7 live tiles of 10
    ([128, 128, 128], 128, 256),          # every group one tile
    ([640], 384, 128),                    # one group, five tiles
], ids=["four-groups", "one-tile-each", "one-group"])
def test_kernels_against_ragged_dot(rows, k, n):
    live = sum(rows)
    m = live + 3 * TILE                   # three dead tiles
    sizes = jnp.asarray(rows, jnp.int32)
    x, w = operands(m, k, n, len(rows))
    (_, got), (dx, dw) = masked_loss(
        lambda x, w: gm.grouped_matmul_kernels(
            x, w, sizes, tile_m=TILE, interpret=True), live)(x, w)
    (_, want), (dx_want, dw_want) = masked_loss(
        lambda x, w: jax.lax.ragged_dot(x, w, sizes), live)(x, w)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dx[:live], dx_want[:live], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(dw, dw_want, rtol=1e-4, atol=1e-4)


def test_bf16_operands_accumulate_in_f32():
    sizes = jnp.asarray([256, 128], jnp.int32)
    x, w = operands(512, 256, 128, 2, jnp.bfloat16)
    got = gm.grouped_matmul_kernels(x, w, sizes, tile_m=TILE,
                                    interpret=True)
    assert got.dtype == jnp.bfloat16
    want = jax.lax.ragged_dot(x.astype(jnp.float32), w.astype(jnp.float32),
                              sizes)
    np.testing.assert_allclose(got[:384].astype(jnp.float32), want[:384],
                               rtol=1e-2, atol=1e-2)


def test_tile_groups_and_column_tiles():
    group, live = gm.tile_groups(jnp.asarray([256, 128, 128, 384]), 10,
                                 TILE)
    assert group.tolist() == [0, 0, 1, 2, 3, 3, 3, 3, 3, 3]
    assert live.tolist() == [7]
    # the published expert: 3584 x 1024 and back
    assert gm.column_tile(1024, 3584, gm._W_BLOCK) == 512
    assert gm.column_tile(3584, 1024, gm._W_BLOCK) == 1792
    assert gm.column_tile(1024, 3584, gm._ACC_BLOCK) == 256
    assert gm.column_tile(3584, 1024, gm._ACC_BLOCK) == 896
    assert gm.column_tile(96, 128, gm._W_BLOCK) == 0
    assert gm.supported((34816, 3584), (8, 3584, 1024))
    assert gm.supported((34816, 1024), (8, 1024, 3584))
    assert not gm.supported((300, 128), (2, 128, 128))
    assert not gm.supported((256, 128), (2, 128, 96))
    with pytest.raises(ValueError, match="grouped matmul does not take"):
        gm.grouped_matmul_kernels(jnp.zeros((256, 128)),
                                  jnp.zeros((2, 128, 96)),
                                  jnp.asarray([256, 0]))


def test_the_cpu_takes_ragged_dot():
    """The engagement rule needs a TPU and bf16; everything else
    computes the same from the same layout with `lax.ragged_dot`."""
    sizes = jnp.asarray([128, 128], jnp.int32)
    x, w = operands(384, 128, 128, 2, jnp.bfloat16)
    assert not gm.kernels_engage(x, w, TILE)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert gm.kernels_engage(x, w, TILE)
        assert not gm.kernels_engage(x.astype(jnp.float32), w, TILE)
    got = gm.grouped_matmul_kernels(x, w, sizes, tile_m=TILE, interpret=True)
    want = jax.lax.ragged_dot(x, w, sizes)
    np.testing.assert_allclose(np.asarray(got[:256], np.float32),
                               np.asarray(want[:256], np.float32),
                               rtol=1e-2, atol=1e-2)


def test_under_shard_map_replicated_weights_get_a_summed_gradient():
    mesh = Mesh(np.array(jax.devices()[:2]), axis_names=("data",))
    sizes = jnp.asarray([128, 128], jnp.int32)
    x, w = operands(2 * 384, 128, 128, 2)

    def local(x, w):
        def loss(x, w):
            out = gm.grouped_matmul_kernels(x, w, sizes, tile_m=TILE,
                                            interpret=True)
            return jnp.sum(jnp.square(out[:256]))
        dx, dw = jax.grad(loss, (0, 1))(x, w)
        return dx, dw
    dx, dw = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("data"), P()),
        out_specs=(P("data"), P())))(x, w)

    def whole(x, w):
        return sum(jnp.sum(jnp.square(jax.lax.ragged_dot(
            x[i * 384:(i + 1) * 384], w, sizes)[:256])) for i in (0, 1))
    dx_want, dw_want = jax.grad(whole, (0, 1))(x, w)
    np.testing.assert_allclose(dw, dw_want, rtol=1e-4, atol=1e-4)
    for i in (0, 1):
        lo = i * 384
        np.testing.assert_allclose(dx[lo:lo + 256], dx_want[lo:lo + 256],
                                   rtol=1e-5, atol=1e-5)
