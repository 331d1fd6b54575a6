"""`parallel/grouped_matmul.py`: the kernels in Pallas's interpreter
against `lax.ragged_dot` over the same layout, forward and both
gradients, the plain product and the gate / up pair with the SwiGLU
inside; dead tiles cost nothing, are never written and never read
(NaN in them reaches nothing); the layout helpers; the engagement
rule; under `shard_map` with the replication checker on, where
replicated weights get their gradient summed."""

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


# ---------------------------------------------------------------------------
# Gate and up as one kernel pair with the SwiGLU inside
# ---------------------------------------------------------------------------

SWIGLU_CASES = {
    # tile, rows of every group, k, n, dead tiles
    "tile256-several": (256, [512, 256, 768], 256, 384, 2),
    "tile256-one-each": (256, [256, 256], 128, 256, 3),
    "tile1024-one-each": (1024, [1024, 1024], 128, 128, 1),
    "tile1024-several": (1024, [2048, 1024], 128, 256, 2),
    "tile128-one-group": (128, [640], 384, 128, 3),
}
swiglu_cases = pytest.mark.parametrize("case", list(SWIGLU_CASES))


def swiglu_operands(case, dtype):
    """(tile, sizes, live rows, x, w_gate, w_up, cotangent of act)."""
    tile, rows, k, n, dead = SWIGLU_CASES[case]
    live = sum(rows)
    key = jax.random.split(jax.random.PRNGKey(5), 4)
    shapes = [(live + dead * tile, k), (len(rows), k, n), (len(rows), k, n),
              (live + dead * tile, n)]
    x, w_gate, w_up, cot = (
        (jax.random.normal(kk, shape, jnp.float32) * scale).astype(dtype)
        for kk, shape, scale in zip(key, shapes, (1.0, 0.1, 0.1, 1.0)))
    return tile, jnp.asarray(rows, jnp.int32), live, x, w_gate, w_up, cot


def dead_rows(a, live, value):
    return a.at[live:].set(value)


def oracle_swiglu(sizes):
    def fn(x, w_gate, w_up):
        return gm.swiglu(jax.lax.ragged_dot(x, w_gate, sizes),
                         jax.lax.ragged_dot(x, w_up, sizes)).astype(x.dtype)
    return fn


@swiglu_cases
def test_swiglu_kernels_against_ragged_dot_with_nan_in_dead_tiles(case):
    """act, d_x and both dW against `lax.ragged_dot` + `jax.numpy`,
    with every row of a dead tile NaN in x and in act's cotangent (the
    interpreter leaves NaN in what a kernel never writes, so the
    residuals' dead tiles are NaN too): nothing of a dead tile is
    read, so the live rows and the weights' gradients stay finite and
    equal to the oracle's over zeros."""
    tile, sizes, live, x, w_gate, w_up, cot = swiglu_operands(
        case, jnp.float32)
    assert x.shape[0] > live
    got, pullback = jax.vjp(
        lambda *a: gm.grouped_swiglu_kernels(*a, sizes, tile_m=tile,
                                             interpret=True),
        dead_rows(x, live, jnp.nan), w_gate, w_up)
    dx, dw_gate, dw_up = pullback(dead_rows(cot, live, jnp.nan))
    want, pullback = jax.vjp(oracle_swiglu(sizes), dead_rows(x, live, 0.0),
                             w_gate, w_up)
    dx_want, dw_gate_want, dw_up_want = pullback(dead_rows(cot, live, 0.0))
    for a in (got[:live], dx[:live], dw_gate, dw_up):
        assert np.isfinite(a).all()
    np.testing.assert_allclose(got[:live], want[:live], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(dx[:live], dx_want[:live], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(dw_gate, dw_gate_want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(dw_up, dw_up_want, rtol=1e-4, atol=1e-4)


@swiglu_cases
def test_swiglu_kernels_round_where_three_grouped_matmuls_did(case):
    """bf16: each product rounded, the SwiGLU in f32 from those, act
    rounded once, as two `grouped_matmul_kernels` with `jax.numpy`
    between them gave it: act bit for bit on the live rows. The
    gradients within bf16's roundings of that path's: the cotangents
    of the two products are written as one formula in f32 and rounded
    once like autodiff's, d_x has one rounding of the f32 sum where
    that path rounds each half and their sum."""
    tile, sizes, live, x, w_gate, w_up, cot = swiglu_operands(
        case, jnp.bfloat16)

    def separate(x, w_gate, w_up):
        h_gate, h_up = (gm.grouped_matmul_kernels(x, w, sizes, tile_m=tile,
                                                  interpret=True)
                        for w in (w_gate, w_up))
        return gm.swiglu(h_gate, h_up).astype(x.dtype)
    cot = dead_rows(cot, live, 0.0)
    got, pullback = jax.vjp(
        lambda *a: gm.grouped_swiglu_kernels(*a, sizes, tile_m=tile,
                                             interpret=True),
        x, w_gate, w_up)
    dx, dw_gate, dw_up = pullback(cot)
    want, pullback = jax.vjp(separate, x, w_gate, w_up)
    dx_want, dw_gate_want, dw_up_want = pullback(cot)
    assert got.dtype == dx.dtype == dw_gate.dtype == jnp.bfloat16

    def f32(a):
        return np.asarray(a, np.float32)
    np.testing.assert_array_equal(f32(got[:live]), f32(want[:live]))
    for g, w_ in ((dx[:live], dx_want[:live]), (dw_gate, dw_gate_want),
                  (dw_up, dw_up_want)):
        # bf16 keeps 8 bits: a rounding is 2**-8 of the value
        np.testing.assert_allclose(
            f32(g), f32(w_), rtol=2 ** -6,
            atol=2 ** -7 * float(np.abs(f32(w_)).max()))
    # and against the oracle at bf16's own tolerance
    oracle = oracle_swiglu(sizes)(x, w_gate, w_up)
    np.testing.assert_allclose(f32(got[:live]), f32(oracle[:live]),
                               rtol=1e-2, atol=1e-2)


def test_swiglu_kernels_refuse_what_the_matmuls_refuse():
    with pytest.raises(ValueError, match="grouped matmul does not take"):
        gm.grouped_swiglu_kernels(
            jnp.zeros((256, 128)), jnp.zeros((2, 128, 128)),
            jnp.zeros((2, 128, 96)), jnp.asarray([128, 128]), tile_m=128)


def test_swiglu_under_shard_map_replicated_weights_get_a_summed_gradient():
    mesh = Mesh(np.array(jax.devices()[:2]), axis_names=("data",))
    sizes = jnp.asarray([128, 128], jnp.int32)
    x, w_gate = operands(2 * 384, 128, 128, 2)
    w_up = jnp.flip(w_gate, axis=2)

    def local(x, w_gate, w_up):
        def loss(x, w_gate, w_up):
            out = gm.grouped_swiglu_kernels(x, w_gate, w_up, sizes,
                                            tile_m=TILE, interpret=True)
            return jnp.sum(jnp.square(out[:256]))
        return jax.grad(loss, (0, 1, 2))(x, w_gate, w_up)
    dx, dw_gate, dw_up = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("data"), P(), P()),
        out_specs=(P("data"), P(), P())))(x, w_gate, w_up)

    def whole(x, w_gate, w_up):
        return sum(jnp.sum(jnp.square(oracle_swiglu(sizes)(
            x[i * 384:(i + 1) * 384], w_gate, w_up)[:256])) for i in (0, 1))
    dx_want, dw_gate_want, dw_up_want = jax.grad(whole, (0, 1, 2))(
        x, w_gate, w_up)
    np.testing.assert_allclose(dw_gate, dw_gate_want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(dw_up, dw_up_want, rtol=1e-4, atol=1e-4)
    for i in (0, 1):
        lo = i * 384
        np.testing.assert_allclose(dx[lo:lo + 256], dx_want[lo:lo + 256],
                                   rtol=1e-5, atol=1e-5)
