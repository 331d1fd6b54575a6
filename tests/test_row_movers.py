"""`parallel/row_movers.py`: the kernels in Pallas's interpreter
against the gathers they replace, and the whole `expert_share_ffn`
with the kernels forced on against its `jnp` gathers: output,
`d_tokens`, `d_gates` and the three weight gradients. Dead tiles are
never written, a scratch that held NaN changes nothing, padding rows
inside a live tile are zero, and the counter names the path."""

import contextlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.pallas import primitives as pallas_primitives
from jax.experimental import pallas as pl

import horovod_tpu as hvd
from horovod_tpu.parallel import grouped_matmul as gm
from horovod_tpu.parallel import moe
from horovod_tpu.parallel import row_movers as rm

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


def f32(x):
    return np.asarray(x, np.float32)


def rows(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape, F32).astype(
        dtype)


def codes(key, shape, sources, none_in=4):
    """Source rows with one code in `none_in` left empty (-1)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(key))
    code = jax.random.randint(k1, shape, 0, sources, I32)
    return jnp.where(jax.random.randint(k2, shape, 0, none_in) == 0, -1,
                     code).astype(I32)


def prefix_codes(key, n, sources, rows):
    """Source rows as a group lays its pairs: every `rows` rows a run
    of rows that have a source, then rows that have none (-1); one
    run empty and one full."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(key))
    code = jax.random.randint(k1, (n // rows, rows), 0, sources, I32)
    filled = jax.random.randint(k2, (n // rows, 1), 0, rows + 1, I32)
    filled = filled.at[0].set(rows // 3).at[-1].set(rows)
    if n // rows > 2:
        filled = filled.at[1].set(0)
    return jnp.where(jnp.arange(rows, dtype=I32)[None] < filled, code,
                     -1).reshape(n).astype(I32)


def gathered(src, index):
    """What `rows_in` gives where it writes: the row, zero for -1."""
    return jnp.where(index[:, None] >= 0, src[jnp.maximum(index, 0)], 0)


@contextlib.contextmanager
def interpreted(poison=True):
    """Every Pallas call in the interpreter. It starts a kernel's float
    scratch (and every output) at NaN already; `poison` makes the
    32-bit words of the fetch scratch two bf16 NaNs as well."""
    real_call = pl.pallas_call
    real_value = pallas_primitives.uninitialized_value

    def call(*args, **kwargs):
        kwargs["interpret"] = True
        return real_call(*args, **kwargs)

    def nan_words(shape, dtype):
        if poison and dtype == jnp.uint32:
            return jnp.full(shape, 0x7FC07FC0, dtype)
        return real_value(shape, dtype)
    with mock.patch.object(pl, "pallas_call", call), \
            mock.patch.object(pallas_primitives, "uninitialized_value",
                              nan_words):
        yield


@pytest.mark.parametrize("tile, live_tiles, tiles", [
    (128, 3, 6), (128, 1, 2), (1024, 1, 2), (256, 2, 2)],
    ids=["tile128", "one-live", "tile1024", "all-live"])
def test_rows_in_moves_the_live_rows_only(tile, live_tiles, tiles):
    """Rows of live tiles hold their source row or, for a code of -1
    (padding inside a live tile), zero; the interpreter's mark of an
    output never written (NaN) survives in every dead tile; the NaN a
    scratch starts with reaches nothing."""
    n, live = tiles * tile, live_tiles * tile
    src = rows(0, (64, 512), BF16)
    index = prefix_codes(1, n, 64, rm.step_rows(tile))
    with interpreted():
        got = rm.rows_in(rm.pack_rows(src), index, jnp.asarray([live], I32),
                         width=512, tile_m=tile)
    assert got.dtype == BF16 and got.shape == (n, 512)
    np.testing.assert_array_equal(f32(got[:live]),
                                  f32(gathered(src, index)[:live]))
    assert np.isnan(f32(got[live:])).all()
    assert int(jnp.sum(index[:live] < 0)) > 0      # padding was there


@pytest.mark.parametrize("tile, live_tiles, tiles", [
    (128, 2, 5), (1024, 1, 2)], ids=["tile128", "tile1024"])
def test_rows_in_scales_a_rounded_row(tile, live_tiles, tiles):
    """The combine's backward to the buffer: `scale[s] * row` of the
    f32 source rounded to bf16 by the pack, rounded once more; a row
    that holds no pair gets exactly zero; dead tiles are not written."""
    n, live = tiles * tile, live_tiles * tile
    src = rows(2, (128, 512), F32)        # rounded by the pack
    index = prefix_codes(3, n, 128, rm.step_rows(tile))
    scale = jax.random.uniform(jax.random.PRNGKey(4), (n,), F32)
    with interpreted():
        got = rm.rows_in(rm.pack_rows(src), index, jnp.asarray([live], I32),
                         width=512, tile_m=tile, scale=scale)
    row = gathered(src.astype(BF16), index).astype(F32)
    want = (row * scale[:, None]).astype(BF16)
    np.testing.assert_array_equal(f32(got[:live]), f32(want[:live]))
    assert np.isnan(f32(got[live:])).all()
    assert not f32(got[:live])[np.asarray(index[:live]) < 0].any()


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_pack_rows_puts_two_columns_in_a_word(dtype):
    src = rows(12, (32, 1024), dtype)
    with interpreted():
        packed = rm.pack_rows(src)
    assert packed.shape == (32, 8, 256) and packed.dtype == jnp.uint32
    words = np.asarray(packed[:, :2].reshape(32, 512))
    bits = np.asarray(jax.lax.bitcast_convert_type(src.astype(BF16),
                                                   jnp.uint16), np.uint32)
    np.testing.assert_array_equal(words & 0xFFFF, bits[:, :512])
    np.testing.assert_array_equal(words >> 16, bits[:, 512:])


def landing_codes(k, tokens, sources):
    """(T, k) buffer rows, half of them -1; token 0 lands no choice
    and token 1 all of them."""
    code = codes(7, (tokens, k), sources, none_in=2)
    code = code.at[0].set(-1).at[1].set(jnp.arange(k, dtype=I32))
    landed = np.asarray(jnp.sum(code >= 0, axis=1))
    assert {0, k} <= set(landed.tolist()) and (k == 1 or
                                               (landed % k != 0).any())
    return code


CHOICES = pytest.mark.parametrize(
    "k, tokens", [(4, 512), (1, 64), (3, 48), (8, 256)],
    ids=["k4-two-steps", "k1", "k3", "k8"])


@pytest.mark.parametrize("gated", [True, False], ids=["gated-f32",
                                                      "ones-bf16"])
@CHOICES
def test_rows_out_sums_the_landed_choices(k, tokens, gated):
    """out[t] = sum_j gates[t, j] * buf[code[t, j]] over the landed
    choices, in f32 in the order of j: tokens with none (a zero row),
    some and all of their choices landed."""
    buf = rows(6, (768, 512), BF16)
    code = landing_codes(k, tokens, 768)
    gates = jax.random.uniform(jax.random.PRNGKey(8), (tokens, k), F32)
    with interpreted():
        got = rm.rows_out(buf, code, gates if gated else None,
                          out_dtype=F32 if gated else None)
    want = sum(jnp.where(code[:, j, None] >= 0,
                         (gates[:, j, None] if gated else 1.0) *
                         buf[jnp.maximum(code[:, j], 0)].astype(F32), 0)
               for j in range(k))
    if gated:
        assert got.dtype == F32
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    else:
        assert got.dtype == BF16
        np.testing.assert_array_equal(f32(got), f32(want.astype(BF16)))
    assert not f32(got[0]).any()


@CHOICES
def test_rows_dot_multiplies_with_the_unrounded_row(k, tokens):
    """out[t, j] = <buf[code[t, j]], against[t]> in f32, `against` not
    rounded on the way (values bf16 cannot hold); exactly zero for a
    choice that did not land."""
    buf = rows(6, (768, 512), BF16)
    code = landing_codes(k, tokens, 768)
    against = rows(9, (tokens, 512), F32)
    assert (f32(against.astype(BF16)) != f32(against)).any()
    with interpreted():
        got = rm.rows_dot(buf, code, against)
    want = jnp.stack(
        [jnp.sum(jnp.where(code[:, j, None] >= 0,
                           buf[jnp.maximum(code[:, j], 0)], 0).astype(F32)
                 * against, axis=-1) for j in range(k)], axis=1)
    assert got.shape == (tokens, k) and got.dtype == F32
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * float(jnp.max(jnp.abs(want))))
    assert not f32(got)[np.asarray(code) < 0].any()


@pytest.mark.parametrize("mover", ["pack", "in", "in-scaled", "out",
                                   "out-ones", "dot"])
def test_the_movers_take_four_and_a_half_chunks(mover):
    """Rows of 2,304 columns (Mellum 2's hidden size: four whole chunks
    of packed words and a half-full fifth) and 8 choices a token,
    against the `jnp` gathers: the half chunk's columns land where
    they belong, and its empty high lanes reach no output."""
    width, k, tokens, tile = 2304, 8, 256, 256
    if mover == "pack":
        src = rows(20, (32, width), BF16)
        with interpreted():
            packed = rm.pack_rows(src)
        assert packed.shape == (32, 8, 256)
        half = width // 2
        words = np.asarray(packed[:, :5].reshape(32, 5 * 256))[:, :half]
        bits = np.asarray(jax.lax.bitcast_convert_type(src, jnp.uint16),
                          np.uint32)
        np.testing.assert_array_equal(words & 0xFFFF, bits[:, :half])
        np.testing.assert_array_equal(words >> 16, bits[:, half:])
    elif mover.startswith("in"):
        src = rows(21, (tokens, width), F32 if mover == "in-scaled" else BF16)
        n, live = 3 * tile, 2 * tile
        index = prefix_codes(22, n, tokens, rm.step_rows(tile))
        scale = jax.random.uniform(jax.random.PRNGKey(23), (n,), F32) \
            if mover == "in-scaled" else None
        with interpreted():
            got = rm.rows_in(rm.pack_rows(src), index,
                             jnp.asarray([live], I32), width=width,
                             tile_m=tile, scale=scale)
        want = gathered(src.astype(BF16), index)
        if scale is not None:
            want = (want.astype(F32) * scale[:, None]).astype(BF16)
        np.testing.assert_array_equal(f32(got[:live]), f32(want[:live]))
        assert np.isnan(f32(got[live:])).all()
    else:
        buf = rows(24, (768, width), BF16)
        code = landing_codes(k, tokens, 768)
        gates = jax.random.uniform(jax.random.PRNGKey(25), (tokens, k), F32)
        against = rows(26, (tokens, width), F32)
        picked = [jnp.where(code[:, j, None] >= 0,
                            buf[jnp.maximum(code[:, j], 0)], 0).astype(F32)
                  for j in range(k)]
        with interpreted():
            if mover == "dot":
                got = rm.rows_dot(buf, code, against)
                want = jnp.stack([jnp.sum(r * against, axis=-1)
                                  for r in picked], axis=1)
            elif mover == "out":
                got = rm.rows_out(buf, code, gates, out_dtype=F32)
                want = sum(gates[:, j, None] * r for j, r in enumerate(picked))
            else:
                got = rm.rows_out(buf, code)
                want = sum(picked).astype(BF16)
        if mover == "out-ones":
            np.testing.assert_array_equal(f32(got), f32(want))
        else:
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 * float(
                jnp.max(jnp.abs(want))))


def test_shapes_the_movers_take():
    assert rm.supported(8192, 4, 3584) and rm.supported(256, 4, 3072)
    assert rm.supported(16384, 8, 2304)             # 4.5 packed chunks
    assert rm.supported(8192, 4, 768) and rm.supported(64, 2, 256)
    assert not rm.supported(8190, 4, 3584)          # no whole bf16 tiles
    assert not rm.supported(8192, 9, 3584)          # too many choices
    assert not rm.supported(8192, 4, 640)           # half a chunk in halves
    assert rm.step_rows(256) == 256 and rm.step_rows(1024) == 256
    assert rm.step_rows(128) == 128 and rm.step_rows(8192, 8) == 128


# ---------------------------------------------------------------------------
# The whole layer: kernels forced on against the gathers
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def kernels_forced(movers=True):
    """`expert_share_ffn` as a TPU would trace it, every Pallas call in
    the interpreter. `movers` False keeps the grouped-matmul kernels
    and moves the rows with the layer's own `jnp` gathers: the
    reference the movers are held to, so that the two differ by the
    movers alone."""
    permute, unpermute = moe._permute, moe._unpermute
    with contextlib.ExitStack() as stack:
        stack.enter_context(interpreted())
        stack.enter_context(
            mock.patch.object(jax, "default_backend", lambda: "tpu"))
        if not movers:
            stack.enter_context(mock.patch.object(
                moe, "_permute", lambda t, route, tile: permute(
                    t, route, None)))
            stack.enter_context(mock.patch.object(
                moe, "_unpermute", lambda ys, gates, route, tile: unpermute(
                    ys, gates, route, None)))
        yield


def layer_case(name):
    """(tokens, experts, gates, weights, first, tile) of a case."""
    T, D, F, k, E, held, first, tile = {
        "typical":       (256, 512, 128, 4, 16, 4, 4, 128),
        "expert-unused": (256, 512, 128, 4, 16, 4, 4, 128),
        "tile1024":      (256, 512, 128, 2, 8, 2, 0, 1024),
        "filled":        (64, 512, 128, 4, 4, 4, 0, 128),
        "narrow":        (64, 384, 128, 2, 4, 4, 0, 128),
        "top8-width2304": (64, 2304, 128, 8, 64, 16, 16, 128),
    }[name]
    key = jax.random.split(jax.random.PRNGKey(11), 6)
    logits = jax.random.normal(key[0], (T, E), F32)
    if name in ("typical", "expert-unused"):
        # token 0 chooses held experts only, token 1 none of them
        logits = logits.at[0, first:first + held].set(9.0)
        logits = logits.at[1, first:first + held].set(-9.0)
    if name == "expert-unused":
        logits = logits.at[:, first + 2].set(-1e9)  # held, gets no row
    experts, gates = moe.topk_sigmoid_route(logits, jnp.zeros((E,)), k, 2.0)
    tokens = jax.random.normal(key[1], (T, D), F32).astype(BF16)
    w = [(jax.random.normal(kk, s, F32) * 0.1).astype(BF16)
         for kk, s in zip(key[2:5], [(held, D, F), (held, D, F),
                                     (held, F, D)])]
    return tokens, experts, gates.astype(F32), w, first, tile


def layer_and_gradients(tokens, experts, gates, w, first, tile):
    # a cotangent bf16 cannot hold: rounding it anywhere would show
    cot = jnp.cos(jnp.arange(tokens.shape[0] * tokens.shape[1],
                             dtype=F32)).reshape(tokens.shape)

    def loss(tokens, gates, *w):
        out = moe.expert_share_ffn(tokens, experts, gates, *w, first,
                                   tile_m=tile)
        return jnp.sum(out * cot), out
    (_, out), grads = jax.value_and_grad(loss, (0, 1, 2, 3, 4),
                                         has_aux=True)(tokens, gates, *w)
    return (out, *grads)


def traces(label):
    return hvd.metrics().get("hvd_moe_traces_total", {}).get((label,), 0)


@pytest.mark.parametrize("case", ["typical", "expert-unused", "tile1024",
                                  "filled", "top8-width2304"])
def test_expert_share_ffn_with_the_movers_against_its_gathers(case):
    """Output, d_tokens, d_gates and the three dW of the layer with
    the row movers against the same layer with `jnp` gathers, the
    grouped-matmul kernels in both: tokens with 0, 1 and k choices on
    the held experts, padding inside live tiles and dead tiles behind
    them; a held expert no token chose; one tile of 1,024 rows a
    group; every expert held, so that the buffer is filled to its
    bound and nothing is dropped. The movers round nothing the
    gathers do not and add in their order: the bf16 results are
    bit-equal; the two f32 ones within an f32 rounding or two of their
    largest (`d_gates`' products add a row's lanes up in another
    order, and the CPU's compiler contracts the gathers' `gate * row +
    sum` of `out` into one rounding)."""
    args = layer_case(case)
    tokens, experts, _, w, first, tile = args
    held, k = w[0].shape[0], experts.shape[1]
    landed = np.asarray(jnp.sum((experts >= first) &
                                (experts < first + held), axis=1))
    if case == "filled":
        assert (landed == k).all()
    elif case == "typical":
        assert {0, 1, k} <= set(landed.tolist())
    if case == "expert-unused":
        assert not np.asarray(experts == first + 2).any()
    # the share of the buffer that is live: what the movers walk
    sizes = jnp.sum(experts.reshape(-1, 1) - first ==
                    jnp.arange(held), axis=0)
    padded = jnp.maximum(-(-sizes // tile) * tile, tile)
    n_tiles = -(-moe.max_pairs(tokens.shape[0], k, held) // tile) + held
    live = int(gm.tile_groups(padded, n_tiles, tile)[1][0])
    assert held <= live <= n_tiles and (case == "filled" or live < n_tiles)

    before = traces("sorted_live_tiles")
    with kernels_forced():
        got = layer_and_gradients(*args)
    assert traces("sorted_live_tiles") == before + 1
    with kernels_forced(movers=False):
        want = layer_and_gradients(*args)
    names = ("out", "d_tokens", "d_gates", "dW_gate", "dW_up", "dW_down")
    for name, g, w_ in zip(names, got, want):
        assert g.dtype == w_.dtype and np.isfinite(f32(g)).all(), name
        if name in ("out", "d_gates"):
            np.testing.assert_allclose(
                g, w_, rtol=1e-6, atol=1e-6 * float(jnp.max(jnp.abs(w_))),
                err_msg=name)
        else:
            np.testing.assert_array_equal(f32(g), f32(w_), err_msg=name)


def test_off_the_tpu_the_gathers_stay():
    """No TPU, no kernel: the CPU traces `sorted_ragged` and computes
    with the `jnp` gathers and `lax.ragged_dot`."""
    args = layer_case("typical")
    before = traces("sorted_ragged")
    out = layer_and_gradients(*args)[0]
    assert traces("sorted_ragged") == before + 1
    with kernels_forced():
        kernel_out = layer_and_gradients(*args)[0]
    np.testing.assert_allclose(out, kernel_out, rtol=2e-2, atol=2e-2)


def test_one_rule_engages_matmuls_and_movers_together():
    """A width the grouped matmuls would take and the movers do not
    (whole lanes, an odd number of them: no whole half chunks of
    packed words): on a TPU the layer takes neither, `sorted_ragged`,
    and no Pallas call is traced."""
    args = layer_case("narrow")
    assert gm.kernels_engage(jax.ShapeDtypeStruct((768, 384), BF16),
                             args[3][0], 128) is False   # off the TPU
    before = traces("sorted_ragged"), traces("sorted_live_tiles")
    want = layer_and_gradients(*args)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.object(pl, "pallas_call", side_effect=AssertionError):
        assert gm.kernels_engage(jax.ShapeDtypeStruct((768, 384), BF16),
                                 args[3][0], 128)
        assert not rm.supported(64, 2, 384)
        got = layer_and_gradients(*args)
    assert (traces("sorted_ragged"), traces("sorted_live_tiles")) == (
        before[0] + 2, before[1])
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(f32(g), f32(w_))
