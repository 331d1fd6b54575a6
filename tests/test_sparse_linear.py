"""`models/sparse_linear.py` (block-sparse attention that selects its
keys and linear attention with a decay a head, mixed 1 : 3 inside a
scanned period, under MiniCPM's multipliers) and its two cores,
`parallel/sparse_attention.py` and `parallel/linear_attention.py`,
against the plain reference of `perfbench/reference/minicpm_sala.py`
and against loops written out, on seeded weights at tiny widths that
keep the published model's ratios: one sparse layer to three linear
ones, pooled keys of 2 strides, blocks of 4 strides, a forced window
of 2 blocks and 1 initial block of top-4, a dense length of a quarter
of the long sequence."""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.metrics import snapshot
from horovod_tpu.models import sparse_linear as sl
from horovod_tpu.parallel import build_train_step
from horovod_tpu.parallel import fused_attention as fa
from horovod_tpu.parallel import linear_attention as la
from horovod_tpu.parallel import sparse_attention as sa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
M4, LA = "minicpm4", "lightning-attn"
SPARSE_CONFIG = {"kernel_size": 4, "kernel_stride": 2, "block_size": 8,
                 "topk": 4, "init_blocks": 1, "window_size": 16,
                 "dense_len": 32}
# the published config.json's keys at tiny widths, as one share holds
# them: two periods, so that the scan runs twice
CONFIG = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 16,
    "hidden_size": 64, "intermediate_size": 128, "lightning_head_dim": 16,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_scale": "1/sqrt(d)",
    "lightning_use_rope": True, "mixer_types": [M4, LA, LA, LA] * 3,
    "num_attention_heads": 4, "num_hidden_layers": 8,
    "num_key_value_heads": 1, "qk_norm": True, "rms_norm_eps": 1e-6,
    "vocab_size": 128, "rope_theta": 10000, "scale_emb": 12,
    "scale_depth": 1.4, "dim_model_base": 4, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True, "ffn_columns_held": 64,
    "lightning_heads_first": 0, "initializer_range": 0.02,
    "sparse_config": SPARSE_CONFIG,
    "published": {"num_hidden_layers": 32, "lightning_nh": 8}}
# the layer whole: 2 head shares, 2 column shares
UNCUT = {**CONFIG, "num_attention_heads": 8, "num_key_value_heads": 2,
         "lightning_nh": 8, "lightning_nkv": 8, "ffn_columns_held": 128}
SPEC = sa.SparseSpec(kernel_size=4, kernel_stride=2, block=8, topk=4,
                     init_blocks=1, window=16, dense_len=32)


def _perfbench(kind):
    from perfbench import run
    return run.load_module(os.path.join(REPO, "perfbench"), kind,
                           "minicpm_sala")


@pytest.fixture(scope="module")
def reference():
    return _perfbench("reference")


def _library(config):
    """The adapter's translation, in float32 and without remat, so
    that the comparison is of the mathematics."""
    return dataclasses.replace(_perfbench("models").library_config(config),
                               dtype=jnp.float32, remat=False)


def _seeded(cfg, seed):
    """`init_params` with every leaf seeded: norm gains that are not
    one show where a gain is applied."""
    params = sl.init_params(cfg, jax.random.PRNGKey(seed))
    leaves, tree = jax.tree.flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))

    def made(key, path, a):
        noise = jax.random.normal(key, a.shape, a.dtype)
        if "norm" in jax.tree_util.keystr(path[-1:]):
            return 1.0 + 0.3 * noise
        return 0.15 * noise
    return jax.tree.unflatten(
        tree, [made(k, path, a) for k, (path, a) in zip(keys, leaves)])


@pytest.fixture(scope="module")
def cfg():
    return _library(CONFIG)


@pytest.fixture(scope="module")
def params(cfg):
    return jax.jit(lambda: _seeded(cfg, 3))()


def one_layer(params, kind):
    return jax.tree.map(lambda a: a[0], params[kind])


def activations(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def close(got, want, tol=2e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


# -- the configuration ------------------------------------------------------

def test_the_period_is_read_from_the_mixer_types(cfg):
    assert cfg.period == 4 and len(cfg.layer_kinds) == 8
    assert cfg.period_kinds == ("sparse", "linear", "linear", "linear")
    assert cfg.sparse == SPEC
    assert cfg.residual_scale == 1.4 / 32 ** 0.5
    assert cfg.logit_scale == 1 / 16 and cfg.embed_scale == 12.0


@pytest.mark.parametrize("change", [
    {"layer_kinds": ("sparse", "linear", "linear")},
    {"layer_kinds": ("sparse", "linear", "linear", "sparse") * 2,
     "period": 8 // 3},
    {"layer_kinds": ("sparse", "full", "linear", "linear")}],
    ids=["half-a-period", "no-repeat", "unknown-kind"])
def test_a_stack_that_is_no_whole_periods_is_refused(change):
    with pytest.raises(ValueError, match="whole periods"):
        sl.SparseLinearConfig(**{"period": 4, **change})


@pytest.mark.parametrize("change", [
    {"kernel_size": 5}, {"block": 7}, {"window": 4}, {"window": 12},
    {"topk": 2}], ids=lambda c: "-".join(f"{k}{v}" for k, v in c.items()))
def test_a_selection_that_is_no_whole_blocks_is_refused(change):
    with pytest.raises(ValueError, match="whole"):
        dataclasses.replace(SPEC, **change)


# -- the linear core --------------------------------------------------------

def quadratic(q, k, v, slopes):
    """o_t = sum_{s <= t} lam^(t - s) (q_t . k_s / sqrt(d)) v_s."""
    L = q.shape[1]
    behind = (jnp.arange(L)[:, None] - jnp.arange(L)[None, :]).astype(
        jnp.float32)
    decay = jnp.where(behind >= 0, jnp.exp(
        -slopes[:, None, None] * jnp.maximum(behind, 0.0)), 0.0)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    return jnp.einsum("bhqk,bkhd->bqhd", scores * decay, v)


@pytest.fixture(scope="module")
def qkv():
    """A length that is a multiple of neither chunk size tried."""
    return tuple(activations(20 + i, 2, 50, 4, 16) for i in range(3))


def test_decay_slopes_are_the_published_heads():
    """Heads 4..7 of 8: 2^(-8 (h + 1) / 8); the fastest head forgets
    within a token, the slowest over 256."""
    np.testing.assert_allclose(
        np.asarray(la.decay_slopes(8, 4, 4)),
        [2.0 ** -5, 2.0 ** -6, 2.0 ** -7, 2.0 ** -8])
    full = np.asarray(la.decay_slopes(32))
    assert full.shape == (32,)
    np.testing.assert_allclose(full[[0, 31]], [2 ** -0.25, 2.0 ** -8])


@pytest.mark.parametrize("chunk", [8, 16, None], ids=["c8", "c16", "whole"])
def test_chunked_linear_core_is_the_recurrence_and_the_quadratic_form(
        qkv, chunk):
    q, k, v = qkv
    slopes = la.decay_slopes(8, 0, 4)
    got = la.linear_attention(q, k, v, slopes, chunk=chunk)
    close(got, la.recurrent_linear_attention(q, k, v, slopes), 1e-5)
    close(got, quadratic(q, k, v, slopes), 1e-5)


@pytest.mark.parametrize("chunk", [8, 16], ids=["c8", "c16"])
def test_chunked_linear_core_gradients(qkv, chunk):
    """The reversed scan of the custom backward against autodiff
    through the recurrence and through the quadratic form."""
    slopes = la.decay_slopes(8, 2, 4)
    weight = jnp.cos(jnp.arange(50 * 4 * 16, dtype=jnp.float32)).reshape(
        50, 4, 16)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a, slopes) * weight),
                        argnums=(0, 1, 2))(*qkv)
    got = grads(lambda q, k, v, s: la.linear_attention(q, k, v, s,
                                                       chunk=chunk))
    for other in (la.recurrent_linear_attention, quadratic):
        for a, b in zip(got, grads(other)):
            close(a, b, 2e-5)
    assert all(float(jnp.max(jnp.abs(g))) > 0.1 for g in got)


def test_a_fast_head_underflows_to_zero_not_to_nan():
    """lam^C = exp(-256 x 0.84) is 0 in float32; nothing divides by
    it."""
    q, k, v = (activations(30 + i, 1, 512, 2, 16) for i in range(3))
    slopes = jnp.asarray([2 ** -0.25, 2.0 ** -8], jnp.float32)
    got = la.linear_attention(q, k, v, slopes, chunk=256)
    assert bool(jnp.all(jnp.isfinite(got)))
    close(got, quadratic(q, k, v, slopes), 2e-5)


@pytest.mark.parametrize("H,chunk", [(8, 128), (2, 256), (3, 128)],
                         ids=["4-heads-a-step", "2-heads", "3-heads"])
def test_linear_kernels_are_the_chunked_path(H, chunk):
    """The three kernels in Pallas's interpreter against `jax.numpy`
    over the same chunks, forward and gradients: the same products
    and casts, the state in VMEM instead of L / C states in HBM."""
    q, k, v = (activations(34 + i, 2, 512, H, 128) for i in range(3))
    slopes = la.decay_slopes(32, 5, H)
    weight = jnp.cos(jnp.arange(128, dtype=jnp.float32))

    def run(kernels):
        def f(q, k, v):
            return la.linear_attention(q, k, v, slopes, chunk=chunk,
                                       kernels=kernels, interpret=True)
        return f(q, k, v), jax.grad(
            lambda *a: jnp.sum(f(*a) * weight), (0, 1, 2))(q, k, v)
    (o, grads), (o_want, grads_want) = run(True), run(False)
    close(o, o_want, 1e-5)
    for a, b in zip(grads, grads_want):
        close(a, b, 1e-5)
    close(o, quadratic(q, k, v, slopes), 1e-4)


def test_the_linear_rule_keeps_the_cpu_on_the_chunks():
    q = jax.ShapeDtypeStruct((1, 32768, 16, 128), jnp.bfloat16)
    assert not la.kernels_engage(q, q, q)
    assert la.supported(q.shape, q.shape)
    assert not la.supported((1, 32768, 16, 64), (1, 32768, 16, 64))
    assert not la.supported((1, 32800, 16, 128), (1, 32800, 16, 128))
    assert not la.supported(q.shape, q.shape, chunk=192)


def test_linear_attention_refuses_grouped_keys():
    q = jnp.zeros((1, 8, 4, 16))
    with pytest.raises(ValueError, match="a slope a head"):
        la.linear_attention(q, q[:, :, :1], q[:, :, :1], jnp.ones((4,)))


# -- the selection ----------------------------------------------------------

def selection_by_hand(q, k, spec, scale):
    """The module docstring's equations, a query at a time: (L, nb)
    bool for one batch element and kv head. q: (L, G, D), k: (L, D)."""
    q, k = np.asarray(q, np.float64), np.asarray(k, np.float64)
    L = q.shape[0]
    nb = L // spec.block
    n_pooled = (L - spec.kernel_size) // spec.kernel_stride + 1
    pooled = np.stack([k[spec.kernel_stride * m:
                         spec.kernel_stride * m + spec.kernel_size].mean(0)
                       for m in range(n_pooled)])
    chosen = np.zeros((L, nb), bool)
    for t in range(L):
        visible = [m for m in range(n_pooled)
                   if spec.kernel_stride * m + spec.kernel_size - 1 <= t]
        r = np.zeros(n_pooled)
        if visible:
            s = q[t] @ pooled[visible].T * scale            # (G, visible)
            e = np.exp(s - s.max(-1, keepdims=True))
            r[visible] = (e / e.sum(-1, keepdims=True)).sum(0)
        own = t // spec.block
        R = np.full(nb, -np.inf)
        for b in range(own + 1):
            over = [m for m in range(n_pooled)
                    if spec.kernel_stride * m + spec.kernel_size - 1
                    >= spec.block * b
                    and spec.kernel_stride * m <= spec.block * b
                    + spec.block - 1]
            R[b] = max([r[m] for m in over], default=0.0)
            if b < spec.init_blocks or b > own - spec.window_blocks:
                R[b] = np.inf
        order = sorted(range(own + 1), key=lambda b: (-R[b], b))
        chosen[t, order[:spec.topk]] = True
    return chosen


@pytest.mark.parametrize("seed", [0, 1])
def test_selection_against_a_loop_written_out(seed):
    """Forced blocks, fewer blocks than top-k (the first 32 queries),
    one group of 4 q heads on each of 2 kv heads."""
    q = activations(40 + seed, 1, 128, 8, 16) * 3.0
    k = activations(50 + seed, 1, 128, 2, 16)
    got = np.asarray(sa.select_blocks(q, k, SPEC))
    assert got.shape == (1, 2, 128, 16)
    for n in range(2):
        want = selection_by_hand(q[0, :, 4 * n:4 * n + 4], k[0, :, n], SPEC,
                                 16 ** -0.5)
        np.testing.assert_array_equal(got[0, n], want)
    kept = got.sum(-1)[0, 0]
    np.testing.assert_array_equal(
        kept, [min(4, t // 8 + 1) for t in range(128)])
    t = np.arange(128)
    assert got[0, :, t, t // 8].all() and got[0, :, :, 0].all()
    assert got[0, :, t[8:], t[8:] // 8 - 1].all()
    # beyond the forced three, the fourth differs between queries
    free = got[0, 0, 64:].copy()
    free[:, 0] = False
    for i, row in enumerate(free):
        row[(64 + i) // 8 - 1:(64 + i) // 8 + 1] = False
    assert free.sum() == 64 and len(set(map(tuple, free))) > 4


def test_a_tie_goes_to_the_lower_block():
    """Keys that are all alike pool alike: every free block scores the
    same and the lowest is kept."""
    q = activations(60, 1, 128, 4, 16)
    k = jnp.ones((1, 128, 1, 16))
    got = np.asarray(sa.select_blocks(q, k, SPEC))[0, 0]
    np.testing.assert_array_equal(
        got, selection_by_hand(q[0], k[0, :, 0], SPEC, 0.25))
    late = got[127]
    assert list(np.flatnonzero(late)) == [0, 1, 14, 15]


def test_selection_needs_whole_blocks():
    q = jnp.zeros((1, 100, 4, 16))
    with pytest.raises(ValueError, match="whole"):
        sa.select_blocks(q, q[:, :, :1], SPEC)


def test_the_dense_border(monkeypatch):
    """At `dense_len` the layer is causal attention and selects
    nothing; one block longer it selects."""
    calls = []
    real = sa.select_blocks
    monkeypatch.setattr(sa, "select_blocks",
                        lambda *a: calls.append(a[0].shape) or real(*a))
    for L in (32, 40):
        q, v = activations(61, 1, L, 4, 16), activations(62, 1, L, 1, 16)
        k = activations(63, 1, L, 1, 16)
        got = sa.sparse_attention(q, k, v, SPEC)
        if L == 32:
            from horovod_tpu.parallel import dense_attention
            close(got, dense_attention(q, k, v, causal=True))
    assert calls == [(1, 40, 4, 16)]


def test_no_gradient_reaches_the_selection():
    """The gradient of the layer is the gradient with the selection
    held: the selection is indices. Were the pooled scores
    differentiated, k's gradient would differ."""
    q, k, v = (activations(64 + i, 1, 128, 4 if i == 0 else 1, 16)
               for i in range(3))
    weight = jnp.sin(jnp.arange(16.0))
    chosen = sa.select_blocks(q, k, SPEC)

    def through(q, k, v):
        return jnp.sum(sa.sparse_attention(q, k, v, SPEC) * weight)

    def held(q, k, v):
        return jnp.sum(sa.selected_attention(q, k, v, chosen, SPEC)
                       * weight)
    for a, b in zip(jax.grad(through, (0, 1, 2))(q, k, v),
                    jax.grad(held, (0, 1, 2))(q, k, v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.asarray(jax.grad(
        lambda q: jnp.sum(sa.select_blocks(q, k, SPEC)
                          .astype(jnp.float32)))(q)).any()


# -- the kernels' walk ------------------------------------------------------

def clustered_selection(B, Hkv, L, spec, blk, seed):
    """Own block and block 0 for every query, and for about half the
    (query block, earlier kernel block) pairs one selection block kept
    by 40 of the block's queries: kernel blocks nobody chose, and
    queries that chose nothing of a visited one."""
    nb, per = L // spec.block, blk // spec.block
    t = np.arange(L)
    chosen = np.zeros((B, Hkv, L, nb), bool)
    chosen[:, :, t, t // spec.block] = True
    chosen[..., 0] = True
    rng = np.random.RandomState(seed)
    for b in range(B):
        for h in range(Hkv):
            for qb in range(L // blk):
                for kb in range(1, qb):
                    if rng.rand() < 0.5:
                        rows = qb * blk + rng.choice(blk, 40, replace=False)
                        chosen[b, h, rows, kb * per + rng.randint(per)] = True
    return jnp.asarray(chosen)


def backward_traces():
    return {kernels: n for (kernels,), n in snapshot().get(
        "hvd_attention_backward_traces_total", {}).items()
        if kernels.startswith("sparse_")}


def traced_since(before):
    after = backward_traces()
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


@pytest.mark.parametrize("backward", ["one", "two"])
@pytest.mark.parametrize("block,blk", [(4, 128), (8, 256)],
                         ids=["32-bits-of-4", "32-bits-of-8"])
def test_kernels_walk_the_table_and_mask_by_token(monkeypatch, block, blk,
                                                  backward):
    """The kernels in Pallas's interpreter against the masked softmax,
    forward and gradients, where the walk skips blocks: the backward as
    one kernel over the forward's walk (the rule's choice at these
    shapes) and as the two kernels dQ and dK/dV, forced by a cap that
    no kv group fits."""
    if backward == "two":
        monkeypatch.setattr(fa, "RESIDENT_KV_CAP", 0)
    spec = dataclasses.replace(SPEC, block=block, window=2 * block,
                               kernel_size=4, kernel_stride=2)
    B, L, H, Hkv, D = 2, 4 * blk, 8, 2, 128
    assert sa.kernel_block(L, spec) == blk
    q, k, v = (activations(70 + i, B, L, H if i == 0 else Hkv, D)
               for i in range(3))
    chosen = clustered_selection(B, Hkv, L, spec, blk, 0)
    _, (table, count), (table_t, count_t) = sa.block_tables(chosen, blk,
                                                            spec)
    count = np.asarray(count).reshape(B, Hkv, 4)
    assert (count[:, :, 0] == 1).all() and count.min() == 1 \
        and (count[:, :, 3] < 4).any() and count.max() >= 3
    visited = np.asarray(chosen).reshape(B, Hkv, 4, blk, 4, -1).any((3, 5))
    np.testing.assert_array_equal(count, visited.sum(-1))
    np.testing.assert_array_equal(
        np.asarray(count_t).reshape(B, Hkv, 4), visited.sum(-2))
    rows = np.asarray(table).reshape(B, Hkv, 4, 4)
    for idx in np.ndindex(B, Hkv, 4):
        assert list(rows[idx][:count[idx]]) == list(
            np.flatnonzero(visited[idx]))
    weight = jnp.cos(jnp.arange(D, dtype=jnp.float32))

    def run(kernels):
        def f(q, k, v):
            return sa.selected_attention(q, k, v, chosen, spec,
                                         kernels=kernels, interpret=True)
        return f(q, k, v), jax.grad(
            lambda *a: jnp.sum(f(*a) * weight), (0, 1, 2))(q, k, v)
    before = backward_traces()
    (o, grads), (o_want, grads_want) = run(True), run(False)
    assert traced_since(before) == {f"sparse_{backward}": 1.0}
    close(o, o_want, 2e-5)
    for a, b in zip(grads, grads_want):
        close(a, b, 5e-5)


@pytest.mark.parametrize("L,one", [(32768, True), (65536, False)],
                         ids=["cell", "past-the-cap"])
def test_the_backward_is_one_kernel_where_a_kv_group_fits(L, one):
    """The rule is `fused_attention.one_kernel_backward` on the call's
    shapes: one kv head's f32 dK and dV over 32,768 positions of 128
    are 32 MiB, `RESIDENT_KV_CAP`, and take one kernel over the
    forward's walk, with no transposed table built; at 65,536 the
    backward is dQ and dK/dV over the transposed table. What a trace of
    the gradient at the cell's share holds, and what the counter
    says."""
    q = jax.ShapeDtypeStruct((1, L, 16, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, L, 1, 128), jnp.bfloat16)
    chosen = jax.ShapeDtypeStruct((1, 1, L, L // 64), jnp.bool_)
    spec = sa.SparseSpec()
    assert fa.one_kernel_backward(q.shape, k.shape, k.shape) is one
    assert (L * (128 + 128) * 4 == fa.RESIDENT_KV_CAP) is one

    def loss(q, k, v, chosen):
        return jnp.sum(sa.selected_attention(
            q, k, v, chosen, spec, kernels=True).astype(jnp.float32))
    before = backward_traces()
    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, k, chosen))
    assert traced_since(before) == {
        "sparse_one" if one else "sparse_two": 1.0}
    assert set(re.findall(r"hvd_sparse_attention_[a-z]+", text)) == (
        {"hvd_sparse_attention_fwd", "hvd_sparse_attention_bwd"} if one
        else {"hvd_sparse_attention_fwd", "hvd_sparse_attention_dq",
              "hvd_sparse_attention_dkv"})
    words, walk, walk_t = jax.eval_shape(
        lambda c: sa.block_tables(c, 512, spec, transposed=not one), chosen)
    assert (walk_t is None) is one and walk[0].shape == ((L // 512) ** 2,)


def test_kernels_on_a_real_selection_in_bf16():
    q, k, v = (activations(80 + i, 1, 512, 4 if i == 0 else 1, 128)
               .astype(jnp.bfloat16) for i in range(3))
    chosen = sa.select_blocks(q, k, SPEC)
    args = (q, k, v, chosen, SPEC)
    got = sa.selected_attention(*args, kernels=True, interpret=True)
    want = sa.selected_attention(*args, kernels=False)
    close(got.astype(jnp.float32), want.astype(jnp.float32), 2e-2)


def test_the_rule_keeps_the_cpu_on_the_masked_softmax():
    """Off the TPU nothing engages; on it bf16 calls whose shapes the
    kernels take do, others do not."""
    q = jax.ShapeDtypeStruct((1, 32768, 16, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 32768, 1, 128), jnp.bfloat16)
    spec = sa.SparseSpec()
    assert not sa.kernels_engage(q, k, k, spec)
    assert sa.supported(q.shape, k.shape, k.shape, spec)
    assert sa.kernel_block(32768, spec) == 512
    assert not sa.supported((1, 32768, 16, 128), (1, 32768, 16, 128),
                            (1, 32768, 16, 128), spec)     # a word a head
    assert not sa.supported((1, 32768, 16, 64), (1, 32768, 1, 64),
                            (1, 32768, 1, 64), spec)       # half a lane
    assert not sa.supported((1, 32800, 16, 128), (1, 32800, 1, 128),
                            (1, 32800, 1, 128), spec)      # no 128-block


# -- the layers against the reference ---------------------------------------

@pytest.mark.parametrize("L", [32, 128], ids=["dense-len", "selecting"])
def test_sparse_mixer(cfg, params, reference, L):
    layer = one_layer(params, "sparse")
    x = activations(10, 2, L, 64)
    close(sl.mixer_sum(cfg, layer, x, "sparse"),
          reference.sparse_sum(CONFIG, layer, x))


def test_the_sparse_mixer_is_not_the_dense_one(cfg, params, reference):
    layer = one_layer(params, "sparse")
    x = activations(10, 2, 128, 64)
    dense = {**CONFIG, "sparse_config": {**SPARSE_CONFIG, "dense_len": 128}}
    apart = jnp.abs(reference.sparse_sum(CONFIG, layer, x)
                    - reference.sparse_sum(dense, layer, x))
    assert float(jnp.max(apart)) > 1e-2
    assert float(jnp.max(apart[:, :32])) < 1e-6    # all blocks kept there


@pytest.mark.parametrize("L", [32, 100], ids=["short", "no-whole-chunk"])
def test_linear_mixer(cfg, params, reference, L):
    layer = one_layer(params, "linear")
    x = activations(11, 2, L, 64)
    close(sl.mixer_sum(cfg, layer, x, "linear"),
          reference.lightning_sum(CONFIG, layer, x))


@pytest.mark.parametrize("name,kind", [("sparse_sum", "sparse"),
                                       ("lightning_sum", "linear")])
def test_block(cfg, params, reference, name, kind):
    layer = one_layer(params, kind)
    x = activations(12, 2, 128, 64)
    close(sl.block(cfg, layer, x, kind),
          reference.layer(CONFIG, getattr(reference, name), layer, x))


def test_each_term_is_there(cfg, params):
    """The gate, the output norm, rope on the linear layers only, the
    decay: each moves its layer's output."""
    x = activations(13, 1, 128, 64)
    for kind, changes in (
            ("sparse", [{"wg": 0.0}]),
            ("linear", [{"wg": 0.0}, {"o_norm": 1.0}])):
        layer = one_layer(params, kind)
        base = sl.mixer_sum(cfg, layer, x, kind)
        for change in changes:
            other = {**layer, **{n: jnp.full_like(layer[n], c)
                                 for n, c in change.items()}}
            apart = jnp.abs(sl.mixer_sum(cfg, other, x, kind) - base)
            assert float(jnp.max(apart)) > 1e-2, change
    linear = one_layer(params, "linear")
    base = sl.mixer_sum(cfg, linear, x, "linear")
    for other in (dataclasses.replace(cfg, rope_theta=1e30),
                  dataclasses.replace(cfg, linear_first=4)):
        apart = jnp.abs(sl.mixer_sum(other, linear, x, "linear") - base)
        assert float(jnp.max(apart)) > 1e-2


# -- the share ---------------------------------------------------------------

@pytest.fixture(scope="module")
def whole():
    """One uncut layer of each kind."""
    uncut = jax.jit(lambda: _seeded(_library(UNCUT), 5))()
    return {kind: one_layer(uncut, kind) for kind in ("sparse", "linear")}


def _share(layer, q, kv, f):
    share = {**layer, "wq": layer["wq"][:, q], "wg": layer["wg"][:, q],
             "wo": layer["wo"][q], "wk": layer["wk"][:, kv],
             "wv": layer["wv"][:, kv], "w_gate": layer["w_gate"][:, f],
             "w_up": layer["w_up"][:, f], "w_down": layer["w_down"][f]}
    if "o_norm" in layer:
        share["o_norm"] = layer["o_norm"][q]
    return share


def _mean_square_of_the_linear_core(cfg, p, x):
    """(B, L) mean square of the held lightning heads' outputs: what
    the output norm divides by, and the one number a token the chips
    of a pair would exchange."""
    B, L, _ = x.shape
    u = sl.rmsnorm(x, p["attn_norm"], cfg.norm_eps)
    q, k = (sl._rope(sl.rmsnorm((u @ p[w]).reshape(B, L, -1, 16), p[n],
                                cfg.norm_eps), jnp.arange(L),
                     cfg.rope_theta)
            for w, n in (("wq", "q_norm"), ("wk", "k_norm")))
    o = la.linear_attention(q, k, (u @ p["wv"]).reshape(B, L, -1, 16),
                            la.decay_slopes(cfg.linear_heads_total,
                                            cfg.linear_first,
                                            q.shape[2]))
    return jnp.mean(jnp.square(o.reshape(B, L, -1)), axis=-1)


@pytest.mark.parametrize("kind,name", [("sparse", "sparse_sum"),
                                       ("linear", "lightning_sum")])
def test_the_two_shares_add_up_to_the_uncut_layer(
        cfg, reference, whole, kind, name):
    """model-configs guide, section 4: what the two chips of a
    tensor-parallel pair compute after W_o (a group of 4 q heads on
    its kv head, or 4 lightning heads with their own slopes) and after
    the down-projection (64 columns) adds up to the uncut layer's. The
    lightning layer's output norm is over all heads: a chip norms what
    it holds, and its part is the uncut layer's once it is rescaled by
    the one number a token the pair would exchange, the mean square of
    the other chip's heads."""
    layer = whole[kind]
    x = activations(14, 2, 128, 64)
    uncut = getattr(reference, name)(UNCUT, layer, x)
    uncut_ffn = reference.ffn_sum(UNCUT, layer, x)
    parts, squares, total_ffn = [], [], jnp.zeros_like(x)
    for chip in range(2):
        q = slice(64 * chip, 64 * chip + 64)
        kv = slice(16 * chip, 16 * chip + 16) if kind == "sparse" else q
        share = _share(layer, q, kv, q)
        held = dataclasses.replace(cfg, linear_first=4 * chip)
        parts.append(sl.mixer_sum(held, share, x, kind))
        close(parts[-1], getattr(reference, name)(
            {**CONFIG, "lightning_heads_first": 4 * chip}, share, x))
        if kind == "linear":
            squares.append(_mean_square_of_the_linear_core(held, share, x))
        total_ffn = total_ffn + sl.ffn_sum(cfg, share, x)
    if kind == "linear":
        whole_square = (squares[0] + squares[1]) / 2 + cfg.norm_eps
        parts = [part * jnp.sqrt((square + cfg.norm_eps)
                                 / whole_square)[..., None]
                 for part, square in zip(parts, squares)]
        # unexchanged, the halves' own norms are close, not equal
        assert 1e-3 < float(jnp.max(jnp.abs(
            squares[0] / squares[1] - 1))) < 3
    close(parts[0] + parts[1], uncut)
    close(total_ffn, uncut_ffn)
    assert float(jnp.max(jnp.abs(uncut - parts[1]))) > 1e-2


# -- the stack ---------------------------------------------------------------

@pytest.fixture(scope="module", params=[32, 128],
                ids=["seq32-dense", "seq128-selecting"])
def two_shards(request, params, reference):
    """(tokens (4, L), the reference's mean loss over two data shards
    of two, its gradients)."""
    L = request.param
    tokens = jax.random.randint(jax.random.PRNGKey(17), (4, L), 0, 128)

    def mean_loss(p):
        return jnp.mean(jnp.stack([
            reference.loss(CONFIG, p, {"tokens": tokens[i:i + 2]})
            for i in (0, 2)]))
    return (tokens, *jax.jit(jax.value_and_grad(mean_loss))(params))


def test_hidden_states_over_two_periods(cfg, params, reference):
    tokens = jax.random.randint(jax.random.PRNGKey(15), (2, 128), 0, 128)
    got = jax.jit(lambda p, t: sl.forward(cfg, p, t))(params, tokens)
    close(got, jax.jit(lambda p, t: reference.hidden_states(CONFIG, p, t))(
        params, tokens), 1e-4)


def test_the_reference_in_blocks_is_the_reference_whole(
        monkeypatch, params, reference, two_shards):
    """Blocks of queries and of tokens change no arithmetic."""
    tokens, want, _ = two_shards
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)
    monkeypatch.setattr(reference, "TOKEN_BLOCK", 16)
    got = jnp.mean(jnp.stack([
        reference.loss(CONFIG, params, {"tokens": tokens[i:i + 2]})
        for i in (0, 2)]))
    close(got, want, 1e-6)


def test_loss_and_gradients_through_build_train_step(cfg, params,
                                                     two_shards):
    """Two data shards through `build_train_step` with every layer
    checkpointed and the period scanned twice, as the cell runs it,
    and an optimizer that changes nothing and hands back the
    gradients; against the plain reference's mean over the shards."""
    conf = dataclasses.replace(cfg, remat=True)
    tokens, want, want_grads = two_shards
    mesh = Mesh(np.array(jax.devices()[:2]), axis_names=("data",))
    keep = optax.GradientTransformation(
        lambda p: jax.tree.map(jnp.zeros_like, p),
        lambda g, s, p=None: (jax.tree.map(jnp.zeros_like, g), g))
    step = build_train_step(
        lambda p, b: sl.loss_fn(conf, p, b), keep, mesh,
        batch_spec={"tokens": P("data")}, donate=False)
    _, grads, metrics = step(params, keep.init(params), {"tokens": tokens})

    close(metrics["loss"], want, 1e-5)
    flat, _ = jax.tree.flatten_with_path(grads)
    for (path, a), b in zip(flat, jax.tree.leaves(want_grads)):
        assert a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-6,
            err_msg=jax.tree_util.keystr(path))
    assert float(optax.global_norm(grads)) > 0.02
    # every layer of both periods got its own gradient
    for kind, n in (("sparse", 2), ("linear", 6)):
        per_layer = jnp.sqrt(jnp.sum(jnp.square(grads[kind]["wg"]),
                                     axis=(1, 2)))
        assert per_layer.shape == (n,) and float(jnp.min(per_layer)) > 1e-4


def test_the_layers_trace_their_paths_and_scopes(cfg, params):
    """One period is traced for the scan: a sparse layer that selects
    at 128 positions and hands 32 to `attention()`, three linear
    cores; the selection counts 4 blocks a query from shapes."""
    def read():
        snap = snapshot()
        paths = snap.get("hvd_attention_traces_total", {})
        return {
            **{p: paths.get((p,), 0.0)
               for p in ("sparse_blocks", "sparse_dense", "dense")},
            "chunks": snap.get("hvd_linear_attention_traces_total",
                               {}).get(("chunks",), 0.0),
            "selected": snap.get("hvd_attention_key_blocks_total",
                                 {}).get(("selected",), 0.0)}
    for L, want in ((128, {"sparse_blocks": 1.0, "chunks": 3.0,
                           "selected": 2.0 * sum(min(4, t // 8 + 1)
                                                 for t in range(128))}),
                    (32, {"sparse_dense": 1.0, "dense": 1.0,
                          "chunks": 3.0})):
        before = read()
        lowered = jax.jit(lambda p, t: sl.forward(cfg, p, t)).lower(
            params, jnp.zeros((2, L), jnp.int32))
        after = read()
        assert {p: after[p] - before[p] for p in after
                if after[p] != before[p]} == want
        text = lowered.as_text(debug_info=True)
        assert "hvd.attn.linear" in text and "hvd.attn.proj" in text
        assert ("hvd.attn.select" in text) == (L == 128)
        assert ("hvd.attn.sparse" in text) == (L == 128)
        assert ("hvd.attn.core" in text) == (L == 32)


def test_the_blocks_a_step_visits_are_a_device_value(cfg, params):
    """From shapes only the selected count is known; which kernel
    blocks a query block's queries chose between them is read off the
    selection the step computed."""
    layer = one_layer(params, "sparse")
    x = activations(16, 1, 512, 64)

    @jax.jit
    def visited(x):
        u = sl.rmsnorm(x, layer["attn_norm"], cfg.norm_eps)
        q = sl.rmsnorm((u @ layer["wq"]).reshape(1, 512, -1, 16),
                       layer["q_norm"], cfg.norm_eps)
        k = sl.rmsnorm((u @ layer["wk"]).reshape(1, 512, -1, 16),
                       layer["k_norm"], cfg.norm_eps)
        chosen = sa.select_blocks(q, k, cfg.sparse)
        _, (_, count), _ = sa.block_tables(chosen, 128, cfg.sparse)
        return count, jnp.sum(chosen)
    count, kept = visited(x)
    assert kept == sa.selected_blocks(512, cfg.sparse)
    assert count.shape == (4,) and int(count[0]) == 1
    assert all(1 <= int(c) <= i + 1 for i, c in enumerate(count))


def test_parameter_count_of_the_published_share():
    """630.2 M parameters: the share of ISSUE 37's arithmetic."""
    from perfbench import run
    config = run.read_json(os.path.join(
        REPO, "perfbench", "configs", "minicpm-sala-tp2vp8.json"))
    made = _perfbench("models").library_config(config)
    shapes = jax.eval_shape(lambda k: sl.init_params(made, k),
                            jax.random.PRNGKey(0))
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert round(count / 1e6, 1) == 630.2

    def of(kind):
        return sum(int(np.prod(s.shape)) for s in
                   jax.tree.leaves(shapes[kind])) / shapes[kind]["wq"].shape[0]
    assert round(of("sparse") / 1e6, 1) == 126.9
    assert round(of("linear") / 1e6, 1) == 142.6
    assert shapes["sparse"]["wq"].shape == (1, 4096, 2048)
    assert shapes["sparse"]["wk"].shape == (1, 4096, 128)
    assert shapes["linear"]["wk"].shape == (3, 4096, 2048)
    assert shapes["linear"]["w_gate"].shape == (3, 4096, 8192)
    assert shapes["linear"]["o_norm"].shape == (3, 2048)
    assert shapes["head"].shape == (4096, 9216)
    assert made.period_kinds == ("sparse", "linear", "linear", "linear")
    assert made.sparse == sa.SparseSpec()
