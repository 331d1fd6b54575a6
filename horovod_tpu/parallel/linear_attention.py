"""Linear attention with a fixed decay a head (Lightning Attention,
arXiv:2401.04658): a recurrent state instead of a softmax over keys.

    S_t = lam_h S_{t-1} + k_t^T v_t        (D x Dv a head, float32)
    o_t = scale * q_t S_t
        = scale * sum_{s <= t} lam_h^(t - s) (q_t . k_s) v_s,
    lam_h = exp(-slope_h)

computed in chunks of C positions, so that no (L x L) array and no
state a token exists: inside a chunk two small masked products, from
chunk to chunk the state,

    O_c     = scale * ((Q_c * a) S_c + ((Q_c K_c^T) * M) V_c)
    S_{c+1} = lam^C S_c + (K_c * b)^T V_c
    a_i = lam^(i+1),  M_ij = lam^(i-j) [i >= j],  b_j = lam^(C-1-j)

with only powers of lam at or under one and never a division by one,
so a fast head (lam^C underflows to 0) is as exact as a slow one. The
state and every decay power are float32; the products take their
operands in the dtype given (bf16 in training) and accumulate in
float32, like the model's other matmuls.

The backward pass carries the state's cotangent from the last chunk,

    dS_c = lam^C dS_{c+1} + (Q_c * a)^T dO_c * scale

and computes dQ, dK, dV of a chunk from S_c, dS_{c+1} and the chunk's
own masked products. Two paths under one function, picked by what the
call observes (`kernels_engage`: TPU, bf16, heads in whole lanes, L in
whole chunks; `hvd_linear_attention_traces_total{path}`):

  * `chunks`, everywhere else and the tests' oracle: `jax.numpy` under
    a `custom_vjp`, the chunk products batched over all chunks, the two
    carries `lax.scan`s over elementwise updates, each chunk's entry
    state kept for the backward pass (L / C states a head).
  * `kernel`: three Pallas kernels under a `custom_vjp`
    (`hvd_linear_attention_fwd`, `_dq`, `_dkv`) whose grid walks the
    chunks in order with the state in VMEM, so q, k, v (and dO) are
    read once a kernel and nothing but the result is written: the
    forward and dQ kernels carry S from the first chunk (dQ rebuilds
    it rather than reading 134 MB of saved states), dK/dV carries dS
    from the last. Same products, same casts as the other path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..metrics import REGISTRY as _METRICS
from .fused_attention import LANES, _head_cols, _params, _tile, _vma

_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_TN = (((0,), (0,)), ((), ()))          # a.T @ b
# Heads a grid step takes side by side: fewer, longer steps.
HEADS_CAP = 4
# Positions of a chunk: the masked (C x C) products cost L * C
# operations and bytes a head, the states L / C * D * Dv; 256 keeps
# both under the q / k / v traffic at heads of 128.
CHUNK = 256

_m_traces = _METRICS.counter(
    "hvd_linear_attention_traces_total",
    "Times linear_attention() was traced, by the path it took: kernel "
    "(the Pallas kernels that walk the chunks with the state in VMEM) "
    "or chunks (jax.numpy over chunks with a scanned state).", ("path",))


def decay_slopes(n_heads: int, first: int = 0,
                 held: Optional[int] = None) -> jax.Array:
    """slope_h = 2^(-8 (h + 1) / n_heads) of heads `first` ..
    `first + held` of a layer of `n_heads` (the ALiBi-shaped slopes of
    the Lightning Attention family); lam_h = exp(-slope_h)."""
    held = n_heads - first if held is None else held
    h = jnp.arange(first, first + held, dtype=_F32)
    return jnp.exp2(-8.0 * (h + 1.0) / n_heads)


def _decays(slopes, C: int):
    """(a (H, C), b (H, C), M (H, C, C), lam^C (H,)) in float32."""
    slopes = slopes.astype(_F32)[:, None]
    i = jnp.arange(C, dtype=_F32)
    a = jnp.exp(-slopes * (i + 1.0))
    b = jnp.exp(-slopes * (C - 1.0 - i))
    behind = i[:, None] - i[None, :]
    M = jnp.where(behind >= 0,
                  jnp.exp(-slopes[:, :, None] * jnp.maximum(behind, 0.0)),
                  0.0)
    return a, b, M, jnp.exp(-slopes[:, 0] * C)


def _chunked(x, C: int):
    """(B, L, H, D) -> (B, N, C, H, D), zero rows after the last
    position: a zero key adds nothing to the state, a zero query's
    output is cut off again."""
    B, L, H, D = x.shape
    pad = -L % C
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return x.reshape(B, (L + pad) // C, C, H, D)


def _scaled(x, row, dtype):
    """x (B, N, C, H, D) times a decay row (H, C), in float32, as an
    operand of `dtype`."""
    return (x.astype(_F32) * row.T[None, None, :, :, None]).astype(dtype)


def _carry(updates, lam_c, reverse: bool):
    """States at the entry of every chunk: s_0 = 0, s_{c+1} = lam^C
    s_c + updates_c (forward), or the same from the last chunk down
    (`reverse`: the cotangent's). updates: (B, N, H, D, Dv) float32."""
    decay = lam_c[None, :, None, None]

    def step(state, update):
        return decay * state + update, state
    _, states = lax.scan(step, jnp.zeros_like(updates[:, 0]),
                         jnp.moveaxis(updates, 1, 0), reverse=reverse)
    return jnp.moveaxis(states, 0, 1)


def _forward(q, k, v, slopes, scale: float, C: int):
    L, dtype = q.shape[1], q.dtype
    a, b, M, lam_c = _decays(slopes, C)
    qc, kc, vc = _chunked(q, C), _chunked(k, C), _chunked(v, C)
    states = _carry(jnp.einsum("bnchd,bnche->bnhde", _scaled(kc, b, dtype),
                               vc, preferred_element_type=_F32),
                    lam_c, reverse=False)
    inter = jnp.einsum("bnchd,bnhde->bnche", _scaled(qc, a, dtype),
                       states.astype(dtype), preferred_element_type=_F32)
    scores = jnp.einsum("bnchd,bnkhd->bnhck", qc, kc,
                        preferred_element_type=_F32) * M[None, None]
    intra = jnp.einsum("bnhck,bnkhe->bnche", scores.astype(dtype), vc,
                       preferred_element_type=_F32)
    o = ((inter + intra) * scale).astype(dtype)
    return o.reshape(o.shape[0], -1, *o.shape[3:])[:, :L], states


def _backward(q, k, v, slopes, states, do, scale: float, C: int):
    L, dtype = q.shape[1], q.dtype
    a, b, M, lam_c = _decays(slopes, C)
    qc, kc, vc = _chunked(q, C), _chunked(k, C), _chunked(v, C)
    doc = _chunked((do.astype(_F32) * scale).astype(dtype), C)
    qa, kb = _scaled(qc, a, dtype), _scaled(kc, b, dtype)
    # the cotangent of the state a chunk hands on: what the chunks
    # after it read of it
    d_states = _carry(jnp.einsum("bnchd,bnche->bnhde", qa, doc,
                                 preferred_element_type=_F32),
                      lam_c, reverse=True).astype(dtype)
    scores = (jnp.einsum("bnchd,bnkhd->bnhck", qc, kc,
                         preferred_element_type=_F32)
              * M[None, None]).astype(dtype)
    d_scores = (jnp.einsum("bnche,bnkhe->bnhck", doc, vc,
                           preferred_element_type=_F32)
                * M[None, None]).astype(dtype)
    rows_a, rows_b = (r.T[None, None, :, :, None] for r in (a, b))
    dq = jnp.einsum("bnhck,bnkhd->bnchd", d_scores, kc,
                    preferred_element_type=_F32) \
        + jnp.einsum("bnche,bnhde->bnchd", doc, states.astype(dtype),
                     preferred_element_type=_F32) * rows_a
    dk = jnp.einsum("bnhck,bnchd->bnkhd", d_scores, qc,
                    preferred_element_type=_F32) \
        + jnp.einsum("bnche,bnhde->bnchd", vc, d_states,
                     preferred_element_type=_F32) * rows_b
    dv = jnp.einsum("bnhck,bnche->bnkhe", scores, doc,
                    preferred_element_type=_F32) \
        + jnp.einsum("bnchd,bnhde->bnche", kb, d_states,
                     preferred_element_type=_F32)

    def whole(x, like):
        return x.reshape(x.shape[0], -1, *x.shape[3:])[:, :L].astype(
            like.dtype)
    return whole(dq, q), whole(dk, k), whole(dv, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attention(q, k, v, slopes, scale, chunk):
    return _forward(q, k, v, slopes, scale, chunk)[0]


def _attention_fwd(q, k, v, slopes, scale, chunk):
    o, states = _forward(q, k, v, slopes, scale, chunk)
    return o, (q, k, v, slopes, states)


def _attention_bwd(scale, chunk, residuals, do):
    q, k, v, slopes, states = residuals
    return (*_backward(q, k, v, slopes, states, do, scale, chunk),
            jnp.zeros_like(slopes))


_attention.defvjp(_attention_fwd, _attention_bwd)


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------

def _step_heads(H: int) -> int:
    return max(n for n in range(1, min(H, HEADS_CAP) + 1) if H % n == 0)


def supported(q_shape, v_shape, chunk: int = CHUNK) -> bool:
    """The shapes the kernels take: both head widths in whole lanes,
    the sequence in whole chunks of whole lanes."""
    _, L, _, D = q_shape
    return (D % LANES == 0 and v_shape[-1] % LANES == 0
            and chunk % LANES == 0 and L % chunk == 0)


def kernels_engage(q, k, v, chunk: int = CHUNK) -> bool:
    """The engagement rule, on what the call observes: TPU backend,
    bf16 operands, shapes the kernels take."""
    return (jax.default_backend() == "tpu"
            and q.dtype == k.dtype == v.dtype == jnp.bfloat16
            and supported(q.shape, v.shape, chunk))


def _lanes(x):
    """A decay row (H, C) lane-replicated, (H, C, 128): times a (C, D)
    block it is a tile, not a lane broadcast."""
    return jnp.broadcast_to(x[..., None], (*x.shape, LANES))


def _advance(state, k, v, b_rows, lam, d, dv):
    """lam^C S + (K * b)^T V for one head: (d, dv) float32."""
    kb = (k.astype(_F32) * _tile(b_rows, d)).astype(k.dtype)
    return state * _tile(lam, dv) + lax.dot_general(
        kb, v, _TN, preferred_element_type=_F32)


def _fwd_kernel(k_ref, v_ref, q_ref, a_ref, b_ref, m_ref, lam_ref, o_ref,
                s_sc, *, scale: float, heads: int, d: int, dv: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_sc[...] = jnp.zeros_like(s_sc)

    for g, (qc, vc) in enumerate(zip(_head_cols(heads, d),
                                     _head_cols(heads, dv))):
        q, k, v = q_ref[:, qc], k_ref[:, qc], v_ref[:, vc]
        state = s_sc[g]
        qa = (q.astype(_F32) * _tile(a_ref[g], d)).astype(q.dtype)
        inter = jnp.dot(qa, state.astype(q.dtype),
                        preferred_element_type=_F32)
        scores = lax.dot_general(q, k, _NT,
                                 preferred_element_type=_F32) * m_ref[g]
        intra = jnp.dot(scores.astype(v.dtype), v,
                        preferred_element_type=_F32)
        o_ref[:, vc] = ((inter + intra) * scale).astype(o_ref.dtype)
        s_sc[g] = _advance(state, k, v, b_ref[g], lam_ref[g, :1], d, dv)


def _dq_kernel(k_ref, v_ref, do_ref, a_ref, b_ref, m_ref, lam_ref, dq_ref,
               s_sc, *, scale: float, heads: int, d: int, dv: int):
    """Chunks in order, the state rebuilt on the way; q is not read."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_sc[...] = jnp.zeros_like(s_sc)

    for g, (qc, vc) in enumerate(zip(_head_cols(heads, d),
                                     _head_cols(heads, dv))):
        k, v = k_ref[:, qc], v_ref[:, vc]
        do = (do_ref[:, vc].astype(_F32) * scale).astype(v.dtype)
        state = s_sc[g]
        d_scores = lax.dot_general(do, v, _NT,
                                   preferred_element_type=_F32) * m_ref[g]
        dq = jnp.dot(d_scores.astype(k.dtype), k,
                     preferred_element_type=_F32) \
            + lax.dot_general(do, state.astype(do.dtype), _NT,
                              preferred_element_type=_F32) \
            * _tile(a_ref[g], d)
        dq_ref[:, qc] = dq.astype(dq_ref.dtype)
        s_sc[g] = _advance(state, k, v, b_ref[g], lam_ref[g, :1], d, dv)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, a_ref, b_ref, m_ref, lam_ref,
                dk_ref, dv_ref, ds_sc, *, scale: float, heads: int, d: int,
                dv: int):
    """Chunks from the last down, the state's cotangent carried: what
    the chunks after this one read of the state it hands on."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_sc[...] = jnp.zeros_like(ds_sc)

    for g, (qc, vc) in enumerate(zip(_head_cols(heads, d),
                                     _head_cols(heads, dv))):
        q, k, v = q_ref[:, qc], k_ref[:, qc], v_ref[:, vc]
        do = (do_ref[:, vc].astype(_F32) * scale).astype(v.dtype)
        d_state = ds_sc[g]
        handed = d_state.astype(q.dtype)
        scores = (lax.dot_general(q, k, _NT, preferred_element_type=_F32)
                  * m_ref[g]).astype(q.dtype)
        d_scores = (lax.dot_general(do, v, _NT, preferred_element_type=_F32)
                    * m_ref[g]).astype(q.dtype)
        kb = (k.astype(_F32) * _tile(b_ref[g], d)).astype(k.dtype)
        dv_ref[:, vc] = (
            lax.dot_general(scores, do, _TN, preferred_element_type=_F32)
            + jnp.dot(kb, handed, preferred_element_type=_F32)
        ).astype(dv_ref.dtype)
        dk_ref[:, qc] = (
            lax.dot_general(d_scores, q, _TN, preferred_element_type=_F32)
            + lax.dot_general(v, handed, _NT, preferred_element_type=_F32)
            * _tile(b_ref[g], d)).astype(dk_ref.dtype)
        qa = (q.astype(_F32) * _tile(a_ref[g], d)).astype(q.dtype)
        ds_sc[g] = d_state * _tile(lam_ref[g, :1], dv) + lax.dot_general(
            qa, do, _TN, preferred_element_type=_F32)


def _call(kernel, name, operands, widths, out_widths, slopes, scale, C,
          interpret, reverse=False):
    """One kernel over (B, head steps, chunks): `operands` are
    (B, L, H * width) arrays read a (C, heads * width) block a step, k
    first and v second, the outputs alike; the decays ride along a
    head step."""
    B, L, _ = operands[0].shape
    H = slopes.shape[0]
    hs = _step_heads(H)
    N = L // C
    d, dv = widths[0], widths[1]
    a, b, M, lam_c = _decays(slopes, C)
    lam = jnp.broadcast_to(lam_c[:, None, None], (H, 8, LANES))

    def chunk(n):
        return N - 1 - n if reverse else n

    def cols(width):
        return pl.BlockSpec((None, C, hs * width),
                            lambda b, h, n: (b, chunk(n), h))

    def heads_of(*shape):
        return pl.BlockSpec((hs, *shape), lambda b, h, n: (h,) + (0,) * len(
            shape))
    vma = _vma(*operands)
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, heads=hs, d=d, dv=dv),
        grid=(B, H // hs, N),
        in_specs=[*(cols(w) for w in widths), heads_of(C, LANES),
                  heads_of(C, LANES), heads_of(C, C), heads_of(8, LANES)],
        out_specs=[cols(w) for w in out_widths],
        out_shape=[jax.ShapeDtypeStruct((B, L, H * w), operands[0].dtype,
                                        vma=vma) for w in out_widths],
        scratch_shapes=[pltpu.VMEM((hs, d, dv), _F32)],
        compiler_params=_params(2, 3),
        interpret=interpret,
        name=name,
    )(*operands, _lanes(a), _lanes(b), M, lam)


def _flat(x):
    return x.reshape(*x.shape[:2], -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _kernel_attention(q, k, v, slopes, scale, chunk, interpret):
    D, Dv = q.shape[-1], v.shape[-1]
    o, = _call(_fwd_kernel, "hvd_linear_attention_fwd",
               (_flat(k), _flat(v), _flat(q)), (D, Dv, D), (Dv,), slopes,
               scale, chunk, interpret)
    return o.reshape(v.shape)


def _kernel_attention_fwd(q, k, v, slopes, scale, chunk, interpret):
    return (_kernel_attention(q, k, v, slopes, scale, chunk, interpret),
            (q, k, v, slopes))


def _kernel_attention_bwd(scale, chunk, interpret, residuals, do):
    q, k, v, slopes = residuals
    D, Dv = q.shape[-1], v.shape[-1]
    k2, v2, do2 = _flat(k), _flat(v), _flat(do.astype(v.dtype))
    dq, = _call(_dq_kernel, "hvd_linear_attention_dq", (k2, v2, do2),
                (D, Dv, Dv), (D,), slopes, scale, chunk, interpret)
    dk, dv = _call(_dkv_kernel, "hvd_linear_attention_dkv",
                   (k2, v2, _flat(q), do2), (D, Dv, D, Dv), (D, Dv), slopes,
                   scale, chunk, interpret, reverse=True)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            jnp.zeros_like(slopes))


_kernel_attention.defvjp(_kernel_attention_fwd, _kernel_attention_bwd)


def linear_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     slopes: jax.Array, chunk: Optional[int] = None, *,
                     kernels: Optional[bool] = None,
                     interpret: bool = False) -> jax.Array:
    """q, k (B, L, H, D), v (B, L, H, Dv), slopes (H,) -> (B, L, H, Dv):
    o_t = D^-0.5 sum_{s <= t} exp(-slope_h (t - s)) (q_t . k_s) v_s.
    Every head has its own k and v. The slopes are constants of the
    architecture: no gradient reaches them. `chunk` (positions a
    chunk) defaults to `CHUNK` or the sequence where that is shorter;
    the `chunks` path takes any L. `kernels` forces a path (the tests;
    None: the rule), `interpret` runs the kernels in Pallas's
    interpreter."""
    if not (q.ndim == 4 and q.shape == k.shape
            and v.shape[:3] == q.shape[:3] and slopes.shape == q.shape[2:3]):
        raise ValueError(
            f"linear attention takes q, k (B, L, H, D), v (B, L, H, Dv) "
            f"and a slope a head; got q {q.shape}, k {k.shape}, "
            f"v {v.shape}, slopes {slopes.shape}")
    chunk = min(CHUNK, q.shape[1]) if chunk is None else int(chunk)
    scale = float(q.shape[-1] ** -0.5)
    slopes = lax.stop_gradient(slopes)
    if kernels is None:
        kernels = kernels_engage(q, k, v, chunk)
    _m_traces.labels(path="kernel" if kernels else "chunks").inc()
    if kernels:
        return _kernel_attention(q, k, v, slopes, scale, chunk,
                                 bool(interpret))
    return _attention(q, k, v, slopes, scale, chunk)


def recurrent_linear_attention(q, k, v, slopes):
    """The same function a position at a time, in float32: the state's
    own definition, for the tests."""
    scale = q.shape[-1] ** -0.5
    lam = jnp.exp(-slopes.astype(_F32))[None, :, None, None]

    def step(state, qkv):
        q_t, k_t, v_t = (x.astype(_F32) for x in qkv)
        state = lam * state + jnp.einsum("bhd,bhe->bhde", k_t, v_t)
        return state, jnp.einsum("bhd,bhde->bhe", q_t, state) * scale
    B, _, H, D = q.shape
    _, o = lax.scan(step, jnp.zeros((B, H, D, v.shape[-1]), _F32),
                    tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v)))
    return jnp.moveaxis(o, 0, 1)
