"""The selective scan of a Mamba-1 mixer (Gu & Dao, arXiv:2312.00752):
a diagonal state of N a channel whose decay the input chooses at every
position.

    h_t = exp(delta_t A) * h_{t-1} + (delta_t x_t) B_t      (C x N, float32)
    y_t = h_t C_t + D x_t,       then y_t * silu(z_t) where z is given

with x, delta, z (B, L, C), A (C, N), B and C (B, L, N), D (C,), h_{-1}
= 0. The recurrence is computed in chunks of `chunk` positions, so that
no (L x C x N) array exists: each chunk's entry state is kept for the
backward pass (L / chunk states of C x N a sequence), which rebuilds a
chunk's states from it and carries the state's cotangent from the last
chunk,

    a_t = g_t C_t + exp(delta_{t+1} A) * a_{t+1},     g = dL / dy_t
    d(delta_t x_t) = a_t B_t       dB_t = sum_c a_t (delta_t x_t)
    dC_t = sum_c g_t h_t           s_t = a_t * h_{t-1} * exp(delta_t A)
    d delta_t += s_t A             dA += s_t delta_t

The state, the decays and every sum are float32; x (and z) may be bf16
and are read as float32. The D skip and the gate are elementwise and
stay outside the scan, in XLA. Two paths under one function, picked by
what the call observes (`kernels_engage`: TPU, bf16 x, channels in
whole lanes, L in whole chunks; `hvd_selective_scan_traces_total{path}`):

  * `chunks`, everywhere else and the tests' oracle: `jax.numpy` under
    a `custom_vjp`, a `lax.scan` over chunks carrying the state, the
    positions of a chunk by an associative scan; the backward pass a
    reverse scan over chunks, each chunk's vjp from its entry state.
  * `kernel`: two Pallas kernels under a `custom_vjp`
    (`hvd_selective_scan_fwd`, `_bwd`) whose grid walks the chunks in
    order with the state in VMEM, a chunk's positions one at a time:
    the channels lie as (C / 128, 128) tiles, one tile stack a state
    index, and B_t, C_t are scalars read from SMEM. The forward reads
    x, delta once and writes y and the entry states; the backward walks
    the chunks from the last, rebuilds a chunk's states into VMEM from
    its entry state, and writes dx, d delta, and for dB and dC one
    partial sum a lane (summed after), dA accumulated in VMEM. Nothing
    per (position, channel, state) goes to HBM.
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
from .fused_attention import LANES, _params, _vma

_F32 = jnp.float32
# Positions of a chunk. The backward kernel holds a chunk's states in
# VMEM, (chunk + 1) x N x C f32: 6.5 MB at 32 positions and 2,560
# channels, which with the blocks stays under the compiler's default
# 16 MiB; the saved entry states are L / chunk x N x C f32 (84 MB a
# layer at 16,384 positions).
CHUNK = 32
# Channel rows (of 128) a grid step takes: all of them up to this many
# (2,560 channels are 20), else whole tiles of 8 that divide them.
ROWS_CAP = 24

_m_traces = _METRICS.counter(
    "hvd_selective_scan_traces_total",
    "Times selective_scan() was traced, by the path it took: kernel "
    "(the Pallas kernels that walk the chunks with the state in VMEM) "
    "or chunks (jax.numpy over chunks with a scanned state).", ("path",))


# ---------------------------------------------------------------------------
# The chunks path
# ---------------------------------------------------------------------------

def _in_chunks(a, T: int):
    """(B, L, ...) -> (N, B, T, ...), zeros after the last position: a
    zero delta keeps the state (exp(0) = 1) and adds nothing to it."""
    B, L = a.shape[:2]
    pad = -L % T
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    return jnp.moveaxis(a.reshape(B, (L + pad) // T, T, *a.shape[2:]), 1, 0)


def _whole(a, L: int):
    """(N, B, T, ...) -> (B, L, ...)."""
    a = jnp.moveaxis(a, 0, 1)
    return a.reshape(a.shape[0], -1, *a.shape[3:])[:, :L]


def _chunk(h0, x, delta, A, Bm, Cm):
    """One chunk: the state leaving it and its readout, float32.
    h0 (B, C, N); x, delta (B, T, C); Bm, Cm (B, T, N)."""
    delta = delta.astype(_F32)
    decay = jnp.exp(delta[..., None] * A)                   # (B, T, C, N)
    push = (delta * x.astype(_F32))[..., None] * Bm[:, :, None, :]

    def combine(first, then):
        (d1, u1), (d2, u2) = first, then
        return d1 * d2, d2 * u1 + u2
    held, h = lax.associative_scan(combine, (decay, push), axis=1)
    h = h + held * h0[:, None]
    return h[:, -1], jnp.einsum("btcn,btn->btc", h, Cm)


def _scan(x, delta, A, Bm, Cm, T: int):
    """(y (B, L, C) float32, entry states (N, B, C, N))."""
    L = x.shape[1]

    def step(h, chunk):
        h_out, y = _chunk(h, *chunk[:2], A, *chunk[2:])
        return h_out, (y, h)
    # zeros that vary over the mesh axes the inputs vary over
    h0 = jnp.zeros_like(delta[:, 0, :, None].astype(_F32) * Bm[:, :1])
    _, (y, states) = lax.scan(step, h0, tuple(
        _in_chunks(a, T) for a in (x, delta, Bm, Cm)))
    return _whole(y, L), states


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunks(x, delta, A, Bm, Cm, T):
    return _scan(x, delta, A, Bm, Cm, T)[0]


def _chunks_fwd(x, delta, A, Bm, Cm, T):
    y, states = _scan(x, delta, A, Bm, Cm, T)
    return y, (x, delta, A, Bm, Cm, states)


def _chunks_bwd(T, residuals, dy):
    """Chunks from the last: each chunk's vjp from its entry state,
    the cotangent of the state it hands on carried back."""
    x, delta, A, Bm, Cm, states = residuals
    L = x.shape[1]

    def step(carry, chunk):
        dh, dA = carry
        h0, xc, dc, bc, cc, dyc = chunk
        _, vjp = jax.vjp(_chunk, h0, xc, dc, A, bc, cc)
        dh0, dx, dd, da, db, dcc = vjp((dh, dyc))
        return (dh0, dA + da), (dx, dd, db, dcc)
    (_, dA), grads = lax.scan(
        step, (jnp.zeros_like(states[0]), jnp.zeros_like(states[0, 0])),
        (states, *(_in_chunks(a, T) for a in (x, delta, Bm, Cm, dy))),
        reverse=True)
    dx, dd, db, dc = (_whole(g, L) for g in grads)
    return (dx.astype(x.dtype), dd.astype(delta.dtype), dA,
            db.astype(Bm.dtype), dc.astype(Cm.dtype))


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------

def _block_rows(rows: int) -> int:
    if rows <= ROWS_CAP:
        return rows
    return max((r for r in range(8, ROWS_CAP + 1, 8) if rows % r == 0),
               default=rows)


def supported(x_shape, chunk: int = CHUNK) -> bool:
    """The shapes the kernels take: channels in whole lanes, the
    sequence in whole chunks."""
    _, L, C = x_shape
    return C % LANES == 0 and L % chunk == 0


def kernels_engage(x, chunk: int = CHUNK) -> bool:
    """The engagement rule, on what the call observes: TPU backend,
    bf16 x, shapes the kernels take."""
    return (jax.default_backend() == "tpu" and x.dtype == jnp.bfloat16
            and supported(x.shape, chunk))


def _advance(h_sc, t, delta, push, a_ref, b_ref, n_state):
    """h <- exp(delta A) h + push B_t for every state index; yields
    each new state."""
    for n in range(n_state):
        h = jnp.exp(delta * a_ref[n]) * h_sc[n] + push * b_ref[t * n_state + n]
        h_sc[n] = h
        yield n, h


def _fwd_kernel(x_ref, d_ref, a_ref, b_ref, c_ref, y_ref, s_ref, h_sc, *,
                chunk: int, n_state: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_sc[...] = jnp.zeros_like(h_sc)

    s_ref[...] = h_sc[...]

    def position(t, carry):
        delta = d_ref[t]
        push = delta * x_ref[t].astype(_F32)
        y = jnp.zeros_like(delta)
        for n, h in _advance(h_sc, t, delta, push, a_ref, b_ref, n_state):
            y = y + h * c_ref[t * n_state + n]
        y_ref[t] = y.astype(y_ref.dtype)
        return carry
    lax.fori_loop(0, chunk, position, 0)


def _bwd_kernel(x_ref, d_ref, a_ref, b_ref, c_ref, s_ref, dy_ref, dx_ref,
                dd_ref, da_ref, db_ref, dc_ref, h_sc, past_sc, adj_sc, *,
                chunk: int, n_state: int):
    """One chunk, from the last: its states rebuilt into `past_sc`
    (entry state first), then the positions walked back with the
    state's cotangent in `adj_sc`."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        adj_sc[...] = jnp.zeros_like(adj_sc)
        da_ref[...] = jnp.zeros_like(da_ref)

    h_sc[...] = s_ref[...]
    past_sc[0] = s_ref[...]

    def rebuild(t, carry):
        delta = d_ref[t]
        push = delta * x_ref[t].astype(_F32)
        for n, h in _advance(h_sc, t, delta, push, a_ref, b_ref, n_state):
            past_sc[t + 1, n] = h
        return carry
    lax.fori_loop(0, chunk, rebuild, 0)

    def rows(v):
        return jnp.sum(v, axis=0, keepdims=True)

    def back(i, carry):
        t = chunk - 1 - i
        delta = d_ref[t]
        x = x_ref[t].astype(_F32)
        push = delta * x
        g = dy_ref[t].astype(_F32)
        d_push = jnp.zeros_like(delta)
        d_delta = jnp.zeros_like(delta)
        for n in range(n_state):
            b = b_ref[t * n_state + n]
            decay = jnp.exp(delta * a_ref[n])
            adj = adj_sc[n] + g * c_ref[t * n_state + n]
            dc_ref[t, n:n + 1, :] = rows(g * past_sc[t + 1, n])
            db_ref[t, n:n + 1, :] = rows(adj * push)
            d_push = d_push + adj * b
            s = adj * past_sc[t, n] * decay
            d_delta = d_delta + s * a_ref[n]
            da_ref[n] = da_ref[n] + s * delta
            adj_sc[n] = adj * decay
        dx_ref[t] = (d_push * delta).astype(dx_ref.dtype)
        dd_ref[t] = (d_delta + d_push * x).astype(dd_ref.dtype)
        return carry
    lax.fori_loop(0, chunk, back, 0)


def _layout(x, delta, A, Bm, Cm, chunk):
    """The kernels' views: x, delta (B, L, R, 128); A (N, R, 128);
    B, C (B, L * N) for SMEM; the grid (B, channel blocks, chunks)."""
    Bt, L, C = x.shape
    N = A.shape[1]
    R = C // LANES
    rb = _block_rows(R)
    tiles = lambda a: a.reshape(*a.shape[:-1], R, LANES)
    return (tiles(x), tiles(delta), tiles(A.T),
            Bm.astype(_F32).reshape(Bt, L * N),
            Cm.astype(_F32).reshape(Bt, L * N), (Bt, R // rb, L // chunk),
            rb, N)


def _specs(rb, N, chunk, reverse: bool, n_chunks: int):
    def chunk_of(n):
        return n_chunks - 1 - n if reverse else n
    rows = pl.BlockSpec((None, chunk, rb, LANES),
                        lambda b, c, n: (b, chunk_of(n), c, 0))
    states = pl.BlockSpec((N, rb, LANES), lambda b, c, n: (0, c, 0))
    smem = pl.BlockSpec((None, chunk * N), lambda b, c, n: (b, chunk_of(n)),
                        memory_space=pltpu.SMEM)
    entry = pl.BlockSpec((None, None, N, rb, LANES),
                         lambda b, c, n: (b, chunk_of(n), 0, c, 0))
    return rows, states, smem, entry


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernels(x, delta, A, Bm, Cm, chunk, interpret):
    return _kernels_fwd(x, delta, A, Bm, Cm, chunk, interpret)[0]


def _kernels_fwd(x, delta, A, Bm, Cm, chunk, interpret):
    xt, dt, at, bt, ct, grid, rb, N = _layout(x, delta, A, Bm, Cm, chunk)
    rows, states, smem, entry = _specs(rb, N, chunk, False, grid[2])
    R = xt.shape[2]
    vma = _vma(x, delta, A, Bm, Cm)
    y, entries = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, n_state=N),
        grid=grid,
        in_specs=[rows, rows, states, smem, smem],
        out_specs=[rows, entry],
        out_shape=[jax.ShapeDtypeStruct(xt.shape, _F32, vma=vma),
                   jax.ShapeDtypeStruct((grid[0], grid[2], N, R, LANES),
                                        _F32, vma=vma)],
        scratch_shapes=[pltpu.VMEM((N, rb, LANES), _F32)],
        compiler_params=_params(2, 3),
        interpret=interpret,
        name="hvd_selective_scan_fwd",
    )(xt, dt, at, bt, ct)
    return y.reshape(x.shape), (x, delta, A, Bm, Cm, entries)


def _kernels_bwd(chunk, interpret, residuals, dy):
    x, delta, A, Bm, Cm, entries = residuals
    xt, dt, at, bt, ct, grid, rb, N = _layout(x, delta, A, Bm, Cm, chunk)
    rows, states, smem, entry = _specs(rb, N, chunk, True, grid[2])
    Bt, L, C = x.shape
    lanes = pl.BlockSpec((None, None, chunk, N, LANES),
                         lambda b, c, n: (b, c, grid[2] - 1 - n, 0, 0))
    acc = pl.BlockSpec((None, None, N, rb, LANES),
                       lambda b, c, n: (b, c, 0, 0, 0))
    vma = _vma(x, delta, A, Bm, Cm, dy)

    def out(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    dx, dd, da, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, n_state=N),
        grid=grid,
        in_specs=[rows, rows, states, smem, smem, entry, rows],
        out_specs=[rows, rows, acc, lanes, lanes],
        out_shape=[out(xt.shape, x.dtype), out(xt.shape, _F32),
                   out((Bt, grid[1], N, rb, LANES), _F32),
                   out((Bt, grid[1], L, N, LANES), _F32),
                   out((Bt, grid[1], L, N, LANES), _F32)],
        scratch_shapes=[pltpu.VMEM((N, rb, LANES), _F32),
                        pltpu.VMEM((chunk + 1, N, rb, LANES), _F32),
                        pltpu.VMEM((N, rb, LANES), _F32)],
        compiler_params=_params(2, 3),
        interpret=interpret,
        name="hvd_selective_scan_bwd",
    )(xt, dt, at, bt, ct, entries, dy.astype(_F32).reshape(xt.shape))
    # dA: (B, blocks, N, rb, 128) -> (C, N); dB, dC: a partial a lane
    da = jnp.moveaxis(da.sum(0), 0, 1).reshape(N, C).T
    return (dx.reshape(x.shape), dd.reshape(delta.shape).astype(delta.dtype),
            da, db.sum((1, 4)).astype(Bm.dtype),
            dc.sum((1, 4)).astype(Cm.dtype))


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def selective_scan(x: jax.Array, delta: jax.Array, A: jax.Array,
                   B: jax.Array, C: jax.Array, D: jax.Array,
                   z: Optional[jax.Array] = None, *,
                   kernels: Optional[bool] = None,
                   interpret: bool = False) -> jax.Array:
    """x, delta (B, L, C), A (C, N), B and C (B, L, N), D (C,), z like x
    or None -> y (B, L, C) in x's dtype: the recurrence of the module
    docstring from a zero state, plus D x, times silu(z) where z is
    given, in float32. A chunk is `CHUNK` positions, or the sequence
    where that is shorter; the `chunks` path takes any L. `kernels`
    forces a path (the tests; None: the rule), `interpret` runs the
    kernels in Pallas's interpreter."""
    Bt, L, Ch = x.shape
    N = A.shape[-1]
    if not (delta.shape == x.shape and A.shape == (Ch, N)
            and B.shape == C.shape == (Bt, L, N) and D.shape == (Ch,)
            and (z is None or z.shape == x.shape)):
        raise ValueError(
            f"selective scan takes x, delta (B, L, C), A (C, N), B and C "
            f"(B, L, N), D (C,) and z like x; got x {x.shape}, delta "
            f"{delta.shape}, A {A.shape}, B {B.shape}, C {C.shape}, "
            f"D {D.shape}, z {None if z is None else z.shape}")
    chunk = min(CHUNK, L)
    A = A.astype(_F32)
    if kernels is None:
        kernels = kernels_engage(x, chunk)
    _m_traces.labels(path="kernel" if kernels else "chunks").inc()
    missing = tuple(_vma(x, delta, B, C) - jax.typeof(A).vma)
    if missing:     # A's gradient is summed by the cast's transpose
        A = lax.pcast(A, missing, to="varying")
    scan = _kernels(x, delta, A, B, C, chunk, bool(interpret)) if kernels \
        else _chunks(x, delta, A, B, C, chunk)
    y = scan + D.astype(_F32) * x.astype(_F32)
    if z is not None:
        y = y * jax.nn.silu(z.astype(_F32))
    return y.astype(x.dtype)


def recurrent_selective_scan(x, delta, A, B, C, D, z=None):
    """The same function a position at a time, in float32: the state's
    own definition, for the tests."""
    f32 = [a.astype(_F32) for a in (x, delta, B, C)]
    A = A.astype(_F32)

    def step(h, inputs):
        x_t, d_t, b_t, c_t = inputs
        h = jnp.exp(d_t[..., None] * A) * h + (d_t * x_t)[..., None] \
            * b_t[:, None, :]
        return h, jnp.einsum("bcn,bn->bc", h, c_t)
    h0 = jnp.zeros((x.shape[0], x.shape[2], A.shape[1]), _F32)
    _, y = lax.scan(step, h0, tuple(jnp.moveaxis(a, 1, 0) for a in f32))
    y = jnp.moveaxis(y, 0, 1) + D * f32[0]
    return y if z is None else y * jax.nn.silu(z.astype(_F32))
