"""Mamba-2 (SSD, arXiv 2405.21060): a state-space recurrence with ONE
scalar decay a head, as two kernels over one float32 state a head.

    S_t = a_t S_{t-1} + dt_t x_t (outer) B_t,   a_t = exp(A dt_t)
    y_t = S_t C_t

``S`` is ``(P, N)`` a head: ``x_t`` has P channels, ``B_t`` and ``C_t``
N state coordinates shared by the heads of a group; ``A < 0`` a head,
``dt_t > 0`` a head and token.  (The skip ``D x_t`` is the layer's.)

THE STATE'S LAYOUT.  P is 64: half a lane row.  The pool therefore
keeps two neighbouring heads side by side and transposed: ``(H / 2, N,
2 P)`` with ``[k, n, i * P + p] = S[2 k + i, p, n]`` (`pair_state` /
`unpair_state`).  A head's channels then lie along lanes, as ``x`` and
``y`` do in the layer's own ``(.., H * P)`` rows, so neither kernel
transposes them; sums over the state coordinate run down sublanes.
Two heads of a pair share their group's ``B`` and ``C``.

`mamba2_decode_step` is the recurrence for one token a batch row: it
reads and writes each live row's state once, in place, and touches no
row that is not live.  `mamba2_prefill_chunk` is the chunked form
(chunks of ``CHUNK`` tokens, the model's ``chunk_size``), from a zero
state or from the state a predecessor left (a long prompt prefilled in
pieces): with ``G_t`` the log-decay summed from the chunk's start
through ``t``,

    Y = (tril(C B^T * exp(G_t - G_s)) (dt X)) + exp(G) * (C S_0)
    S_C = exp(G_C) S_0 + B^T (dt X * exp(G_C - G))

all on the MXU in float32, the state carried from chunk to chunk in
VMEM.  Every exponent is <= 0: nothing overflows however fast a head
forgets.  ``C B^T`` is formed once a group and chunk.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.utils.platform import (
    SCOPED_VMEM_LIMIT, default_interpret)

#: Tokens a chunk of the prefill kernel (`chunk_size` of the family).
CHUNK = 128
#: Lanes of a pair of heads.
_LANES = 128


def pair_state(state):
    """(B, H, P, N) a head -> the pool's (B, H / 2, N, 2 P)."""
    b, h, p, n = state.shape
    return state.reshape(b, h // 2, 2, p, n).transpose(
        0, 1, 4, 2, 3).reshape(b, h // 2, n, 2 * p)


def unpair_state(state):
    """The pool's (B, H / 2, N, 2 P) -> (B, H, P, N) a head."""
    b, k, n, pp = state.shape
    return state.reshape(b, k, n, 2, pp // 2).transpose(
        0, 1, 3, 4, 2).reshape(b, 2 * k, pp // 2, n)


def mamba2_recurrent_reference(x, dt, a, b, c, state=None):
    """The recurrence itself, float32, a token at a time (tests, and
    the layer's "xla" mode).

    x: (B, T, H, P); dt: (B, T, H) — 0 where a token is to leave the
    state as it was; a: (H,) < 0; b, c: (B, T, G, N), a group serving
    H / G heads in order; ``state``: (B, H, P, N) or None for zeros.
    Returns (y (B, T, H, P), state)."""
    f32 = jnp.float32
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    bsz, _, h, p = x.shape
    rep = h // b.shape[2]
    if state is None:
        state = jnp.zeros((bsz, h, p, b.shape[-1]), f32)

    def step(s, v):
        x_t, dt_t, b_t, c_t = v
        b_t, c_t = (jnp.repeat(u, rep, axis=1) for u in (b_t, c_t))
        s = (s * jnp.exp(dt_t * a.astype(f32))[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t,
                             precision="highest")

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c))
    state, y = jax.lax.scan(step, state.astype(f32), xs)
    return jnp.moveaxis(y, 0, 1), state


def _check(h, p, g, n):
    assert 2 * p == _LANES and n == _LANES, (
        "a pair of heads fills a lane row and the state is square", p, n)
    assert h % g == 0 and (h // g) % 2 == 0, (
        "a pair of heads lies inside one group", h, g)


# ---------------------------------------------------------------------------
# decode: one token a row
# ---------------------------------------------------------------------------

def _decode_kernel(groups, idx_ref, n_ref, x_ref, a_ref, bc_ref, s_ref,
                   o_ref, so_ref):
    """Grid (B,).  Step ``i`` works on row ``idx[i]`` while ``i < n``
    (the live rows, in order); every later step maps to the last live
    row and does nothing, so nothing is fetched or written for it.
    x (dt x), a (the decay, repeated over a head's lanes): (1, H / 2,
    128), a row a pair; bc: (1, >= 2 G, N) — the groups' B, then their
    C; the state (1, H / 2, N, 128)."""
    i = pl.program_id(0)
    n = n_ref[0]
    pairs, ns, lanes = s_ref.shape[1:]

    @pl.when(i < n)
    def _():
        # B and C scale or contract the state's ROWS: turn the 2 G
        # vectors into columns with one transpose
        bc = bc_ref[0]
        cols = jnp.concatenate(
            [bc, jnp.zeros((ns - bc.shape[0], ns), jnp.float32)],
            axis=0).T
        per = pairs // groups
        for g in range(groups):
            bb = jnp.broadcast_to(cols[:, g:g + 1], (ns, lanes))
            cc = jnp.broadcast_to(cols[:, groups + g:groups + g + 1],
                                  (ns, lanes))
            for k in range(g * per, (g + 1) * per):
                s = (s_ref[0, k] * a_ref[0, k:k + 1, :]
                     + bb * x_ref[0, k:k + 1, :])
                so_ref[0, k] = s
                o_ref[0, k:k + 1, :] = jnp.sum(s * cc, axis=0,
                                               keepdims=True)

    @pl.when(n == 0)
    def _():
        # no live row at all: the one block every step maps to is
        # written back as it was read
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def mamba2_decode_step(x, dt, a, b, c, state, live, *,
                       interpret: Optional[bool] = None):
    """One state-space step a batch row.

    x: (B, H * P); dt: (B, H) float32 > 0; a: (H,) < 0; b, c: (B, G *
    N); ``state``: (B, H / 2, N, 2 P) float32 (`pair_state`), updated
    IN PLACE (aliased to the second result: donate it); ``live``: (B,)
    bool.  Rows that are not live are neither read nor written; their
    output is zero.  Returns (y (B, H * P) float32, state)."""
    f32 = jnp.float32
    bsz, h = dt.shape
    pairs, n, lanes = state.shape[1:]
    p = lanes // 2
    g = b.shape[1] // n
    _check(h, p, g, n)
    assert state.shape == (bsz, h // 2, n, 2 * p), (state.shape, h, p)
    assert state.dtype == f32 and x.shape == (bsz, h * p)
    dt = dt.astype(f32)
    xdt = (x.astype(f32).reshape(bsz, h, p) * dt[..., None]).reshape(
        bsz, pairs, lanes)
    decay = jnp.repeat(jnp.exp(dt * a.astype(f32)), p, axis=1).reshape(
        bsz, pairs, lanes)
    rows = -(-2 * g // 8) * 8
    bc = jnp.concatenate(
        [b.astype(f32).reshape(bsz, g, n), c.astype(f32).reshape(bsz, g, n),
         jnp.zeros((bsz, rows - 2 * g, n), f32)], axis=1)

    count = jnp.sum(live).astype(jnp.int32)
    order = jnp.argsort(jnp.logical_not(live), stable=True)
    idx = order[jnp.minimum(jnp.arange(bsz), jnp.maximum(count - 1, 0))]

    def row(i, idx_ref, n_ref):
        return idx_ref[i]

    vec = pl.BlockSpec((1, pairs, lanes),
                       lambda i, *pre: (row(i, *pre), 0, 0))
    bc_spec = pl.BlockSpec((1, rows, n),
                           lambda i, *pre: (row(i, *pre), 0, 0))
    st = pl.BlockSpec((1, pairs, n, lanes),
                      lambda i, *pre: (row(i, *pre), 0, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_decode_kernel, g),
        name="mamba2_decode_step",
        out_shape=(jax.ShapeDtypeStruct((bsz, pairs, lanes), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz,),
            in_specs=[vec, vec, bc_spec, st],
            out_specs=(vec, st),
        ),
        # operands: idx, n, x dt, decay, bc, state
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=SCOPED_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=6 * bsz * h * p * n,
            bytes_accessed=2 * bsz * h * p * n * 4,
            transcendentals=0),
        interpret=default_interpret(interpret),
    )(idx.astype(jnp.int32), count.reshape(1), xdt, decay, bc, state)
    return (jnp.where(live[:, None], y.reshape(bsz, h * p), 0.0), state)


# ---------------------------------------------------------------------------
# prefill: chunks of CHUNK tokens
# ---------------------------------------------------------------------------

def _mm(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               precision="highest",
                               preferred_element_type=jnp.float32)


#: (i, k) x (k, j), (i, k) x (j, k), (c, i) x (c, j)
_NN = ((1,), (0,))
_NT = ((1,), (1,))
_TN = ((0,), (0,))


def _prefill_kernel(x_ref, b_ref, c_ref, dt_ref, gc_ref, gr_ref, *rest):
    """Grid (B, G, T / CHUNK), the chunks in order.  One group a step:
    x, y (1, CHUNK, pairs * 128); b, c (1, CHUNK, N); dt and gc — G,
    the log-decay summed from the chunk's start — (1, CHUNK, 128), a
    lane a head of the group; gr the same G, a ROW a head (1, >= heads,
    CHUNK).  ``rest``: [s0_ref (1, pairs, N, 128), where the caller
    carries a state in,] y_ref, so_ref, s_scr — the group's pairs'
    states, which start from ``s0_ref``, else from zeros."""
    *s0_ref, y_ref, so_ref, s_scr = rest
    ci = pl.program_id(2)
    f32 = jnp.float32

    @pl.when(ci == 0)
    def _():
        s_scr[...] = s0_ref[0][0] if s0_ref else jnp.zeros_like(s_scr)

    bm, cm = b_ref[0].astype(f32), c_ref[0].astype(f32)
    dt, gc, gr = dt_ref[0], gc_ref[0], gr_ref[0]
    cs = bm.shape[0]
    pairs, _, lanes = s_scr.shape
    cb = _mm(cm, bm, _NT)                               # (t, s)
    seen = (jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 1))
    first = jax.lax.broadcasted_iota(jnp.int32, (cs, lanes),
                                     1) < lanes // 2
    for k in range(pairs):
        h0, h1 = 2 * k, 2 * k + 1
        both = lambda v: jnp.where(       # noqa: E731
            first, v[:, h0:h0 + 1], v[:, h1:h1 + 1])
        g2 = both(gc)
        xdt = x_ref[0, :, k * lanes:(k + 1) * lanes].astype(f32) * both(dt)
        within = []
        for h in (h0, h1):
            decay = jnp.exp(jnp.minimum(gc[:, h:h + 1] - gr[h:h + 1, :],
                                        0.0))
            within.append(_mm(jnp.where(seen, cb * decay, 0.0), xdt,
                              _NN))
        s0 = s_scr[k]
        y_ref[0, :, k * lanes:(k + 1) * lanes] = (
            jnp.where(first, *within) + _mm(cm, s0, _NN) * jnp.exp(g2))
        last = g2[cs - 1:cs, :]
        s_new = jnp.exp(last) * s0 + _mm(bm, xdt * jnp.exp(last - g2),
                                         _TN)
        s_scr[k] = s_new

        @pl.when(ci == pl.num_programs(2) - 1)
        def _():
            so_ref[0, k] = s_new


def mamba2_prefill_chunk(x, dt, a, b, c, state=None, *,
                         interpret: Optional[bool] = None):
    """The recurrence over T tokens a sequence, from ``state`` — (B,
    H / 2, N, 2 P) float32 in the pool's layout: what the sequence's
    earlier tokens left — or, ``None``, from a zero state (a whole
    prompt; the program then has no such operand).

    x: (B, T, H * P); dt: (B, T, H) float32 — 0 where a token is to
    leave the state as it was (how a caller masks a padded tail); a:
    (H,) < 0; b, c: (B, T, G * N); T a multiple of `CHUNK`.  Returns (y
    (B, T, H * P) float32, state (B, H / 2, N, 2 P) float32 after the
    last token, in the pool's layout)."""
    f32 = jnp.float32
    bsz, t, h = dt.shape
    p = x.shape[-1] // h
    n = _LANES
    g = b.shape[-1] // n
    _check(h, p, g, n)
    assert t % CHUNK == 0, (t, CHUNK)
    per = h // g                    # heads a group
    pairs, lanes = per // 2, 2 * p
    nc = t // CHUNK
    dt = dt.astype(f32)
    gsum = jnp.cumsum((dt * a.astype(f32)).reshape(bsz, nc, CHUNK, h),
                      axis=2).reshape(bsz, t, g, per)
    # a lane a head of the group, a lane row a group
    lane_rows = lambda v: jnp.pad(       # noqa: E731
        v, ((0, 0), (0, 0), (0, 0), (0, lanes - per))).reshape(
            bsz, t, g * lanes)
    rows = -(-per // 8) * 8
    g_rows = jnp.pad(jnp.moveaxis(gsum, 1, 3),
                     ((0, 0), (0, 0), (0, rows - per), (0, 0))).reshape(
                         bsz, g * rows, t)

    def seq(width):
        return pl.BlockSpec((1, CHUNK, width), lambda i, j, ci: (i, ci, j))

    st = pl.BlockSpec((1, pairs, n, lanes), lambda i, j, ci: (i, j, 0, 0))
    carried = [] if state is None else [state]
    for s0 in carried:
        assert s0.shape == (bsz, h // 2, n, lanes), (s0.shape, h, p)
        assert s0.dtype == f32, s0.dtype
    macs = CHUNK * CHUNK * n + pairs * 4 * CHUNK * n * lanes
    return pl.pallas_call(
        _prefill_kernel,
        name="mamba2_prefill_chunk",
        out_shape=(jax.ShapeDtypeStruct((bsz, t, h * p), f32),
                   jax.ShapeDtypeStruct((bsz, h // 2, n, lanes), f32)),
        grid=(bsz, g, nc),
        in_specs=[seq(pairs * lanes), seq(n), seq(n), seq(lanes),
                  seq(lanes),
                  pl.BlockSpec((1, rows, CHUNK),
                               lambda i, j, ci: (i, j, ci))]
        + [st] * len(carried),
        out_specs=(seq(pairs * lanes), st),
        scratch_shapes=[pltpu.VMEM((pairs, n, lanes), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=SCOPED_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * bsz * g * nc * macs,
            bytes_accessed=bsz * t * (h * p * (x.dtype.itemsize + 4)
                                      + 2 * g * n * b.dtype.itemsize),
            transcendentals=bsz * t * h * (CHUNK + 2 * p)),
        interpret=default_interpret(interpret),
    )(x, b, c, lane_rows(dt.reshape(bsz, t, g, per)), lane_rows(gsum),
      g_rows, *carried)
