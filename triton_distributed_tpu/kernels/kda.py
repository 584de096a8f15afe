"""Kimi Delta Attention (KDA, arXiv 2510.26692): the gated delta rule
with a decay a CHANNEL, as two kernels over one float32 state a head.

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

``S`` is ``(dk, dv)``; ``a_t = exp(g_t)`` in (0, 1) a key channel,
``b_t`` the write strength, ``k_t`` of unit length.  Step by step:
``S' = Diag(a_t) S``; ``u = b_t (v_t - S'^T k_t)``; ``S_t = S' + k_t
u^T``.

`kda_decode_step` is that step for one token a batch row: it reads and
writes each live row's state once, in place, and touches no row that is
not live.  `kda_prefill_chunk` is the chunked form: within a chunk of
``CHUNK`` tokens the WY / UT-transform products run on the MXU and the
state is carried from chunk to chunk in VMEM — from zeros, or from the
state a predecessor left (``state=``: a long prompt prefilled in
pieces, each a multiple of ``CHUNK`` tokens; float32 in and out, so the
pieces compute what one call would).  With ``G_t`` the summed
log-decay from the chunk's start through ``t``, ``k+_t = k_t exp(G_t)``
and ``k-_s = k_s exp(-G_s)``:

    A_ts = b_t (k+_t . k-_s)  for s < t;   T = (I + A)^-1
    U = T (b V) - T (b K+) S_0
    O = Q+ S_0 + tril(Q+ K-^T) U
    S_C = Diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U

``exp(-G_s)`` overflows where a channel forgets fast, so every product
``exp(G_t - G_s)`` is formed inside sub-blocks of ``SUB`` tokens about
the decay at the sub-block's start (both factors then stay in range),
as the published kernels do.  ``T`` comes from the nilpotent products
``(I + N)^-1 = (I - N)(I + N^2)(I + N^4)...`` — exact in exact
arithmetic; matmuls only.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.utils.platform import default_interpret

#: Tokens a chunk of the prefill kernel, and a sub-block of it.
CHUNK = 64
SUB = 16
#: Largest exponent formed about a sub-block's start (float32 holds
#: e^88): a channel would have to forget 80 nats inside 16 tokens.
_EXP_CAP = 80.0
#: Heads a grid step.
_DECODE_HEADS = 8
_PREFILL_HEADS = 8


def kda_recurrent_reference(q, k, v, g, beta, state=None):
    """The recurrence itself, float32, a token at a time (tests).

    q, k, g: (B, H, T, dk); v: (B, H, T, dv); beta: (B, H, T);
    ``state``: (B, H, dk, dv) or None for zeros.  Returns (o (B, H, T,
    dv), state)."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    b, h, _, dk = q.shape
    if state is None:
        state = jnp.zeros((b, h, dk, v.shape[-1]), f32)

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", s, k_t, precision="highest"))
        s = s + k_t[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t,
                             precision="highest")

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta))
    state, o = jax.lax.scan(step, state.astype(f32), xs)
    return jnp.moveaxis(o, 0, 2), state


# ---------------------------------------------------------------------------
# decode: one token a row
# ---------------------------------------------------------------------------

def _decode_kernel(hb, idx_ref, n_ref, q_ref, k_ref, a_ref, v_ref,
                   b_ref, s_ref, o_ref, so_ref):
    """Grid (B, H / hb).  Step ``i`` works on row ``idx[i]`` while ``i <
    n`` (the live rows, in order); every later step maps to the last
    live block and does nothing, so nothing is fetched or written for
    it.  q, k, a, v, b: (1, hb, 128) rows; the state ``(1, hb, dk,
    dv)``."""
    i = pl.program_id(0)
    n = n_ref[0]
    dk = s_ref.shape[2]

    @pl.when(i < n)
    def _():
        # k, a and q scale or contract the state's ROWS: turn the
        # 3 * hb vectors into columns with one transpose
        rows = jnp.concatenate(
            [k_ref[0], a_ref[0], q_ref[0],
             jnp.zeros((dk - 3 * hb, dk), jnp.float32)], axis=0)
        cols = rows.T
        for j in range(hb):
            kc = cols[:, j:j + 1]
            ac = cols[:, hb + j:hb + j + 1]
            qc = cols[:, 2 * hb + j:2 * hb + j + 1]
            s = s_ref[0, j] * ac
            u = b_ref[0, j:j + 1, :] * (
                v_ref[0, j:j + 1, :]
                - jnp.sum(s * kc, axis=0, keepdims=True))
            s = s + kc * u
            so_ref[0, j] = s
            o_ref[0, j:j + 1, :] = jnp.sum(s * qc, axis=0, keepdims=True)

    @pl.when(n == 0)
    def _():
        # no live row at all: the one block every step maps to is
        # written back as it was read
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def kda_decode_step(q, k, v, alpha, beta, state, live, *,
                    interpret: Optional[bool] = None):
    """One delta-rule step a batch row.

    q, k, alpha: (B, H, dk) float32 — ``alpha = exp(g)``; v: (B, H,
    dv); beta: (B, H); ``state``: (B, H, dk, dv) float32, updated IN
    PLACE (aliased to the second result: donate it); ``live``: (B,)
    bool.  Rows that are not live are neither read nor written; their
    output is zero.  Returns (o (B, H, dv) float32, state)."""
    b, h, dk = q.shape
    dv = v.shape[-1]
    hb = min(_DECODE_HEADS, h)
    assert state.shape == (b, h, dk, dv) and state.dtype == jnp.float32
    assert dk == dv and dk % 128 == 0 and 3 * hb <= dk, (dk, dv, hb)
    assert h % hb == 0 and hb % 8 == 0, (h, hb)
    f32 = jnp.float32
    nhb = h // hb
    n = jnp.sum(live).astype(jnp.int32)
    order = jnp.argsort(jnp.logical_not(live), stable=True)
    idx = order[jnp.minimum(jnp.arange(b), jnp.maximum(n - 1, 0))]

    def block(i, j, idx_ref, n_ref):
        # past the live rows: stay on the last live block
        return (idx_ref[i], jnp.where(i < n_ref[0], j, nhb - 1))

    def vec_spec():
        return pl.BlockSpec(
            (1, hb, dk), lambda i, j, *pre: (*block(i, j, *pre), 0))

    def state_spec():
        return pl.BlockSpec(
            (1, hb, dk, dv),
            lambda i, j, *pre: (*block(i, j, *pre), 0, 0))

    o, state = pl.pallas_call(
        functools.partial(_decode_kernel, hb),
        name="kda_decode_step",
        out_shape=(jax.ShapeDtypeStruct((b, h, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nhb),
            in_specs=[vec_spec() for _ in range(5)] + [state_spec()],
            out_specs=(vec_spec(), state_spec()),
        ),
        # operands: idx, n, q, k, alpha, v, beta, state
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=8 * b * h * dk * dv,
            bytes_accessed=2 * b * h * dk * dv * 4,
            transcendentals=0),
        interpret=default_interpret(interpret),
    )(idx.astype(jnp.int32), n.reshape(1), q.astype(f32), k.astype(f32),
      alpha.astype(f32), v.astype(f32),
      jnp.broadcast_to(beta.astype(f32)[..., None], (b, h, dv)), state)
    return jnp.where(live[:, None, None], o, 0.0), state


# ---------------------------------------------------------------------------
# prefill: chunks of CHUNK tokens
# ---------------------------------------------------------------------------

def _bmm(a, b, dims):
    return jax.lax.dot_general(a, b, dims, precision="highest",
                               preferred_element_type=jnp.float32)


#: (h, i, k) x (h, k, j), (h, i, k) x (h, j, k), (h, c, i) x (h, c, j)
_NN = (((2,), (1,)), ((0,), (0,)))
_NT = (((2,), (2,)), ((0,), (0,)))
_TN = (((1,), (1,)), ((0,), (0,)))


def _prefill_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, *rest):
    """Grid (B, H / hb, T / CHUNK), the chunks in order.  Blocks (1,
    hb, CHUNK, d); ``g`` holds G, the log-decay summed from the chunk's
    start; kb = b k, vb = b v.  ``rest``: [s0_ref (1, hb, dk, dv), where
    the caller carries a state in,] o_ref, so_ref, s_scr — the heads'
    states, which start from ``s0_ref``, else from zeros."""
    *s0_ref, o_ref, so_ref, s_scr = rest
    c = pl.program_id(2)
    f32 = jnp.float32

    @pl.when(c == 0)
    def _():
        s_scr[...] = s0_ref[0][0] if s0_ref else jnp.zeros_like(s_scr)

    q, k, kb, vb, g = (r[0] for r in (q_ref, k_ref, kb_ref, vb_ref,
                                      g_ref))
    hb, cs, _ = q.shape
    s0 = s_scr[...]

    # A and the output's own scores, a sub-block of rows at a time,
    # about the decay at that sub-block's start
    a_rows, qk_rows = [], []
    for i in range(cs // SUB):
        lo = i * SUB
        ref = g[:, lo - 1:lo, :] if i else jnp.zeros_like(g[:, :1, :])
        down = jnp.exp(g[:, lo:lo + SUB, :] - ref)
        up = k * jnp.exp(jnp.minimum(ref - g, _EXP_CAP))
        a_rows.append(_bmm(kb[:, lo:lo + SUB, :] * down, up, _NT))
        qk_rows.append(_bmm(q[:, lo:lo + SUB, :] * down, up, _NT))
    row = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 1)
    a = jnp.where(row > col, jnp.concatenate(a_rows, axis=1), 0.0)
    qk = jnp.where(row >= col, jnp.concatenate(qk_rows, axis=1), 0.0)

    # T = (I + A)^-1: the diagonal sub-blocks (nilpotent of index SUB),
    # then the blocks below them (index CHUNK / SUB)
    eye = (row == col).astype(f32)
    a_d = jnp.where(row // SUB == col // SUB, a, 0.0)
    inv = eye - a_d
    p = a_d
    for _ in range((SUB - 1).bit_length() - 1):
        p = _bmm(p, p, _NN)
        inv = _bmm(inv, eye + p, _NN)
    nil = _bmm(inv, a - a_d, _NN)
    t = eye - nil
    p = nil
    for _ in range((cs // SUB - 1).bit_length() - 1):
        p = _bmm(p, p, _NN)
        t = _bmm(t, eye + p, _NN)
    t = _bmm(t, inv, _NN)

    decay = jnp.exp(g)
    u = _bmm(t, vb, _NN) - _bmm(_bmm(t, kb * decay, _NN), s0, _NN)
    o_ref[0] = _bmm(q * decay, s0, _NN) + _bmm(qk, u, _NN)
    last = g[:, cs - 1:cs, :]
    # Diag(exp(G_C)) S_0 as a product with the diagonal matrix: the
    # decay lies along lanes and scales the state's rows
    dk = s0.shape[1]
    r2 = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
    c2 = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    diag = jnp.where(r2 == c2, jnp.exp(last), 0.0)      # (hb, dk, dk)
    s_new = _bmm(diag, s0, _NN) + _bmm(k * jnp.exp(last - g), u, _TN)
    s_scr[...] = s_new

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        so_ref[0] = s_new


def kda_prefill_chunk(q, k, v, g, beta, state=None, *,
                      interpret: Optional[bool] = None):
    """The delta rule over T tokens a sequence, from ``state`` — (B, H,
    dk, dv) float32: what the sequence's earlier tokens left — or,
    ``None``, from a zero state (a whole prompt; the program then has
    no such operand).

    q, k, g: (B, H, T, dk) — ``g`` the log-decay of each token (<= 0);
    v: (B, H, T, dv); beta: (B, H, T); T a multiple of `CHUNK`.  A
    token with ``g = 0`` and ``beta = 0`` leaves the state as it was
    (how a caller masks a padded tail).  Returns (o (B, H, T, dv)
    float32, state (B, H, dk, dv) float32 after the last token)."""
    b, h, t, dk = q.shape
    dv = v.shape[-1]
    assert t % CHUNK == 0, (t, CHUNK)
    assert dk % 128 == 0 and dv % 128 == 0, (dk, dv)
    hb = min(_PREFILL_HEADS, h)
    assert h % hb == 0, (h, hb)
    f32 = jnp.float32
    q, k, v, g = (x.astype(f32) for x in (q, k, v, g))
    bt = beta.astype(f32)[..., None]
    gsum = jnp.cumsum(g.reshape(b, h, t // CHUNK, CHUNK, dk),
                      axis=3).reshape(b, h, t, dk)

    def seq_spec(d):
        return pl.BlockSpec((1, hb, CHUNK, d),
                            lambda i, j, c: (i, j, c, 0))

    st = pl.BlockSpec((1, hb, dk, dv), lambda i, j, c: (i, j, 0, 0))
    carried = [] if state is None else [state]
    for s0 in carried:
        assert s0.shape == (b, h, dk, dv), (s0.shape, q.shape, v.shape)
        assert s0.dtype == f32, s0.dtype
    nc = t // CHUNK
    # per head and chunk: A and QK (2 CHUNK^2 dk), the inverse (10
    # CHUNK^3), T times (b K+, b V), and five products with the state
    macs = (2 * CHUNK * CHUNK * dk + 10 * CHUNK ** 3
            + CHUNK * CHUNK * (dk + 2 * dv) + 3 * CHUNK * dk * dv
            + dk * dk * dv)
    return pl.pallas_call(
        _prefill_kernel,
        name="kda_prefill_chunk",
        out_shape=(jax.ShapeDtypeStruct((b, h, t, dv), f32),
                   jax.ShapeDtypeStruct((b, h, dk, dv), f32)),
        grid=(b, h // hb, nc),
        in_specs=[seq_spec(dk), seq_spec(dk), seq_spec(dk), seq_spec(dv),
                  seq_spec(dk)] + [st] * len(carried),
        out_specs=(seq_spec(dv), st),
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * nc * macs,
            bytes_accessed=4 * b * h * (t * (3 * dk + 2 * dv)
                                        + len(carried) * dk * dv),
            transcendentals=b * h * t * dk * (CHUNK // SUB + 3)),
        interpret=default_interpret(interpret),
    )(q, k, k * bt, v * bt, gsum, *carried)
