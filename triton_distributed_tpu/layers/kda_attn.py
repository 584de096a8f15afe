"""Kimi Delta Attention mixer (arXiv 2510.26692): a linear-attention
layer whose memory is a fixed-size recurrent state a sequence — one
``(d, d)`` float32 matrix a head, and the short convolution's last
``conv - 1`` inputs — where a softmax layer keeps K and V of every
token.

With ``x`` the layer's normed input, a row a token:

    [q | k | v] = SiLU(conv(x W_qkv)),  conv causal, depthwise, no bias
    q <- L2norm_head(q) d^-0.5,  k <- L2norm_head(k)
    g = -exp(A_h) softplus((x W_a1) W_a2 + b_dt)     log-decay a channel
    b = (2 | 1) sigmoid(x W_b)                       write strength a head
    S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t
    y = (RMSNorm_head(o) * sigmoid((x W_g1) W_g2)) W_o

The three rank-small input projections (``W_a1``, ``W_g1``, ``W_b``)
ride side by side as ``w_low``.  The state recurrence is
`kernels.kda`: the chunked kernel over a prefill, the one-token kernel
in a decode step.  A prefill starts a sequence — a zero state behind a
convolution window of zeros — or CONTINUES one from the state and the
kept inputs an earlier prefill returned (``state=``, ``conv_in=``): a
long prompt goes in in pieces, and the state stays float32 and the kept
inputs the layer's dtype from piece to piece as they do from step to
step.  ONE device: nothing here is sharded.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from triton_distributed_tpu.kernels import kda

_L2_EPS = 1e-6


def _dot(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


@dataclasses.dataclass
class KDAttention:
    hidden: int
    num_heads: int
    head_dim: int
    conv: int = 4                 # the short convolution's taps
    rank: int = 128               # of the decay's and the gate's maps
    #: write strength in (0, 2) — the state's transition may then have
    #: negative eigenvalues — rather than (0, 1).  Set by the model.
    neg_eigval: bool = True
    eps: float = 1e-5
    mode: str = "fused"           # fused (kernels) | xla (the recurrence)
    interpret: Optional[bool] = None

    @property
    def width(self) -> int:
        """Channels of q, of k and of v."""
        return self.num_heads * self.head_dim

    def init_params(self, key, dtype=jnp.bfloat16):
        ks = jax.random.split(key, 9)
        h, c, r = self.hidden, self.width, self.rank

        def normal(k, shape, fan_in):
            return (jax.random.normal(k, shape) * fan_in ** -0.5
                    ).astype(dtype)

        dt = jax.random.uniform(ks[7], (c,), minval=0.001, maxval=0.1)
        return {
            "wqkv": normal(ks[0], (h, 3 * c), h),
            "conv": normal(ks[1], (self.conv, 3 * c), self.conv),
            "w_low": normal(ks[2], (h, 2 * r + self.num_heads), h),
            "wa_up": normal(ks[3], (r, c), r),
            "wg_up": normal(ks[4], (r, c), r),
            "a_log": jnp.log(jax.random.uniform(
                ks[6], (self.num_heads,), minval=1.0, maxval=16.0)),
            "dt_bias": jnp.log(jnp.expm1(dt)),      # softplus^-1
            "o_norm": jnp.ones((self.head_dim,), dtype),
            "wo": normal(ks[5], (c, h), c),
        }

    def param_specs(self):
        from jax.sharding import PartitionSpec as P
        return {"wqkv": P(None, None), "conv": P(None, None),
                "w_low": P(None, None), "wa_up": P(None, None),
                "wg_up": P(None, None), "a_log": P(None),
                "dt_bias": P(None), "o_norm": P(None),
                "wo": P(None, None)}

    # ------------------------------------------------------------------

    def _heads(self, x):
        return x.reshape(*x.shape[:-1], self.num_heads, self.head_dim)

    def _features(self, x, conved, params):
        """From the layer's input (..., hidden) and the convolution's
        output (..., 3 * width) float32: q, k, v, g (..., H, d), beta
        (..., H), all float32, and the output gate (..., width)."""
        f32 = jnp.float32
        q, k, v = (self._heads(a) for a in jnp.split(
            jax.nn.silu(conved), 3, axis=-1))

        def unit(a):
            return a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + _L2_EPS)

        r = self.rank
        low = _dot(x, params["w_low"]).astype(x.dtype)
        decay = _dot(low[..., :r], params["wa_up"]) + params["dt_bias"]
        g = -jnp.exp(params["a_log"].astype(f32))[:, None] * self._heads(
            jax.nn.softplus(decay))
        beta = jax.nn.sigmoid(low[..., 2 * r:].astype(f32))
        if self.neg_eigval:
            beta = 2.0 * beta
        gate = jax.nn.sigmoid(_dot(low[..., r:2 * r], params["wg_up"]))
        return (unit(q) * self.head_dim ** -0.5, unit(k), v, g, beta,
                gate)

    def _output(self, o, gate, params, dtype):
        """o: (..., H, d) float32 -> (..., hidden)."""
        o = o * jax.lax.rsqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + self.eps)
        o = o * params["o_norm"].astype(jnp.float32)
        y = o.reshape(*o.shape[:-2], self.width) * gate
        return _dot(y.astype(dtype), params["wo"]).astype(dtype)

    def prefill(self, x, params, batch: int, length, state=None,
                conv_in=None):
        """x: (B * T, hidden); ``length``: (B,) int32 — the tokens of
        each row the state absorbs (positions from there on, a padded
        tail, leave it as it was).  ``state`` (B, H, d, d) float32 and
        ``conv_in`` (B, (conv - 1) * 3 * width), both or neither: what
        a prefill of the rows' EARLIER tokens returned — x then
        continues those sequences (a long prompt prefilled in pieces);
        without them the rows start a sequence, from a zero state
        behind a window of zeros.  Returns (y like x, state (B, H, d,
        d) float32, conv inputs (B, (conv - 1) * 3 * width): the
        projections at positions ``length - conv + 1 .. length - 1``
        side by side, oldest first — cut from the window the
        convolution ran over, so a piece that absorbs fewer than ``conv
        - 1`` tokens hands on inputs of the piece before it, and one
        that absorbs none hands on what it was given)."""
        assert (state is None) == (conv_in is None), "both or neither"
        f32 = jnp.float32
        t = x.shape[0] // batch
        taps = self.conv
        proj = _dot(x, params["wqkv"]).astype(x.dtype).reshape(
            batch, t, -1)
        if conv_in is None:
            padded = jnp.pad(proj, ((0, 0), (taps - 1, 0), (0, 0)))
        else:
            padded = jnp.concatenate(
                [conv_in.reshape(batch, taps - 1, -1).astype(proj.dtype),
                 proj], axis=1)
        w = params["conv"].astype(f32)
        conved = sum(padded[:, i:i + t].astype(f32) * w[i]
                     for i in range(taps))
        q, k, v, g, beta, gate = self._features(
            x.reshape(batch, t, -1), conved, params)
        seen = jnp.arange(t)[None, :] < length[:, None]        # (B, T)
        g = jnp.where(seen[..., None, None], g, 0.0)
        beta = jnp.where(seen[..., None], beta, 0.0)
        seq = lambda a: jnp.moveaxis(a, 1, 2)       # noqa: E731
        q, k, v, g, beta = (seq(a) for a in (q, k, v, g, beta))
        if self.mode == "xla":
            o, state = kda.kda_recurrent_reference(q, k, v, g, beta,
                                                   state)
        else:
            pad = -t % kda.CHUNK
            if pad:     # whole chunks: g = 0, beta = 0 changes nothing
                q, k, v, g = (jnp.pad(a, ((0, 0), (0, 0), (0, pad),
                                          (0, 0))) for a in (q, k, v, g))
                beta = jnp.pad(beta, ((0, 0), (0, 0), (0, pad)))
            o, state = kda.kda_prefill_chunk(q, k, v, g, beta, state,
                                             interpret=self.interpret)
            o = o[:, :, :t]
        y = self._output(seq(o), gate, params, x.dtype)
        last = jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(
            row, n, taps - 1, axis=0))(padded, length)
        return y.reshape(batch * t, -1), state, last.reshape(batch, -1)

    def decode(self, x, params, state, conv_in, live):
        """One token a row.  x: (B, hidden); ``state``: (B, H, d, d)
        float32, updated where it lies; ``conv_in``: (B, (conv - 1) * 3
        * width); ``live``: (B,) bool — the rest keep their state and
        their inputs.  Returns (y, state, conv_in)."""
        f32 = jnp.float32
        proj = _dot(x, params["wqkv"]).astype(x.dtype)
        # the kept inputs lie side by side, oldest first: a tap is a
        # whole-lane slice and the shift a concatenation
        window = jnp.concatenate([conv_in, proj], axis=1)
        c3 = proj.shape[1]
        w = params["conv"].astype(f32)
        conved = sum(window[:, i * c3:(i + 1) * c3].astype(f32) * w[i]
                     for i in range(self.conv))
        q, k, v, g, beta, gate = self._features(x, conved, params)
        if self.mode == "xla":
            o, new = kda.kda_recurrent_reference(
                *(a[:, :, None] for a in (q, k, v, g, beta)), state)
            o = o[:, :, 0]
            state = jnp.where(live[:, None, None, None], new, state)
        else:
            o, state = kda.kda_decode_step(
                q, k, v, jnp.exp(g), beta, state, live,
                interpret=self.interpret)
        conv_in = jnp.where(live[:, None], window[:, c3:], conv_in)
        return self._output(o, gate, params, x.dtype), state, conv_in
