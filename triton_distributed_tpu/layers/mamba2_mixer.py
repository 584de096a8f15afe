"""Mamba-2 mixer (arXiv 2405.21060, as the `nemotron_h` family lays it
out): a state-space layer whose memory is a fixed-size recurrent state
a sequence — one ``(head_dim, state)`` float32 matrix a head, and the
short convolution's last ``conv - 1`` inputs — where a softmax layer
keeps K and V of every token.

With ``u`` the layer's normed input, a row a token:

    [z | xBC | dt] = u W_in
    xBC <- SiLU(b_c + conv(xBC)),  conv causal, depthwise;  [x | B | C]
    dt = softplus(dt + dt_bias) a head;  a = exp(-exp(A_log) dt)
    S_t = a_t S_{t-1} + dt_t x_t (outer) B_t;  y_t = S_t C_t + D x_t
    y <- RMSNorm_group(y * SiLU(z)) * w_norm;  out = y W_out

``B`` and ``C`` are ``groups`` vectors of ``state`` numbers, a group
serving ``num_heads / groups`` heads; the norm runs inside each of the
``groups`` slices of the ``num_heads * head_dim`` channels.  The state
recurrence is `kernels.mamba2`: the chunked kernel over a prefill, the
one-token kernel in a decode step; the state lies in that module's
paired layout whatever the mode.  ONE device: nothing here is sharded.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from triton_distributed_tpu.kernels import mamba2


def _dot(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


@dataclasses.dataclass
class Mamba2Mixer:
    hidden: int
    num_heads: int
    head_dim: int
    groups: int
    state: int
    conv: int = 4                 # the short convolution's taps
    eps: float = 1e-5
    mode: str = "fused"           # fused (kernels) | xla (the recurrence)
    interpret: Optional[bool] = None

    @property
    def inner(self) -> int:
        """Channels of x, of z and of y."""
        return self.num_heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: x, B and C."""
        return self.inner + 2 * self.groups * self.state

    @property
    def state_shapes(self):
        """(state, conv inputs) a sequence (`models.kv_cache`)."""
        return ((self.num_heads // 2, self.state, 2 * self.head_dim),
                ((self.conv - 1) * self.conv_width,))

    def init_params(self, key, dtype=jnp.bfloat16):
        ks = jax.random.split(key, 7)
        h, c, n = self.hidden, self.inner, self.num_heads

        def normal(k, shape, fan_in):
            return (jax.random.normal(k, shape) * fan_in ** -0.5
                    ).astype(dtype)

        dt = jax.random.uniform(ks[4], (n,), minval=0.001, maxval=0.1)
        return {
            "w_in": normal(ks[0], (h, c + self.conv_width + n), h),
            "conv": normal(ks[1], (self.conv, self.conv_width),
                           self.conv),
            "conv_bias": 0.1 * jax.random.normal(
                ks[2], (self.conv_width,)).astype(dtype),
            "a_log": jnp.log(jax.random.uniform(
                ks[3], (n,), minval=1.0, maxval=16.0)),
            "dt_bias": jnp.log(jnp.expm1(dt)),      # softplus^-1
            "d": jnp.ones((n,), jnp.float32),
            "norm": jnp.ones((c,), dtype),
            "w_out": normal(ks[5], (c, h), c),
        }

    def param_specs(self):
        from jax.sharding import PartitionSpec as P
        return {"w_in": P(None, None), "conv": P(None, None),
                "conv_bias": P(None), "a_log": P(None),
                "dt_bias": P(None), "d": P(None), "norm": P(None),
                "w_out": P(None, None)}

    # ------------------------------------------------------------------

    def _split(self, proj):
        c = self.inner
        return (proj[..., :c], proj[..., c:c + self.conv_width],
                proj[..., c + self.conv_width:])

    def _conved(self, taps, params, dtype):
        """``taps``: the convolution's ``conv`` inputs of each output,
        oldest first.  SiLU(bias + sum), in the served type."""
        f32 = jnp.float32
        w = params["conv"].astype(f32)
        y = sum(t.astype(f32) * w[i] for i, t in enumerate(taps))
        return jax.nn.silu(y + params["conv_bias"].astype(f32)
                           ).astype(dtype)

    def _step_size(self, dt, params):
        return jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])

    def _output(self, y, x, z, params, dtype):
        """y: (..., inner) float32 from the recurrence -> (..., hidden):
        the skip, the gate, the norm inside each group, W_out."""
        f32 = jnp.float32
        lead = y.shape[:-1]
        skip = x.astype(f32).reshape(*lead, self.num_heads, self.head_dim
                                     ) * params["d"][:, None]
        y = (y + skip.reshape(*lead, -1)) * jax.nn.silu(z.astype(f32))
        y = y.reshape(*lead, self.groups, -1)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + self.eps)
        y = y.reshape(*lead, -1) * params["norm"].astype(f32)
        return _dot(y.astype(dtype), params["w_out"]).astype(dtype)

    def prefill(self, x, params, batch: int, length, state=None,
                conv_in=None):
        """x: (B * T, hidden); ``length``: (B,) int32 — the tokens of
        each row the state absorbs (positions from there on, a padded
        tail, leave it as it was).  ``state`` (B, H / 2, N, 2 P)
        float32 and ``conv_in`` (B, (conv - 1) * conv_width), both or
        neither: what a prefill of the rows' EARLIER tokens returned —
        x then continues those sequences (a long prompt prefilled in
        pieces); without them the rows start a sequence, from a zero
        state behind a window of zeros.  Returns (y like x, state (B,
        H / 2, N, 2 P) float32, conv inputs (B, (conv - 1) *
        conv_width): the projections at positions ``length - conv + 1
        .. length - 1`` side by side, oldest first — cut from the
        window the convolution ran over, so a piece that absorbs fewer
        than ``conv - 1`` tokens hands on inputs of the piece before
        it, and one that absorbs none hands on what it was given)."""
        assert (state is None) == (conv_in is None), "both or neither"
        t = x.shape[0] // batch
        taps = self.conv
        z, xbc, dt = self._split(
            _dot(x, params["w_in"]).astype(x.dtype).reshape(batch, t, -1))
        if conv_in is None:
            padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
        else:
            padded = jnp.concatenate(
                [conv_in.reshape(batch, taps - 1, -1).astype(xbc.dtype),
                 xbc], axis=1)
        xs, b, c = jnp.split(
            self._conved([padded[:, i:i + t] for i in range(taps)],
                         params, x.dtype),
            [self.inner, self.inner + self.groups * self.state], axis=-1)
        seen = jnp.arange(t)[None, :] < length[:, None]        # (B, T)
        dt = jnp.where(seen[..., None], self._step_size(dt, params), 0.0)
        a = -jnp.exp(params["a_log"].astype(jnp.float32))
        if self.mode == "xla":
            heads = lambda v, n: v.reshape(batch, t, n, -1)  # noqa: E731
            y, state = mamba2.mamba2_recurrent_reference(
                heads(xs, self.num_heads), dt, a, heads(b, self.groups),
                heads(c, self.groups),
                None if state is None else mamba2.unpair_state(state))
            y, state = y.reshape(batch, t, -1), mamba2.pair_state(state)
        else:
            pad = -t % mamba2.CHUNK
            grow = lambda v: jnp.pad(       # noqa: E731
                v, ((0, 0), (0, pad), (0, 0)))    # dt = 0: no change
            y, state = mamba2.mamba2_prefill_chunk(
                grow(xs), grow(dt), a, grow(b), grow(c), state,
                interpret=self.interpret)
            y = y[:, :t]
        out = self._output(y, xs, z, params, x.dtype)
        last = jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(
            row, n, taps - 1, axis=0))(padded, length)
        return out.reshape(batch * t, -1), state, last.reshape(batch, -1)

    def decode(self, x, params, state, conv_in, live):
        """One token a row.  x: (B, hidden); ``state``: (B, H / 2, N,
        2 P) float32, updated where it lies; ``conv_in``: (B, (conv -
        1) * conv_width); ``live``: (B,) bool — the rest keep their
        state and their inputs.  Returns (y, state, conv_in)."""
        z, xbc, dt = self._split(_dot(x, params["w_in"]).astype(x.dtype))
        # the kept inputs lie side by side, oldest first: a tap is a
        # whole-lane slice and the shift a concatenation
        window = jnp.concatenate([conv_in, xbc], axis=1)
        cw = self.conv_width
        xs, b, c = jnp.split(
            self._conved([window[:, i * cw:(i + 1) * cw]
                          for i in range(self.conv)], params, x.dtype),
            [self.inner, self.inner + self.groups * self.state], axis=-1)
        dt = self._step_size(dt, params)
        a = -jnp.exp(params["a_log"].astype(jnp.float32))
        if self.mode == "xla":
            n = x.shape[0]
            heads = lambda v, k: v.reshape(n, 1, k, -1)     # noqa: E731
            y, new = mamba2.mamba2_recurrent_reference(
                heads(xs, self.num_heads), dt[:, None], a,
                heads(b, self.groups), heads(c, self.groups),
                mamba2.unpair_state(state))
            y = y.reshape(n, -1)
            state = jnp.where(live[:, None, None, None],
                              mamba2.pair_state(new), state)
        else:
            y, state = mamba2.mamba2_decode_step(
                xs, dt, a, b, c, state, live, interpret=self.interpret)
        conv_in = jnp.where(live[:, None], window[:, cw:], conv_in)
        return self._output(y, xs, z, params, x.dtype), state, conv_in
