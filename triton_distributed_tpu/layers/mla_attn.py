"""Multi-head latent attention (MLA) on one chip.

Queries go through a low-rank bottleneck; keys and values of all heads
are expanded from ONE latent a token, and a rotated key ``k_rope`` is
shared by every head:

    c_q = RMSNorm(x W_qa);  q = c_q W_qb -> per head [q_nope | q_rope]
    [c_kv | k_r] = x W_kva; c_kv <- RMSNorm(c_kv); k_rope = RoPE(k_r)
    [k_nope | v] = c_kv W_kvb per head
    scores = (q_nope . k_nope + RoPE(q_rope) . k_rope) / sqrt(nope + rope)

What is cached a token is the row ``[c_kv | k_rope | 0 pad]``
(`models.kv_cache`, latent layout), not per-head keys and values.

- `prefill` is the non-absorbed form: keys and values are expanded and
  go through `flash_attention` at head size ``nope + rope`` (which the
  published sizes make equal to the value head size).
- `prefill_suffix` is `prefill` for a CHUNK of one sequence whose
  earlier rows already lie in the page pool: those rows are gathered
  through the sequence's page ids and expanded (non-absorbed, as
  above) in front of the chunk's own keys and values, and the chunk's
  queries attend both under a causal diagonal shifted by the chunk's
  first position.
- `decode_paged` is the absorbed form: ``W_kvb``'s key half is folded
  into the query (``q~ = q_nope W^K``), the scores and the weighted sum
  are taken over the cached rows themselves (`mla_decode_paged`: one
  read serves as K and V), and ``W_kvb``'s value half expands the
  result.  The two agree up to rounding (`tests/test_glm4_moe_lite`).

Weights are `(in, out)` oriented; ``wk_b`` / ``wv_b`` are ``W_kvb``
split and shaped ``(lat, H, nope)`` / ``(lat, H, v)``.  Not tensor
parallel (`TPAttention` is the tp layer).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from triton_distributed_tpu.kernels.flash_attention import (
    attention_reference,
    flash_attention,
)
from triton_distributed_tpu.kernels.mla_decode import (
    mla_decode_paged,
    mla_decode_reference,
)
from triton_distributed_tpu.layers.tp_attn import (
    apply_rope,
    rms_norm,
    rope_cos_sin,
)

LANES = 128
#: Rows of the pool that `prefill_suffix` expands at a time: only the
#: blocks below the chunk's first position are.
EXPAND_ROWS = 1024


def _mm(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32
                   ).astype(x.dtype)


@dataclasses.dataclass
class MLAttention:
    hidden: int
    num_heads: int
    q_rank: int
    lat: int                      # kv_lora_rank
    nope: int                     # qk_nope_head_dim
    rope: int                     # qk_rope_head_dim
    v_dim: int
    rope_theta: float = 1e6
    eps: float = 1e-5
    mode: str = "fused"           # xla | fused
    interpret: Optional[bool] = None

    def __post_init__(self):
        assert self.lat % LANES == 0, (
            f"kv_lora_rank={self.lat}: the cached row's latent part "
            f"must be whole lanes ({LANES})")
        assert self.nope + self.rope == self.v_dim, (
            "prefill runs one flash_attention head size: "
            f"{self.nope}+{self.rope} != {self.v_dim}")

    @property
    def row_width(self) -> int:
        """Width of a cached row: latent + rotated key, padded up to
        whole lanes."""
        return -(-(self.lat + self.rope) // LANES) * LANES

    @property
    def row_used(self) -> int:
        """Numbers of a row that carry information (the rest is pad)."""
        return self.lat + self.rope

    @property
    def scale(self) -> float:
        return (self.nope + self.rope) ** -0.5

    def init_params(self, key, dtype=jnp.bfloat16):
        ks = jax.random.split(key, 6)
        h, hd, lat = self.hidden, self.num_heads, self.lat

        def normal(k, shape, fan_in):
            return (jax.random.normal(k, shape) * fan_in ** -0.5
                    ).astype(dtype)

        return {
            "wq_a": normal(ks[0], (h, self.q_rank), h),
            "q_norm": jnp.ones((self.q_rank,), dtype),
            "wq_b": normal(ks[1], (self.q_rank,
                                   hd * (self.nope + self.rope)),
                           self.q_rank),
            "wkv_a": normal(ks[2], (h, lat + self.rope), h),
            "kv_norm": jnp.ones((lat,), dtype),
            "wk_b": normal(ks[3], (lat, hd, self.nope), lat),
            "wv_b": normal(ks[4], (lat, hd, self.v_dim), lat),
            "wo": normal(ks[5], (hd * self.v_dim, h), hd * self.v_dim),
        }

    def param_specs(self):
        from jax.sharding import PartitionSpec as P
        two, three = P(None, None), P(None, None, None)
        return {"wq_a": two, "q_norm": P(None), "wq_b": two,
                "wkv_a": two, "kv_norm": P(None), "wk_b": three,
                "wv_b": three, "wo": two}

    # ------------------------------------------------------------------

    def _project(self, x, params):
        """x (M, hidden) -> q (M, H, nope + rope), c_kv (M, lat)
        normalised, k_r (M, rope) before rotation."""
        cq = rms_norm(_mm(x, params["wq_a"]), params["q_norm"], self.eps)
        q = _mm(cq, params["wq_b"]).reshape(
            x.shape[0], self.num_heads, self.nope + self.rope)
        kv = _mm(x, params["wkv_a"])
        c = rms_norm(kv[:, :self.lat], params["kv_norm"], self.eps)
        return q, c, kv[:, self.lat:]

    def _rows(self, c, k_rope):
        """The cached rows: (M, R) = [c | k_rope | 0]."""
        pad = self.row_width - self.row_used
        parts = [c, k_rope.astype(c.dtype)]
        if pad:
            parts.append(jnp.zeros((c.shape[0], pad), c.dtype))
        return jnp.concatenate(parts, axis=-1)

    def prefill(self, x, params, batch: int):
        """x: (B*S, hidden).  Returns (out (B*S, hidden), rows
        (B, 1, S, R) for the cache)."""
        m = x.shape[0]
        s = m // batch
        hd = self.num_heads
        q, c, k_r = self._project(x, params)
        cos, sin = rope_cos_sin(jnp.arange(s), self.rope, self.rope_theta)
        q = q.reshape(batch, s, hd, -1).transpose(0, 2, 1, 3)
        q_rope = apply_rope(q[..., self.nope:], cos, sin)
        k_rope = apply_rope(k_r.reshape(batch, 1, s, self.rope), cos, sin)
        k_nope = jnp.einsum("ml,lhn->mhn", c, params["wk_b"],
                            preferred_element_type=jnp.float32
                            ).astype(x.dtype)
        v = jnp.einsum("ml,lhv->mhv", c, params["wv_b"],
                       preferred_element_type=jnp.float32).astype(x.dtype)
        k_nope = k_nope.reshape(batch, s, hd, -1).transpose(0, 2, 1, 3)
        v = v.reshape(batch, s, hd, -1).transpose(0, 2, 1, 3)
        qf = jnp.concatenate([q[..., :self.nope], q_rope], axis=-1)
        kf = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (batch, hd, s, self.rope))],
            axis=-1)
        if self.mode == "xla":
            attn = attention_reference(qf, kf, v, causal=True,
                                       scale=self.scale)
        else:
            attn = flash_attention(qf, kf, v, causal=True,
                                   scale=self.scale,
                                   interpret=self.interpret)
        attn = attn.transpose(0, 2, 1, 3).reshape(m, hd * self.v_dim)
        rows = self._rows(c, k_rope.reshape(m, self.rope))
        return _mm(attn, params["wo"]), rows.reshape(
            batch, 1, s, self.row_width)

    def _keys_values(self, rows, params):
        """Cached rows (M, R) expanded for every head: keys
        (H, M, nope + rope) — the shared rotated key behind each head's
        own — and values (H, M, v)."""
        c = rows[:, :self.lat]
        k_rope = rows[:, self.lat:self.row_used]
        k_nope = jnp.einsum("ml,lhn->hmn", c, params["wk_b"],
                            preferred_element_type=jnp.float32
                            ).astype(rows.dtype)
        v = jnp.einsum("ml,lhv->hmv", c, params["wv_b"],
                       preferred_element_type=jnp.float32
                       ).astype(rows.dtype)
        return jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[None], (
                self.num_heads, *k_rope.shape))], axis=-1), v

    def suffix_buffers(self, span: int, chunk: int, dtype):
        """The key and value buffers `prefill_suffix` fills: ``span``
        positions a sequence's page ids reach, in whole blocks, and
        room behind them for a chunk that starts at the last.  Made
        once a program and handed from layer to layer: what a layer
        leaves behind lies past the next one's diagonal."""
        eb = min(EXPAND_ROWS, span)
        n = -(-span // eb) * eb + chunk
        return (jnp.zeros((self.num_heads, n, self.nope + self.rope),
                          dtype),
                jnp.zeros((self.num_heads, n, self.v_dim), dtype))

    def prefill_suffix(self, x, params, start, pool, page_ids, bufs):
        """x: (C, hidden), positions ``start + arange(C)`` of ONE
        sequence whose rows below ``start`` lie in ``pool``
        (P, 1, page, R) at the pages ``page_ids`` (T,) names in
        logical order.  Those rows are expanded block by block up to
        ``start`` into ``bufs`` (`suffix_buffers`), the chunk's own keys
        and values are put at ``start``, and `flash_attention` runs
        with its diagonal shifted by ``start``: row i sees nothing
        past ``start + i``, so what the buffers hold beyond the chunk
        needs no mask of its own.  Returns (out (C, hidden), rows
        (1, 1, C, R) for the cache, bufs)."""
        m = x.shape[0]
        hd = self.num_heads
        q, c, k_r = self._project(x, params)
        cos, sin = rope_cos_sin(start + jnp.arange(m), self.rope,
                                self.rope_theta)
        q = q.reshape(1, m, hd, -1).transpose(0, 2, 1, 3)
        qf = jnp.concatenate(
            [q[..., :self.nope], apply_rope(q[..., self.nope:], cos, sin)],
            axis=-1)
        rows = self._rows(c, apply_rope(k_r, cos, sin))

        span = page_ids.shape[0] * pool.shape[2]
        room = bufs[0].shape[1] - m         # span, in whole blocks
        eb = min(EXPAND_ROWS, room)
        prefix = jnp.pad(pool[page_ids, 0].reshape(span, self.row_width),
                         ((0, room - span), (0, 0)))

        def expand(i, kv):
            blk = jax.lax.dynamic_slice_in_dim(prefix, i * eb, eb)
            return tuple(
                jax.lax.dynamic_update_slice_in_dim(buf, new, i * eb,
                                                    axis=1)
                for buf, new in zip(kv, self._keys_values(blk, params)))

        bufs = jax.lax.fori_loop(0, (start + eb - 1) // eb, expand, bufs)
        kf, v = (jax.lax.dynamic_update_slice_in_dim(buf, own, start,
                                                     axis=1)
                 for buf, own in zip(bufs,
                                     self._keys_values(rows, params)))
        attend = (attention_reference if self.mode == "xla" else
                  functools.partial(flash_attention,
                                    interpret=self.interpret))
        attn = attend(qf, kf[None], v[None], causal=True,
                      scale=self.scale, kv_offset=start)
        attn = attn.astype(x.dtype).transpose(0, 2, 1, 3).reshape(
            m, hd * self.v_dim)
        return (_mm(attn, params["wo"]),
                rows.reshape(1, 1, m, self.row_width), (kf, v))

    def decode_paged(self, x, params, pool, page_table, offset):
        """One position a row.  x: (B, hidden); pool: (P, 1, page, R);
        page_table: (B, T); offset: (B,) filled lengths.  The new row is
        written at ``page_table[b, offset // page]``, row ``offset %
        page`` (a masked slot's NULL-mapped write lands in the trash
        page).  Returns (out (B, hidden), pool)."""
        b = x.shape[0]
        ps = pool.shape[2]
        q, c, k_r = self._project(x, params)
        cos, sin = rope_cos_sin(offset, self.rope, self.rope_theta)

        # `apply_rope` rotates along its second-to-last axis' table:
        # here one position a batch row
        q_rope = apply_rope(q[..., self.nope:].swapaxes(0, 1), cos,
                            sin).swapaxes(0, 1)
        rows = self._rows(c, apply_rope(k_r, cos, sin))
        pool = pool.at[page_table[jnp.arange(b), offset // ps], 0,
                       offset % ps, :].set(rows.astype(pool.dtype))

        q_abs = jnp.einsum("bhn,lhn->bhl", q[..., :self.nope],
                           params["wk_b"],
                           preferred_element_type=jnp.float32
                           ).astype(x.dtype)
        pad = self.row_width - self.row_used
        qf = jnp.concatenate(
            [q_abs, q_rope]
            + ([jnp.zeros((b, self.num_heads, pad), x.dtype)]
               if pad else []), axis=-1)
        if self.mode == "xla":
            o_lat = mla_decode_reference(
                qf, pool, page_table, offset + 1, lat=self.lat,
                scale=self.scale).astype(x.dtype)
        else:
            o_lat = mla_decode_paged(
                qf, pool, page_table, offset + 1, lat=self.lat,
                scale=self.scale, interpret=self.interpret)
        o = jnp.einsum("bhl,lhv->bhv", o_lat, params["wv_b"],
                       preferred_element_type=jnp.float32).astype(x.dtype)
        return _mm(o.reshape(b, -1), params["wo"]), pool
