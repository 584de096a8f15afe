"""Tensor-parallel attention (heads sharded over the tp axis).

Reference: `python/triton_dist/layers/nvidia/tp_attn.py` (274 LoC):
AG-GEMM for the fused QKV projection, RoPE cache
(`_set_cos_sin_cache:69`), flash attention for prefill / flash-decode
for decode, GEMM-RS for the output projection.

TPU layout: per rank H_loc = H/world query heads and Hkv_loc kv heads;
activations are M-sharded between layers (sequence parallel), gathered
by the fused AG-GEMM for the projections — identical dataflow to the
reference's `dist_triton_fwd`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from triton_distributed_tpu import collective_ids as cids

from triton_distributed_tpu.kernels.allgather_gemm import (
    AllGatherGEMMContext,
    ag_gemm,
)
from triton_distributed_tpu.kernels.flash_attention import (
    attention_reference,
    flash_attention,
    flash_attention_diff,
)
from triton_distributed_tpu.kernels.flash_decode import (
    flash_decode,
    flash_decode_paged,
)
from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
    GEMMReduceScatterContext,
    gemm_rs,
)
from triton_distributed_tpu.kernels.matmul import MatmulConfig


def rope_cos_sin(positions, dim: int, theta: float = 1e6,
                 dtype=jnp.float32):
    """RoPE tables (reference `_set_cos_sin_cache`, `tp_attn.py:69`).
    positions: (S,) → cos/sin (S, dim/2)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2,
                                           dtype=jnp.float32) / dim))
    freqs = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rope(x, cos, sin):
    """x: (..., S, D) with rotate-half convention; cos/sin (S, D/2),
    or anything that broadcasts against half a head."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c, s = cos, sin
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def apply_rope_pairs(x, cos, sin):
    """`apply_rope` over ADJACENT pairs ``(x[2i], x[2i + 1])`` (the
    GPT-J convention), where `apply_rope` pairs ``x[i]`` with ``x[i +
    D/2]``: the same rotation on another layout of the head.  cos/sin
    broadcast against ``x[..., ::2]``."""
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def layer_norm(x, weight, eps: float = 1e-5):
    """Mean-subtracting LayerNorm with a weight and no bias."""
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight


def rms_norm(x, weight, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
            ).astype(x.dtype) * weight


#: A sliding-window layer's kernels in a device trace.
WINDOW_KERNELS = {"decode": "swa_decode_paged",
                  "prefill": "swa_prefill_attention"}


@dataclasses.dataclass
class TPAttention:
    """Reference analogue: `TP_Attn` (`tp_attn.py:78`)."""

    axis: str
    world_size: int
    hidden: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 1e6
    qk_norm: bool = True          # Qwen3-style per-head q/k RMSNorm
    #: False: no positional encoding at all (NoPE).  Set by the model.
    rope: bool = True
    #: True: the rotation pairs ADJACENT dimensions (`apply_rope_pairs`;
    #: `rope_gptj`), not a dimension with the one half a head away.
    rope_pairs: bool = False
    #: > 0: a SLIDING-WINDOW layer — key j is visible to query i iff
    #: ``i - window < j <= i``.  Its kernels run under names of their
    #: own (`WINDOW_KERNELS`), and its pages behind the window go back
    #: to their pool (`serving.pages`).  Set by the model.
    window: int = 0
    #: An output gate (arXiv 2505.06708): ``sigmoid(x W_g)``, a number
    #: a head and channel, times the heads' output before ``W_o``;
    #: ``W_g`` rides as further columns of ``wqkv``.  Set by the model.
    gate: bool = False
    #: > 1: the model generates by blocks of this many positions — the
    #: prefill's mask is BLOCK-causal (causal across blocks,
    #: bidirectional inside one; forward only) and the paged step is
    #: `block_paged`.  Set by the model.
    block: int = 0
    mode: str = "fused"           # xla | fused
    gemm: MatmulConfig = dataclasses.field(default_factory=MatmulConfig)
    collective_ids: tuple = (cids.TP_ATTN_QKV, cids.TP_ATTN_OUT)
    interpret: Optional[bool] = None

    def __post_init__(self):
        # Exact per-rank splits only — head replication is unsupported
        # (weights, cache and sharding specs all assume it).
        assert self.num_heads % self.world_size == 0, (
            self.num_heads, self.world_size)
        assert self.num_kv_heads % self.world_size == 0, (
            self.num_kv_heads, self.world_size)

    @property
    def h_loc(self):
        return self.num_heads // self.world_size

    @property
    def hkv_loc(self):
        return self.num_kv_heads // self.world_size

    @property
    def qkv_cols(self):
        return ((1 + self.gate) * self.h_loc
                + 2 * self.hkv_loc) * self.head_dim

    def init_params(self, key, dtype=jnp.bfloat16):
        k1, k2 = jax.random.split(key)
        scale = self.hidden ** -0.5
        p = {
            "wqkv": (jax.random.normal(
                k1, (self.hidden, self.qkv_cols)) * scale).astype(dtype),
            "wo": (jax.random.normal(
                k2, (self.h_loc * self.head_dim, self.hidden))
                * scale).astype(dtype),
        }
        if self.qk_norm:
            p["q_norm"] = jnp.ones((self.head_dim,), dtype)
            p["k_norm"] = jnp.ones((self.head_dim,), dtype)
        return p

    def global_param_specs(self):
        from jax.sharding import PartitionSpec as P
        specs = {"wqkv": P(None, self.axis), "wo": P(self.axis, None)}
        if self.qk_norm:
            specs["q_norm"] = P(None)
            specs["k_norm"] = P(None)
        return specs

    # ------------------------------------------------------------------

    def _project_qkv(self, x, params):
        if self.mode == "fused":
            ctx = AllGatherGEMMContext(
                axis=self.axis, world_size=self.world_size,
                gemm=self.gemm, collective_id=self.collective_ids[0],
                interpret=self.interpret)
            qkv = ag_gemm(x, params["wqkv"], ctx)
        else:
            full = jax.lax.all_gather(x, self.axis, tiled=True)
            qkv = jnp.dot(full, params["wqkv"],
                          preferred_element_type=jnp.float32
                          ).astype(x.dtype)
        return qkv  # (M, qkv_cols)

    def _split_gate(self, qkv):
        """(q | k | v columns, the gate (M, h_loc * d) or None)."""
        if not self.gate:
            return qkv, None
        cut = qkv.shape[-1] - self.h_loc * self.head_dim
        return qkv[:, :cut], jax.nn.sigmoid(
            qkv[:, cut:].astype(jnp.float32))

    def _split_heads(self, qkv, batch, seq):
        d = self.head_dim
        q, k, v = jnp.split(
            qkv.reshape(batch, seq, -1),
            [self.h_loc * d, (self.h_loc + self.hkv_loc) * d], axis=-1)
        q = q.reshape(batch, seq, self.h_loc, d).transpose(0, 2, 1, 3)
        k = k.reshape(batch, seq, self.hkv_loc, d).transpose(0, 2, 1, 3)
        v = v.reshape(batch, seq, self.hkv_loc, d).transpose(0, 2, 1, 3)
        return q, k, v

    def _out_proj(self, attn, x_dtype, params):
        if self.mode == "fused":
            ctx = GEMMReduceScatterContext(
                axis=self.axis, world_size=self.world_size,
                gemm=self.gemm, collective_id=self.collective_ids[1],
                interpret=self.interpret)
            return gemm_rs(attn, params["wo"], ctx)
        partial = jnp.dot(attn, params["wo"],
                          preferred_element_type=jnp.float32)
        world = self.world_size
        m = partial.shape[0]
        return jax.lax.psum_scatter(
            partial.reshape(world, m // world, -1), self.axis,
            scatter_dimension=0, tiled=False).astype(x_dtype)

    def _rotate(self, x, cos, sin):
        """x (..., D) by cos/sin that broadcast against half a head."""
        return (apply_rope_pairs if self.rope_pairs else apply_rope)(
            x, cos, sin)

    def _prefill_heads(self, x, params, batch: int, positions=None):
        """The projections of ``batch`` sequences whose rows stand at
        ``positions`` (S,), ``arange(S)`` by default: (q (B, H_loc, S,
        D), k, v (B, Hkv_loc, S, D) — normed and rotated as the layer
        is set — and the output gate or None)."""
        qkv, gate = self._split_gate(
            self._project_qkv(x, params))           # (M, qkv_cols)
        seq = qkv.shape[0] // batch
        q, k, v = self._split_heads(qkv, batch, seq)
        if self.qk_norm:
            q = rms_norm(q, params["q_norm"])
            k = rms_norm(k, params["k_norm"])
        if self.rope:
            cos, sin = rope_cos_sin(
                jnp.arange(seq) if positions is None else positions,
                self.head_dim, self.rope_theta)
            q = self._rotate(q, cos, sin)
            k = self._rotate(k, cos, sin)
        return q, k, v, gate

    def _prefill_out(self, attn, gate, x_dtype, params):
        """The heads' output (B, H_loc, S, D) gated and projected."""
        b, _, seq, _ = attn.shape
        attn = attn.astype(x_dtype).transpose(0, 2, 1, 3).reshape(
            b * seq, -1)
        if gate is not None:
            attn = (attn * gate).astype(x_dtype)
        return self._out_proj(attn, x_dtype, params)

    def prefill(self, x, params, batch: int):
        """x: (M/world, hidden) M-sharded; returns same sharding, plus
        this rank's KV (B, Hkv_loc, S, D) for the cache."""
        q, k, v, gate = self._prefill_heads(x, params, batch)
        if self.mode == "xla":
            # dense golden (differentiable; materializes S² — use the
            # fused mode for long sequences)
            attn = attention_reference(q, k, v, causal=True,
                                       causal_block=self.block,
                                       window=self.window)
        elif self.window:
            attn = flash_attention(q, k, v, causal=True,
                                   window=self.window,
                                   name=WINDOW_KERNELS["prefill"],
                                   interpret=self.interpret)
        elif self.block > 1:
            attn = flash_attention(q, k, v, causal=True,
                                   causal_block=self.block,
                                   interpret=self.interpret)
        else:
            # Pallas flash with a Pallas backward (custom VJP): the
            # fused mode trains too.
            attn = flash_attention_diff(q, k, v, causal=True,
                                        interpret=self.interpret)
        return self._prefill_out(attn, gate, x.dtype, params), (k, v)

    def prefill_suffix(self, x, params, start, kv_pools, page_ids):
        """`prefill` for a CHUNK of ONE sequence whose earlier rows lie
        in the page pool.  x: (C / world, hidden), positions ``start +
        arange(C)``; ``kv_pools``: this layer's (k pool, v pool), each
        (P, Hkv_loc, page, D), read and not written; ``page_ids`` (T,):
        the sequence's pages in logical order.  The pages' rows below
        ``start`` are gathered into one buffer a pool (whatever lies at
        or past ``start`` there is zeroed: another owner's rows, the
        trash page), the chunk's own K and V are put at ``start``, and
        `flash_attention` runs with its diagonal shifted by ``start``:
        row i sees nothing past ``start + i``, so the buffer's tail
        needs no mask of its own.  Nothing is expanded, so the buffers
        are a gather a layer (`MLAttention.prefill_suffix` hands its
        own from layer to layer).  A WINDOW layer gathers only the
        ``window`` tokens' pages below ``start`` (``start`` a multiple
        of the page; ``page_ids`` its own table's row, NULL where a
        page was given back: nothing there is visible) and runs the
        windowed kernel over that buffer and the chunk.  A BLOCK layer
        (``block`` > 1) runs the kernel under the block-causal mask:
        row i sees column j iff ``j // block <= (start + i) // block``
        — ``start`` is the caller's to keep a multiple of the block
        (it is one of the chunk or of the page, which both hold whole
        blocks): all below it is whole earlier blocks, and a row's own
        block ends inside the chunk.  Returns (out like x, the chunk's
        (k, v), each (1, Hkv_loc, C, D), for the cache)."""
        k_pool, v_pool = kv_pools
        assert k_pool.dtype != jnp.int8, "chunks over an int8 pool"
        m = x.shape[0] * self.world_size
        q, k, v, gate = self._prefill_heads(x, params, 1,
                                            start + jnp.arange(m))
        ps = k_pool.shape[2]
        kw = {}
        if self.window:
            # the pages that hold the window's tokens below ``start``:
            # the buffer begins at position ``base``
            n = min(-(-self.window // ps), page_ids.shape[0])
            first = jnp.clip(start // ps - n, 0, page_ids.shape[0] - n)
            page_ids = jax.lax.dynamic_slice_in_dim(page_ids, first, n)
            start = start - first * ps
            kw = dict(window=self.window)
            if self.mode != "xla":
                kw["name"] = WINDOW_KERNELS["prefill"]
        elif self.block > 1:
            assert m % self.block == 0 and ps % self.block == 0, (
                "a chunk and a page hold whole blocks", m, ps, self.block)
            kw = dict(causal_block=self.block)
        span = page_ids.shape[0] * ps
        # whole query blocks, and room for a chunk that starts at the
        # last row the pages reach
        room = -(-span // m) * m + m - span
        below = (jnp.arange(span) < start)[None, :, None]

        def with_prefix(pool, own):
            rows = jnp.moveaxis(pool[page_ids], 0, 1).reshape(
                self.hkv_loc, span, self.head_dim)
            buf = jnp.pad(jnp.where(below, rows, 0),
                          ((0, 0), (0, room), (0, 0)))
            return jax.lax.dynamic_update_slice_in_dim(
                buf, own[0].astype(buf.dtype), start, axis=1)[None]

        attend = (attention_reference if self.mode == "xla" else
                  functools.partial(flash_attention,
                                    interpret=self.interpret))
        attn = attend(q, with_prefix(k_pool, k), with_prefix(v_pool, v),
                      causal=True, kv_offset=start, **kw)
        return self._prefill_out(attn, gate, x.dtype, params), (k, v)

    def decode(self, x, params, kv_cache, offset, kv_scales=None):
        """x: (B/world... ) decode step with B*1 tokens: x is
        (B/world rows? ) — following the reference, decode activations
        are M=B-sharded; B must divide world or be replicated.

        Here: x (B_loc, hidden) with B_loc = B/world when B >= world,
        else x replicated (B, hidden) and mode falls back to gather.
        kv_cache: (k, v) each (B, Hkv_loc, S_max, D); offset: (B,) int32
        current lengths (same on all ranks).  With ``kv_scales``
        ((k_scale, v_scale), each (B, Hkv_loc, S_max) f32) the cache is
        int8 and the new token is quantized on write.
        Returns (out like x, updated cache, updated scales or None)."""
        assert not self.gate and self.rope, (
            "the gate and NoPE are built for the prefill and the paged "
            "decode")
        k_cache, v_cache = kv_cache
        b = k_cache.shape[0]
        qkv = self._project_qkv(x, params)          # (B, qkv_cols)
        q, k, v = self._split_heads(qkv, b, 1)
        if self.qk_norm:
            q = rms_norm(q, params["q_norm"])
            k = rms_norm(k, params["k_norm"])
        cos, sin = rope_cos_sin(offset, self.head_dim, self.rope_theta)

        def rope1(x):  # x: (B, H, 1, D); cos/sin: (B, D/2)
            d2 = x.shape[-1] // 2
            c = cos[:, None, None, :].astype(jnp.float32)
            s = sin[:, None, None, :].astype(jnp.float32)
            x1, x2 = x[..., :d2], x[..., d2:]
            return jnp.concatenate(
                [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)

        q = rope1(q)
        k = rope1(k)

        # scatter new kv at offset (quantizing first for int8 caches)
        assert (kv_scales is not None) == (k_cache.dtype == jnp.int8), (
            "int8 caches require kv_scales (and float caches must not "
            "pass them)")
        k_sc = v_sc = None
        if kv_scales is not None:
            from triton_distributed_tpu.kernels.flash_decode import (
                quantize_kv)

            k_sc, v_sc = kv_scales
            # Same scheme as the prefill write path (quantize_kv).
            k, v, kscale_new, vscale_new = quantize_kv(k, v)
            k_sc = jax.vmap(
                lambda c, u, o: jax.lax.dynamic_update_slice(
                    c, u, (0, o)))(k_sc, kscale_new, offset)
            v_sc = jax.vmap(
                lambda c, u, o: jax.lax.dynamic_update_slice(
                    c, u, (0, o)))(v_sc, vscale_new, offset)
        k_cache = jax.vmap(
            lambda c, u, o: jax.lax.dynamic_update_slice(
                c, u, (0, o, 0)))(k_cache, k.astype(k_cache.dtype), offset)
        v_cache = jax.vmap(
            lambda c, u, o: jax.lax.dynamic_update_slice(
                c, u, (0, o, 0)))(v_cache, v.astype(v_cache.dtype), offset)

        out, _ = flash_decode(q.reshape(b, self.h_loc, self.head_dim),
                              k_cache, v_cache, offset + 1,
                              k_scale=k_sc, v_scale=v_sc,
                              interpret=self.interpret)
        attn = out.reshape(b, self.h_loc * self.head_dim)
        out_x = self._out_proj(attn, x.dtype, params)
        scales = (k_sc, v_sc) if kv_scales is not None else None
        return out_x, (k_cache, v_cache), scales

    def decode_paged(self, x, params, kv_pools, page_table, offset,
                     kv_scales=None):
        """Paged `decode`: the KV lives in a page pool
        (`models.kv_cache.PagedKVCache` layout — (P, Hkv_loc, page, D)
        per pool) addressed through ``page_table`` ((B, T) int32).
        The new token's KV is scattered into
        ``page_table[b, offset // page]`` at row ``offset % page``
        (masked rows' NULL-mapped writes land in the reserved trash
        page) and attention runs the page-table-indexed split-KV
        kernel (`flash_decode_paged`).  Same projections, rope and
        int8 quantize-on-write as the dense path.

        The write goes through `kv_cache.write_token_rows`, which
        indexes page, head and row explicitly: with the heads left as
        a slice the compiled step copied every pool twice (a layout
        change around the scatter; PERF.md section 6, PR 29)."""
        # `models` imports this package: bind at call time
        from triton_distributed_tpu.models.kv_cache import (
            write_token_rows)

        k_pool, v_pool = kv_pools
        b = offset.shape[0]
        ps = k_pool.shape[2]
        qkv, gate = self._split_gate(
            self._project_qkv(x, params))           # (B, qkv_cols)
        q, k, v = self._split_heads(qkv, b, 1)
        if self.qk_norm:
            q = rms_norm(q, params["q_norm"])
            k = rms_norm(k, params["k_norm"])
        if self.rope:
            cos, sin = rope_cos_sin(offset, self.head_dim,
                                    self.rope_theta)
            # x: (B, H, 1, D); cos/sin: (B, D/2)
            c = cos[:, None, None, :].astype(jnp.float32)
            s = sin[:, None, None, :].astype(jnp.float32)
            q = self._rotate(q, c, s)
            k = self._rotate(k, c, s)

        assert (kv_scales is not None) == (k_pool.dtype == jnp.int8), (
            "int8 pools require kv_scales (and float pools must not "
            "pass them)")
        assert not (self.window and kv_scales), "a window over int8 pools"
        bidx = jnp.arange(b)
        phys = page_table[bidx, offset // ps]       # (B,)
        within = offset % ps
        k_sc = v_sc = None
        if kv_scales is not None:
            from triton_distributed_tpu.kernels.flash_decode import (
                quantize_kv)

            k_sc, v_sc = kv_scales
            k, v, kscale_new, vscale_new = quantize_kv(k, v)
            k_sc = write_token_rows(k_sc, phys, within,
                                    kscale_new[:, :, 0])
            v_sc = write_token_rows(v_sc, phys, within,
                                    vscale_new[:, :, 0])
        k_pool = write_token_rows(k_pool, phys, within, k[:, :, 0])
        v_pool = write_token_rows(v_pool, phys, within, v[:, :, 0])

        out, _ = flash_decode_paged(
            q.reshape(b, self.h_loc, self.head_dim), k_pool, v_pool,
            page_table, offset + 1, k_scale=k_sc, v_scale=v_sc,
            interpret=self.interpret,
            **(dict(window=self.window, name=WINDOW_KERNELS["decode"])
               if self.window else {}))
        attn = out.reshape(b, self.h_loc * self.head_dim)
        if gate is not None:
            attn = (attn * gate).astype(x.dtype)
        out_x = self._out_proj(attn, x.dtype, params)
        scales = (k_sc, v_sc) if kv_scales is not None else None
        return out_x, (k_pool, v_pool), scales

    def block_paged(self, x, params, kv_pools, page_table, cursor,
                    active, folded):
        """One pass over TWO block-widths a row (``2 * self.block``
        positions): the FRONT half is the block the row has just
        finished (every token final), the BACK half its block in
        flight.  x ``(B * 2 * block, hidden)``, a row's positions
        together, front first; ``cursor`` (B,) int32 the row's first
        position whose K/V is not final yet (a multiple of ``block``,
        so a block never straddles a page); ``active``, ``folded`` (B,)
        bool.  A ``folded`` row's front half stands at ``cursor`` and
        its back half a block further on; a row whose previous block is
        committed already (not ``folded``) has a DEAD front half — it
        stands on the committed block below the cursor, writes nothing
        and its output means nothing — and its back half at ``cursor``.

        The K/V of both halves goes into the mapped pages BEFORE
        attention: the front half's is FINAL (this is the finished
        block's commit: it rides on the next block's pass), the back
        half's provisional — rows above the cursor that every later
        pass of the block overwrites; a dead front half's and an
        inactive row's go to the trash page.  Then the front queries
        see the keys up to the end of their own block and the back
        queries those and their own block's — block-causal over two
        blocks — in ONE call of `flash_decode_paged` at ``G * 2 *
        block`` query rows a KV head, the front half's first, with the
        back block's keys hidden from them (``front_hidden``), so the
        pages are read once.  Returns (out like x, updated pools)."""
        from triton_distributed_tpu.models.kv_cache import (
            NULL_PAGE, write_token_rows)

        assert self.block > 1 and not self.gate, (self.block, self.gate)
        k_pool, v_pool = kv_pools
        b, n = cursor.shape[0], self.block
        w = 2 * n
        ps = k_pool.shape[2]
        d, hkv, g = self.head_dim, self.hkv_loc, self.h_loc // self.hkv_loc
        q, k, v = self._split_heads(self._project_qkv(x, params), b, w)
        if self.qk_norm:
            q = rms_norm(q, params["q_norm"])
            k = rms_norm(k, params["k_norm"])
        first = jnp.where(folded, cursor, cursor - n)
        place = jnp.arange(w, dtype=jnp.int32)
        # (a dead front half below position 0 — a prompt shorter than
        # a block — is no position: it is written nowhere, sees nothing)
        pos = jnp.maximum(first[:, None] + place, 0)            # (B, 2n)
        if self.rope:
            cos, sin = rope_cos_sin(pos.reshape(-1), d, self.rope_theta)
            cos = cos.reshape(b, 1, w, d // 2)
            sin = sin.reshape(b, 1, w, d // 2)

            def rope_rows(x_):      # x_: (B, H, 2n, D)
                x1, x2 = x_[..., :d // 2], x_[..., d // 2:]
                return jnp.concatenate(
                    [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                    axis=-1).astype(x_.dtype)

            q = rope_rows(q)
            k = rope_rows(k)
        written = active[:, None] & (folded[:, None] | (place >= n))
        phys = jnp.take_along_axis(page_table, pos // ps, axis=1)
        phys = jnp.where(written, phys, NULL_PAGE).reshape(-1)
        within = (pos % ps).reshape(-1)

        def rows(t):                # (B, Hkv, 2n, D) -> (B * 2n, Hkv, D)
            return t.transpose(0, 2, 1, 3).reshape(b * w, hkv, d)

        k_pool = write_token_rows(k_pool, phys, within, rows(k))
        v_pool = write_token_rows(v_pool, phys, within, rows(v))
        # head h reads KV head h // G: a KV head's queries together,
        # the front half's G * n ahead of the back half's
        q = q.reshape(b, hkv, g, 2, n, d).transpose(0, 1, 3, 2, 4, 5)
        out, _ = flash_decode_paged(
            q.reshape(b, hkv * 2 * g * n, d), k_pool, v_pool, page_table,
            first + w, front_hidden=n, interpret=self.interpret)
        attn = out.reshape(b, hkv, 2, g, n, d).transpose(0, 2, 4, 1, 3, 5)
        out_x = self._out_proj(attn.reshape(b * w, -1), x.dtype, params)
        return out_x, (k_pool, v_pool)
