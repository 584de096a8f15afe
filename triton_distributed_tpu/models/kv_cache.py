"""Static KV cache with offset tracking.

Reference: `python/triton_dist/models/kv_cache.py` (`KV_Cache:29-66`) —
per-layer static tensors + `inc_offset`.

TPU: a pytree of per-layer (k, v) arrays with a shared offset vector;
updates are functional (`jax.lax.dynamic_update_slice`) and the whole
cache is donated through the jitted decode step, so XLA updates it in
place — the role CUDA graphs + in-place writes play in the reference.

Both layouts hold either kind of layer state.  An ordinary layer keeps
K and V per KV head (``ks`` and ``vs``).  A LATENT layer (MLA,
`layers.mla_attn`) keeps ONE row a token — the normalised latent and
the rotated shared key, zero-padded to a lane multiple — that serves
as K and as V for every head: ``ks[l]`` is ``(B | P, 1, S | page, R)``
and ``vs`` is None.  Everything that walks a cache (insert, spill,
byte accounting) walks ``ks`` and, where there is one, ``vs``.

A third kind of layer keeps no row a token at all: a RECURRENT layer
(`layers.kda_attn`) holds a fixed-size state a sequence — ``states[i]``
``(B, H, dk, dv)`` float32 and the short convolution's last inputs
``convs[i]`` ``(B, (taps - 1) * channels)`` — indexed by batch row (slot)
on the leading dimension in both layouts.  A STATE-SPACE layer
(`layers.mamba2_mixer`) is recurrent in the same way and rides the same
two lists with shapes of its own (``state_shapes``): ``(B, H / 2, N,
2 P)`` float32 — two heads' ``(P, N)`` states side by side
(`kernels.mamba2.pair_state`) — and ``(B, (taps - 1) * channels)`` over
the channels of x, B and C.  A hybrid model's cache holds both kinds:
``ks`` / ``vs`` list its attention layers, ``states`` / ``convs`` its
recurrent ones, each in layer order; a layer that is a feed-forward
alone owns nothing here; pages, the page table and the radix cache
concern the attention layers alone.

A fourth kind keeps rows a token, but only a sequence's LAST ones: a
SLIDING-WINDOW attention layer (`layers.tp_attn.TPAttention.window`).
Its K and V ride in lists of their own, ``wks`` / ``wvs`` — in the
paged layout pools of their own page count, sized by slots x (window +
slack) and not by tokens, behind a page table of their own
(``window_table``, indexed by the same logical page as ``page_table``;
`NULL_PAGE` where a page behind the window went back to the pool:
`serving.pages`).  ``ks`` / ``vs`` then list the model's FULL attention
layers alone.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    ks: List[jnp.ndarray]          # per layer: (B, Hkv_loc, S_max, D)
    vs: Optional[List[jnp.ndarray]]    # None: latent rows (K is V)
    offset: jnp.ndarray            # (B,) int32 — filled length
    #: Per-token dequant scales (B, Hkv_loc, S_max) f32 per layer when
    #: the cache is int8-quantized (see `kernels.flash_decode`:
    #: quantize_kv / flash_decode's k_scale/v_scale); None = float
    #: cache.  Int8 halves both the cache footprint and decode's KV
    #: streaming bytes (measured 1.6–1.66× faster decode).
    kss: Optional[List[jnp.ndarray]] = None
    vss: Optional[List[jnp.ndarray]] = None
    #: Recurrent layers' state (module docstring); None: there are none.
    states: Optional[List[jnp.ndarray]] = None
    convs: Optional[List[jnp.ndarray]] = None
    #: (B,) int32, read by a prefill whose model has recurrent layers:
    #: the tokens of each row its state is to absorb (a bucket's padded
    #: tail must not reach it; attention simply masks the tail later).
    #: `create` says the whole row.
    length: Optional[jnp.ndarray] = None
    #: Sliding-window layers' rows, shaped like ``ks`` / ``vs`` (module
    #: docstring); None: there are none.
    wks: Optional[List[jnp.ndarray]] = None
    wvs: Optional[List[jnp.ndarray]] = None

    @property
    def quantized(self) -> bool:
        return self.kss is not None

    @classmethod
    def create(cls, num_layers: int, batch: int, num_kv_heads: int,
               max_seq: int, head_dim: int, dtype=jnp.bfloat16,
               quantized: bool = False, latent: bool = False,
               state_shapes=None, window_layers: int = 0):
        """``latent``: one ``head_dim``-wide row a token and no V
        (``num_kv_heads`` must be 1).  ``state_shapes``: a recurrent
        layer's (state, conv) shapes a row, one pair a layer.
        ``window_layers``: sliding-window layers (``num_layers`` counts
        the others)."""
        assert not latent or (num_kv_heads == 1 and not quantized)
        assert not window_layers or not (latent or quantized)
        shape = (batch, num_kv_heads, max_seq, head_dim)
        if quantized:
            dtype = jnp.int8
        states, convs = (_state_pools(batch, state_shapes, dtype)
                         if state_shapes else (None, None))
        windows = lambda: (                               # noqa: E731
            [jnp.zeros(shape, dtype) for _ in range(window_layers)]
            if window_layers else None)
        return cls(
            wks=windows(), wvs=windows(),
            states=states, convs=convs,
            length=(jnp.full((batch,), max_seq, jnp.int32)
                    if state_shapes else None),
            ks=[jnp.zeros(shape, dtype) for _ in range(num_layers)],
            vs=(None if latent else
                [jnp.zeros(shape, dtype) for _ in range(num_layers)]),
            offset=jnp.zeros((batch,), jnp.int32),
            kss=([jnp.zeros(shape[:3], jnp.float32)
                  for _ in range(num_layers)] if quantized else None),
            vss=([jnp.zeros(shape[:3], jnp.float32)
                  for _ in range(num_layers)] if quantized else None),
        )

    def write_prefill(self, layer: int, k, v=None):
        """k/v: (B, Hkv, S, D) float — fill from position 0
        (quantizing on write when the cache is int8); a latent cache
        takes its rows as ``k`` alone."""
        ks = list(self.ks)
        if self.vs is None:
            ks[layer] = jax.lax.dynamic_update_slice(
                self.ks[layer], k.astype(self.ks[layer].dtype),
                (0, 0, 0, 0))
            return dataclasses.replace(self, ks=ks)
        vs = list(self.vs)
        if self.quantized:
            from triton_distributed_tpu.kernels.flash_decode import (
                quantize_kv)

            k_q, v_q, kscale, vscale = quantize_kv(k, v)
            kss = list(self.kss)
            vss = list(self.vss)
            ks[layer] = jax.lax.dynamic_update_slice(
                self.ks[layer], k_q, (0, 0, 0, 0))
            vs[layer] = jax.lax.dynamic_update_slice(
                self.vs[layer], v_q, (0, 0, 0, 0))
            kss[layer] = jax.lax.dynamic_update_slice(
                self.kss[layer], kscale, (0, 0, 0))
            vss[layer] = jax.lax.dynamic_update_slice(
                self.vss[layer], vscale, (0, 0, 0))
            return dataclasses.replace(self, ks=ks, vs=vs, kss=kss,
                                       vss=vss)
        ks[layer] = jax.lax.dynamic_update_slice(
            self.ks[layer], k.astype(self.ks[layer].dtype), (0, 0, 0, 0))
        vs[layer] = jax.lax.dynamic_update_slice(
            self.vs[layer], v.astype(self.vs[layer].dtype), (0, 0, 0, 0))
        return dataclasses.replace(self, ks=ks, vs=vs)

    def set_layer(self, layer: int, k, v=None, kscale=None, vscale=None):
        ks = list(self.ks)
        ks[layer] = k
        rep = dict(ks=ks)
        if self.vs is not None:
            vs = list(self.vs)
            vs[layer] = v
            rep["vs"] = vs
        if kscale is not None:
            kss = list(self.kss)
            vss = list(self.vss)
            kss[layer] = kscale
            vss[layer] = vscale
            rep.update(kss=kss, vss=vss)
        return dataclasses.replace(self, **rep)

    def write_window(self, layer: int, k, v):
        """`write_prefill` for the ``layer``-th sliding-window layer."""
        return _with_window(self, layer, *(
            jax.lax.dynamic_update_slice(
                dst[layer], src.astype(dst[layer].dtype), (0, 0, 0, 0))
            for dst, src in ((self.wks, k), (self.wvs, v))))

    def set_state(self, layer: int, state, conv):
        """The ``layer``-th recurrent layer's state and inputs."""
        return _with_state(self, layer, state, conv)

    def inc_offset(self, n: int = 1):
        return dataclasses.replace(self, offset=self.offset + n)

    def reset_slot(self, b):
        """Free batch row ``b`` for reuse: zero its offset.  The K/V
        data itself is left in place — a slot is semantically empty
        when its offset is 0 (every attention path masks positions
        ``>= offset``), and the next `insert_prefill` overwrites the
        row anyway, so re-zeroing HBM here would be pure waste."""
        return dataclasses.replace(
            self, offset=self.offset.at[b].set(0))

    def bytes_per_slot(self) -> int:
        """HBM bytes one batch row pins across all layers — the unit
        the serving scheduler's KV admission budget is counted in.
        Covers K+V (and the per-token dequant scales when the cache is
        int8-quantized)."""
        total = sum(math.prod(x.shape[1:]) * x.dtype.itemsize
                    for x in self.ks + (self.vs or []))
        if self.quantized:
            for ks_, vs_ in zip(self.kss, self.vss):
                per_row = ks_.shape[1] * ks_.shape[2]
                total += per_row * (ks_.dtype.itemsize
                                    + vs_.dtype.itemsize)
        return total

    def set_offset(self, value):
        return dataclasses.replace(
            self, offset=jnp.broadcast_to(
                jnp.asarray(value, jnp.int32), self.offset.shape))


# ---------------------------------------------------------------------------
# Paged layout
# ---------------------------------------------------------------------------

#: Physical page 0 is reserved as the NULL/trash page: unmapped page-
#: table entries point at it, and writes that must be discarded (a
#: shared prefix page the writer may not touch, a masked slot's frozen-
#: offset write) are directed at it.  Its contents are garbage by
#: design and are never read unmasked.
NULL_PAGE = 0


def pages_for(tokens: int, page_size: int) -> int:
    """Pages needed to hold ``tokens`` KV positions."""
    return -(-int(tokens) // int(page_size)) if tokens > 0 else 0


def _state_pools(batch: int, state_shapes, dtype):
    """(states, convs) of zeros for ``batch`` rows."""
    return ([jnp.zeros((batch, *st), jnp.float32)
             for st, _ in state_shapes],
            [jnp.zeros((batch, *cv), dtype) for _, cv in state_shapes])


def _with_state(cache, layer: int, state, conv):
    states = list(cache.states)
    convs = list(cache.convs)
    states[layer] = state
    convs[layer] = conv.astype(convs[layer].dtype)
    return dataclasses.replace(cache, states=states, convs=convs)


def _with_window(cache, layer: int, k, v):
    wks, wvs = list(cache.wks), list(cache.wvs)
    wks[layer], wvs[layer] = k, v
    return dataclasses.replace(cache, wks=wks, wvs=wvs)


def zero_state_rows(states, convs, b):
    """(states, convs) of a cache with row (slot) ``b`` zeroed where it
    lies — run it jitted with both lists donated
    (`serving.pages.PagedKV.release`): eagerly it would copy every pool
    whole."""
    def zero(pool):
        return jax.lax.dynamic_update_slice_in_dim(
            pool, jnp.zeros((1, *pool.shape[1:]), pool.dtype), b, axis=0)
    return [zero(x) for x in states], [zero(x) for x in convs]


def write_token_rows(pool, phys, within, rows):
    """One decode step's rows into a page pool, where the pool lies.

    ``pool``: (P, H, page, D) values or (P, H, page) scales; ``phys``,
    ``within``: (B,) int32 — the physical page and the row inside it
    of each batch row's new token; ``rows``: (B, H, D) / (B, H).

    The head index is WRITTEN OUT so that the three scattered
    dimensions (page, head, row) are the pool's leading ones and the
    window is ``D`` alone.  ``pool.at[phys, :, within]`` names the
    same elements, but its window dimension (the heads) lies between
    the two scattered ones; XLA's TPU scatter wants windows minor, so
    it copied the whole pool into another layout and back around the
    scatter — two pool-sized copies a pool a layer a step, although
    the donated buffer was aliased (PERF.md, PR 29).  Duplicate
    targets are allowed (masked rows all land in the trash page), so
    the scatter does not promise unique indices.
    """
    heads = jnp.arange(pool.shape[1])
    return pool.at[phys[:, None], heads[None, :], within[:, None]].set(
        rows.astype(pool.dtype))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    """Page-table-indexed KV pool: the serving-scale layout.

    Where `KVCache` pins ``batch × max_seq`` contiguous rows (every
    admitted request pays full-length KV), this cache is ONE pool of
    ``num_pages`` fixed-size pages plus a per-slot page table mapping
    logical KV block ``j`` of slot ``b`` to a physical page.  A
    sequence of length L pins only ``ceil(L / page_size)`` pages, and
    two slots may map the SAME physical page (refcounted prefix
    sharing — `serving.pages`).  This is PagedAttention's block-table
    indirection in XLA-functional form: the pool and offsets are
    donated through the jitted step exactly like `KVCache`, while the
    page table itself is host-managed (a tiny (B, T) int32 array
    re-shipped only when an allocation changes it).

    Physical page `NULL_PAGE` (0) is reserved: unmapped table entries
    and discarded writes land there, so allocation never recompiles
    and masked rows can keep "writing" harmlessly.
    """

    ks: List[jnp.ndarray]          # per layer: (P, Hkv_loc, page, D)
    vs: Optional[List[jnp.ndarray]]    # None: latent rows (K is V)
    page_table: jnp.ndarray        # (B, T) int32 — physical page ids
    offset: jnp.ndarray            # (B,) int32 — filled length
    #: Per-token dequant scales (P, Hkv_loc, page) f32 per layer when
    #: int8-quantized (same scheme as `KVCache.kss/vss`); None = float.
    kss: Optional[List[jnp.ndarray]] = None
    vss: Optional[List[jnp.ndarray]] = None
    #: What the model counted in its LAST decode step, left here so it
    #: reaches the host with the step's outputs and no dispatch of its
    #: own (a sparse model's routing counters,
    #: `models.glm4_moe_lite.MOE_STATS`); None: the model counts
    #: nothing.
    stats: Optional[jnp.ndarray] = None
    #: Recurrent layers' state a slot (module docstring): (B, H, dk,
    #: dv) — or a state-space layer's (B, H / 2, N, 2 P) — float32 and
    #: (B, (taps - 1) * channels) a layer, rewritten where they lie by
    #: every decode step.  None: there are none.
    states: Optional[List[jnp.ndarray]] = None
    convs: Optional[List[jnp.ndarray]] = None
    #: Sliding-window layers' pools (Pw, Hkv_loc, page, D) — a page
    #: count of their own — and their page table (B, T), indexed like
    #: ``page_table`` (module docstring).  None: there are none.
    wks: Optional[List[jnp.ndarray]] = None
    wvs: Optional[List[jnp.ndarray]] = None
    window_table: Optional[jnp.ndarray] = None
    #: Tokens per page — static: it shapes the compiled programs.
    page_size: int = dataclasses.field(
        default=16, metadata=dict(static=True))

    @property
    def quantized(self) -> bool:
        return self.kss is not None

    @property
    def num_pages(self) -> int:
        return int(self.ks[0].shape[0])

    @property
    def pages_per_seq(self) -> int:
        return int(self.page_table.shape[1])

    @property
    def batch(self) -> int:
        return int(self.offset.shape[0])

    @property
    def max_seq(self) -> int:
        """Logical sequence capacity of one slot (T × page_size)."""
        return self.pages_per_seq * self.page_size

    @classmethod
    def create(cls, num_layers: int, num_pages: int, batch: int,
               num_kv_heads: int, page_size: int, head_dim: int,
               max_pages_per_seq: int, dtype=jnp.bfloat16,
               quantized: bool = False, latent: bool = False,
               num_stats: int = 0, state_shapes=None,
               window_layers: int = 0, window_pages: int = 0):
        """``window_layers`` sliding-window layers share pools of
        ``window_pages`` pages (the null page among them;
        ``num_layers`` counts the others).
        ``num_pages`` INCLUDES the reserved null page 0 (usable
        pages = num_pages - 1).  ``latent``: one pool a layer of
        ``head_dim``-wide rows and no V pool (``num_kv_heads`` 1).
        ``num_stats``: width of the model's `stats` vector.
        ``state_shapes``: a recurrent layer's (state, conv) shapes a
        slot, one pair a layer (``num_layers`` counts the others)."""
        assert num_pages >= 2, "need >= 1 usable page beside NULL_PAGE"
        assert not latent or (num_kv_heads == 1 and not quantized)
        shape = (num_pages, num_kv_heads, page_size, head_dim)
        if quantized:
            dtype = jnp.int8
        states, convs = (_state_pools(batch, state_shapes, dtype)
                         if state_shapes else (None, None))
        assert not window_layers or (window_pages >= 2
                                     and not (latent or quantized))
        windows = lambda: (                               # noqa: E731
            [jnp.zeros((window_pages, *shape[1:]), dtype)
             for _ in range(window_layers)] if window_layers else None)
        return cls(
            wks=windows(), wvs=windows(),
            window_table=(jnp.zeros((batch, max_pages_per_seq), jnp.int32)
                          if window_layers else None),
            states=states, convs=convs,
            ks=[jnp.zeros(shape, dtype) for _ in range(num_layers)],
            vs=(None if latent else
                [jnp.zeros(shape, dtype) for _ in range(num_layers)]),
            stats=(jnp.zeros((num_stats,), jnp.float32) if num_stats
                   else None),
            page_table=jnp.zeros((batch, max_pages_per_seq), jnp.int32),
            offset=jnp.zeros((batch,), jnp.int32),
            kss=([jnp.zeros(shape[:3], jnp.float32)
                  for _ in range(num_layers)] if quantized else None),
            vss=([jnp.zeros(shape[:3], jnp.float32)
                  for _ in range(num_layers)] if quantized else None),
            page_size=page_size,
        )

    def bytes_per_page(self) -> int:
        """HBM bytes one physical page pins across all layers — the
        unit the paged serving scheduler's admission budget is counted
        in.  Unlike `KVCache.bytes_per_slot` (which prices a request
        at max-context worst case), a request costs
        ``pages_for(len) * bytes_per_page`` — its TRUE footprint."""
        total = sum(math.prod(x.shape[1:]) * x.dtype.itemsize
                    for x in self.ks + (self.vs or []))
        if self.quantized:
            for ks_, vs_ in zip(self.kss, self.vss):
                per_page = ks_.shape[1] * ks_.shape[2]
                total += per_page * (ks_.dtype.itemsize
                                     + vs_.dtype.itemsize)
        return total

    def window_bytes_per_page(self) -> int:
        """HBM bytes one page of the sliding-window layers' pools pins
        across those layers (0: the model has none)."""
        return sum(math.prod(x.shape[1:]) * x.dtype.itemsize
                   for x in (self.wks or []) + (self.wvs or []))

    def state_bytes_per_slot(self) -> int:
        """HBM bytes one slot's recurrent state pins across all layers
        whatever the sequence's length (0: no recurrent layer)."""
        return sum(math.prod(x.shape[1:]) * x.dtype.itemsize
                   for x in (self.states or []) + (self.convs or []))

    @property
    def live_rows(self):
        """(B,) bool: the rows that hold a sequence — a slot's first
        logical page is mapped from its insert to its release."""
        return self.page_table[:, 0] != NULL_PAGE

    def set_state(self, layer: int, state, conv):
        """The ``layer``-th recurrent layer's state and inputs."""
        return _with_state(self, layer, state, conv)

    def set_layer(self, layer: int, k, v=None, kscale=None, vscale=None):
        ks = list(self.ks)
        ks[layer] = k
        rep = dict(ks=ks)
        if self.vs is not None:
            vs = list(self.vs)
            vs[layer] = v
            rep["vs"] = vs
        if kscale is not None:
            kss = list(self.kss)
            vss = list(self.vss)
            kss[layer] = kscale
            vss[layer] = vscale
            rep.update(kss=kss, vss=vss)
        return dataclasses.replace(self, **rep)

    def set_window_layer(self, layer: int, k, v):
        """The ``layer``-th sliding-window layer's pools."""
        return _with_window(self, layer, k, v)

    def inc_offset(self, n: int = 1):
        return dataclasses.replace(self, offset=self.offset + n)

    def reset_slot(self, b):
        """Zero slot ``b``'s offset.  The page-table row is host-
        managed (`serving.pages.PagedKV.release` resets it to
        NULL_PAGE before the next dispatch) — an offset of 0 already
        masks every position.  (A recurrent layer's state has no such
        mask: `zero_state_rows` clears it.)"""
        return dataclasses.replace(
            self, offset=self.offset.at[b].set(0))

    def set_offset(self, value):
        return dataclasses.replace(
            self, offset=jnp.broadcast_to(
                jnp.asarray(value, jnp.int32), self.offset.shape))

    def with_page_table(self, table, window_table=None):
        """Rebind the page table (host mirror → device) without
        touching the donated pool buffers — and, where there are
        sliding-window layers, theirs."""
        rep = dict(page_table=jnp.asarray(table, jnp.int32))
        if window_table is not None:
            rep["window_table"] = jnp.asarray(window_table, jnp.int32)
        return dataclasses.replace(self, **rep)

    def gather_logical(self, layer: int):
        """Debug/test helper: reassemble the logical (B, Hkv, T*page,
        D) view of ``layer`` through the page table.  NOT for the hot
        path — decode reads through the table in-kernel."""
        b = self.batch

        def logical(pool):
            x = pool[self.page_table]          # (B, T, Hkv, page, D)
            return jnp.moveaxis(x, 2, 1).reshape(
                b, x.shape[2], -1, x.shape[-1])

        k = logical(self.ks[layer])
        return k, (logical(self.vs[layer]) if self.vs is not None
                   else None)
