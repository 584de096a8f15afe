"""`WindowAndFullLayers`: a `ServedModel` whose attention layers are of
TWO kinds — ``sliding_attention`` layers that see the last ``window``
tokens and ``full_attention`` layers that see everything — each kind's
K/V in pools of its own (`models.kv_cache`): the full layers' pages in
``ks`` / ``vs`` behind ``page_table``, the window layers' in ``wks`` /
``wvs`` behind ``window_table``, a pool sized by slots x window whose
pages behind a row's window the page manager takes back as the row
grows (`serving.pages`).

This is the ONE home of that plumbing: which pool a layer's rows go to
and which index it has there, and the attention layer of each kind
(`_set_layer_kinds`), the cache's
declaration (`cache_layout`), and the three per-device programs that
walk the layers by kind (`prefill_shard`, `prefill_shard_suffix`,
`decode_shard`).  A family (`models.cohere2_moe`, `models.smallthinker`)
writes what is its own: its parameters, its block — the three layer
bodies — and its head:

- ``_layer_fwd_prefill(x, lp, *, batch, kind) -> (x, (k, v))``;
- ``_layer_fwd_suffix(x, lp, kept, page_ids, start, *, kind) -> (x,
  (k, v))`` — ``kept`` the layer's (k pool, v pool) of ITS kind, read
  and not written, ``page_ids`` the sequence's pages in that pool;
- ``_layer_fwd_decode(x, lp, kept, table, offset, *, kind) -> (x,
  pools, the expert layer's counters)``;
- ``_logits(x, params) -> float32 logits``.

The order of the kinds in a period is the family's (window layers first
or a full layer first): nothing here reads it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax.numpy as jnp

from triton_distributed_tpu.kernels.matmul import MatmulConfig
from triton_distributed_tpu.layers.tp_attn import TPAttention
from triton_distributed_tpu.models.base import ServedModel
from triton_distributed_tpu.models.kv_cache import KVCache, PagedKVCache

__all__ = ["WindowAndFullLayers", "SLIDING", "FULL"]

#: A layer's kind, as the published ``layer_types`` write it.
SLIDING, FULL = "sliding_attention", "full_attention"


class WindowAndFullLayers(ServedModel):

    def _set_layer_kinds(self, kinds, window: int,
                         gemm: Optional[MatmulConfig] = None) -> None:
        """``kinds``: one of `SLIDING` / `FULL` a layer; ``window``:
        the tokens a window layer sees back.  Makes ``self.attn``, the
        attention layer of each kind from the config's heads: a window
        layer rotates (``config.rope_pairs`` says which pairs) and sees
        ``window`` tokens, a full layer has no positions."""
        kinds = tuple(kinds)
        assert len(kinds) == self.config.num_layers, (kinds, self.config)
        assert FULL in kinds and set(kinds) <= {SLIDING, FULL}, kinds
        assert SLIDING not in kinds or window > 0
        assert not self.config.quantize_kv_cache, (
            "no int8 pool under a window")
        self.layer_kinds = kinds
        #: Tokens a window layer sees back (0: the cut kept none): the
        #: page manager keeps that layer kind's pages by it.
        self.window = window if SLIDING in kinds else 0
        #: Each layer's place among the layers of its kind: the index
        #: of its pools (``wks`` / ``wvs``, or ``ks`` / ``vs``).
        self._index = [kinds[:i].count(k) for i, k in enumerate(kinds)]
        self.num_window, self.num_full = kinds.count(SLIDING), kinds.count(FULL)
        config = self.config
        attention = functools.partial(
            TPAttention, axis=self.axis, world_size=1,
            hidden=config.hidden_size, num_heads=config.num_heads,
            num_kv_heads=config.num_kv_heads, head_dim=config.head_dim,
            rope_theta=config.rope_theta, qk_norm=False, mode=self.mode,
            gemm=gemm or MatmulConfig(), interpret=self.interpret)
        self.attn = {
            SLIDING: attention(rope=True, rope_pairs=config.rope_pairs,
                               window=window),
            FULL: attention(rope=False)}

    def cache_layout(self) -> dict:
        """Pages of the full layers, and pages of their own for the
        window layers."""
        return dict(num_layers=self.num_full,
                    window_layers=self.num_window)

    # ------------------------------------------------------------------
    # per-device programs (called inside shard_map)
    # ------------------------------------------------------------------

    @staticmethod
    def _put(cache, kind, i, k, v):
        """A prefill's rows of layer ``i`` of its kind."""
        return (cache.write_window(i, k, v) if kind == SLIDING
                else cache.write_prefill(i, k, v))

    def prefill_shard(self, params, input_ids, cache: Optional[KVCache]):
        """input_ids: (B, S).  Returns (logits (B, V) float32 of each
        sequence's last position, cache)."""
        b, s = input_ids.shape
        x = params["embed"][input_ids].reshape(b * s, -1)
        layer = self._per_layer(self._layer_fwd_prefill, batch=b)
        for li, (kind, lp) in enumerate(zip(self.layer_kinds,
                                            params["layers"])):
            x, kept = layer[kind](x, lp)
            if cache is not None:
                cache = self._put(cache, kind, self._index[li], *kept)
        logits = self._logits(x.reshape(b, s, -1)[:, -1], params)
        if cache is not None:
            cache = cache.set_offset(s)
        return logits, cache

    def prefill_shard_suffix(self, params, input_ids, start,
                             cache: KVCache, pools, page_ids):
        """One chunk of one prompt.  input_ids: (1, C), the tokens at
        positions ``start + arange(C)`` (a last chunk right-padded);
        ``cache``: the single-row cache of `create_cache`, C long;
        ``pools``: the paged cache's (ks, vs, wks, wvs), read and not
        written; ``page_ids`` (2, T): the sequence's pages in logical
        order — row 0 in the full layers' pools, row 1 in the window
        layers' (NULL where a page went back).  Returns ``cache``
        holding the chunk's K/V rows of every layer at LOCAL positions
        [0, C): the paged insert puts each kind's into its own pages.
        No logits: the first decode step recomputes the prompt's last
        position."""
        b, s = input_ids.shape
        assert b == 1, "a chunk is one sequence's"
        ks, vs, wks, wvs = pools
        x = params["embed"][input_ids].reshape(s, -1)
        start = jnp.asarray(start, jnp.int32).reshape(())
        layer = self._per_layer(self._layer_fwd_suffix)
        for li, (kind, lp) in enumerate(zip(self.layer_kinds,
                                            params["layers"])):
            i = self._index[li]
            kept, ids = (((wks[i], wvs[i]), page_ids[1])
                         if kind == SLIDING else
                         ((ks[i], vs[i]), page_ids[0]))
            x, kept = layer[kind](x, lp, kept, ids, start)
            cache = self._put(cache, kind, i, *kept)
        return cache.set_offset(s)

    def decode_shard(self, params, tokens, cache: PagedKVCache):
        """One decode step.  tokens: (B,).  Returns (logits (B, V),
        cache) — the cache's `stats` hold what the step counted
        (`STATS`: the pairs and the experts hit summed over the layers,
        the busiest expert's share in the worst, whatever a held share
        counts beside them summed)."""
        x = params["embed"][tokens]
        layer = self._per_layer(self._layer_fwd_decode)
        counted = []
        for li, (kind, lp) in enumerate(zip(self.layer_kinds,
                                            params["layers"])):
            i = self._index[li]
            if kind == SLIDING:
                x, kept, stats = layer[kind](
                    x, lp, (cache.wks[i], cache.wvs[i]),
                    cache.window_table, cache.offset)
                cache = cache.set_window_layer(i, *kept)
            else:
                x, kept, stats = layer[kind](
                    x, lp, (cache.ks[i], cache.vs[i]), cache.page_table,
                    cache.offset)
                cache = cache.set_layer(i, *kept)
            counted.append(stats)
        logits = self._logits(x, params)
        if cache.stats is not None:
            c = jnp.stack(counted)                      # (layers, stats)
            cache = dataclasses.replace(cache, stats=jnp.concatenate(
                [c[:, :2].sum(axis=0), c[:, 2:3].max(axis=0),
                 c[:, 3:].sum(axis=0)]))
        return logits, cache.inc_offset(1)
