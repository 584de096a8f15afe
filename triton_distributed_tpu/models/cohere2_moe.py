"""Cohere2-MoE family (`model_type` ``cohere2_moe``): window and full
attention mixed in one model, in a PARALLEL block.

    u = LayerNorm(h);  h <- h + Attn_l(u) + FFN_l(u)

ONE mean-subtracting, weight-only LayerNorm a layer
(`layers.tp_attn.layer_norm`) feeds both readers, and both are added to
the residual.  ``Attn_l`` is named by ``config.layer_types[l]``:
``sliding_attention`` — grouped-query attention that rotates ADJACENT
pairs and sees the last ``sliding_window`` tokens (`TPAttention` with
``rope_pairs`` and ``window``); ``full_attention`` — causal, NO
positional encoding.  ``FFN_l`` is a sparse feed-forward
(`layers.moe_mlp.SparseMoE`): sigmoid scores with no selection bias,
the top-k renormalised, the ``n_shared_experts`` AVERAGED, told which
experts of the layer this chip holds (``config.experts_held``).  Final
LayerNorm; the head is the embedding, tied, over the rows of the
vocabulary this chip holds, times ``logit_scale``.

It stands behind the entry points the scheduler calls on the other
families (`make_prefill_fn`, `make_paged_decode_fn`,
`make_prefill_suffix_fn`, `create_paged_cache`, `create_cache`).  Its
cache holds TWO kinds of attention state (`models.kv_cache`): pages of
the full layers in ``ks`` / ``vs`` behind ``page_table``, and pages of
the window layers in ``wks`` / ``wvs`` behind ``window_table`` — a
pool sized by slots x window, whose pages behind a row's window the
page manager takes back as the row grows (`serving.pages`).  ``window``
tells the scheduler so; nothing else is a knob.

ONE device (``tp`` of size 1); tensor parallelism for this family (the
window pools sharded by KV head), the exchange that would make the held
expert layer expert-parallel, a window-aware prefix hit and the int8
pool under a window are not built (ROADMAP Reach).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_distributed_tpu.kernels.matmul import MatmulConfig
from triton_distributed_tpu.layers.moe_mlp import HELD_STATS, SparseMoE
from triton_distributed_tpu.layers.tp_attn import TPAttention, layer_norm
from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.models.kv_cache import KVCache, PagedKVCache

__all__ = ["Cohere2Moe", "PREFILL_CHUNK"]

#: A layer's kind, as the published ``layer_types`` write it.
SLIDING, FULL = "sliding_attention", "full_attention"

#: Tokens of a prompt the scheduler prefills between two decode steps
#: (`make_prefill_suffix_fn`).  A chunk streams the held experts once,
#: so a shorter one costs tokens a second and a longer one lengthens the
#: token gap of every running row: settled on the chip by PR 40's rule
#: (PERF.md section 5 has what 1024 and 2048 read, PR 44).
PREFILL_CHUNK = 1024


class Cohere2Moe:
    #: What a decode step leaves in the cache's `stats`: the held
    #: experts' counters summed over the layers (the busiest expert's
    #: share in the worst).
    STATS = HELD_STATS

    def __init__(self, config: ModelConfig, mesh: Mesh, axis: str = "tp",
                 mode: str = "fused", interpret: Optional[bool] = None,
                 gemm: Optional[MatmulConfig] = None):
        kinds = tuple(config.layer_types)
        assert len(kinds) == config.num_layers, (kinds, config)
        assert FULL in kinds and set(kinds) <= {SLIDING, FULL}, kinds
        assert SLIDING not in kinds or config.sliding_window > 0
        assert config.experts_held is not None, "which experts are here?"
        assert mesh.shape[axis] == 1, (
            f"{type(self).__name__} runs on one device; "
            f"{axis}={mesh.shape[axis]} is not built")
        assert not config.quantize_kv_cache, "no int8 pool under a window"
        self.config = config
        self.mesh = mesh
        self.axis = axis
        self.world = 1
        self.mode = mode
        self.interpret = interpret
        self.dtype = jnp.dtype(config.dtype)
        self.prefill_chunk = PREFILL_CHUNK
        self.kinds = kinds
        #: Tokens a window layer sees back (0: the cut kept none): the
        #: page manager keeps that layer kind's pages by it.
        self.window = config.sliding_window if SLIDING in kinds else 0
        attention = functools.partial(
            TPAttention, axis=axis, world_size=1,
            hidden=config.hidden_size, num_heads=config.num_heads,
            num_kv_heads=config.num_kv_heads, head_dim=config.head_dim,
            rope_theta=config.rope_theta, qk_norm=False, mode=mode,
            gemm=gemm or MatmulConfig(), interpret=interpret)
        self.attn = {
            SLIDING: attention(rope=True, rope_pairs=config.rope_pairs,
                               window=config.sliding_window),
            FULL: attention(rope=False)}
        self.moe = SparseMoE(
            hidden=config.hidden_size, ffn=config.moe_intermediate_size,
            num_experts=config.num_experts,
            topk=config.num_experts_per_tok,
            n_shared=config.n_shared_experts,
            routed_scaling=config.routed_scaling_factor,
            norm_topk_prob=config.norm_topk_prob, mode=mode,
            interpret=interpret, held=tuple(config.experts_held),
            shared_combine=config.moe_shared_combine,
            selection_bias=config.moe_selection_bias)
        #: Each layer's place among the layers of its kind: the index
        #: of its pools (``wks`` / ``wvs``, or ``ks`` / ``vs``).
        self._index = [kinds[:i].count(k) for i, k in enumerate(kinds)]
        self.num_window, self.num_full = kinds.count(SLIDING), kinds.count(FULL)

    # ------------------------------------------------------------------
    # parameters
    # ------------------------------------------------------------------

    def _named(self, specs):
        return jax.tree.map(
            lambda sp: NamedSharding(self.mesh, sp), specs,
            is_leaf=lambda x: isinstance(x, P))

    def _layer_specs(self):
        return {"ln": P(None),
                "attn": self.attn[FULL].global_param_specs(),
                "moe": self.moe.param_specs()}

    def param_specs(self):
        return {"embed": P(None, None),
                "layers": [self._layer_specs() for _ in self.kinds],
                "ln_f": P(None)}

    def init_params(self, key):
        """Seeded parameters, made on the device a layer at a time."""
        cfg = self.config
        h = cfg.hidden_size

        def one_layer(k):
            ka, km = jax.random.split(k)
            return {"ln": jnp.ones((h,), self.dtype),
                    "attn": self.attn[FULL].init_params(ka, self.dtype),
                    "moe": self.moe.init_params(km, self.dtype)}

        def ends(k):
            return {"embed": (jax.random.normal(k, (cfg.vocab_size, h))
                              * h ** -0.5).astype(self.dtype),
                    "ln_f": jnp.ones((h,), self.dtype)}

        keys = jax.random.split(key, cfg.num_layers + 1)
        specs = self.param_specs()
        params = jax.jit(ends, out_shardings=self._named(
            {k: specs[k] for k in ("embed", "ln_f")}))(keys[-1])
        make = jax.jit(one_layer,
                       out_shardings=self._named(self._layer_specs()))
        params["layers"] = [make(keys[i]) for i in range(cfg.num_layers)]
        return params

    # ------------------------------------------------------------------
    # per-device forward bodies (called inside shard_map)
    # ------------------------------------------------------------------

    def _layer_fwd_prefill(self, x, lp, *, batch, kind):
        """(x, the layer's (k, v) for the cache)."""
        u = layer_norm(x, lp["ln"], self.config.rms_norm_eps)
        a, kept = self.attn[kind].prefill(u, lp["attn"], batch)
        f, _ = self.moe(u, lp["moe"], phase="prefill")
        return x + a + f, kept

    def _layer_fwd_suffix(self, x, lp, kept, page_ids, start, *, kind):
        """A chunk of one sequence.  ``kept``: the layer's (k pool, v
        pool) — its kind's — read and not written; ``page_ids``: the
        sequence's pages IN THAT POOL.  Returns (x, the chunk's (k,
        v))."""
        u = layer_norm(x, lp["ln"], self.config.rms_norm_eps)
        a, kept = self.attn[kind].prefill_suffix(u, lp["attn"], start,
                                                 kept, page_ids)
        f, _ = self.moe(u, lp["moe"], phase="prefill")
        return x + a + f, kept

    def _layer_fwd_decode(self, x, lp, kept, table, offset, *, kind):
        """``kept``: the layer's (k pool, v pool), ``table`` its
        kind's page table.  Returns (x, the pools, the expert layer's
        counters)."""
        u = layer_norm(x, lp["ln"], self.config.rms_norm_eps)
        a, kept, _ = self.attn[kind].decode_paged(u, lp["attn"], kept,
                                                  table, offset)
        f, stats = self.moe(u, lp["moe"], phase="decode")
        return x + a + f, kept, stats

    def _per_layer(self, fn, **static):
        """One jitted body for each KIND of layer (`Qwen3._per_layer`):
        the loop over layers traces each kind once."""
        return {kind: jax.jit(functools.partial(fn, kind=kind, **static))
                for kind in set(self.kinds)}

    def _logits(self, x, params):
        """Tied head: the final norm's rows over the held rows of the
        embedding, float32."""
        x = layer_norm(x, params["ln_f"], self.config.rms_norm_eps)
        logits = jax.lax.dot_general(
            x, params["embed"], (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return logits * self.config.logit_scale

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
        for li, (kind, lp) in enumerate(zip(self.kinds, params["layers"])):
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
        for li, (kind, lp) in enumerate(zip(self.kinds, params["layers"])):
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
        (`STATS`)."""
        x = params["embed"][tokens]
        layer = self._per_layer(self._layer_fwd_decode)
        counted = []
        for li, (kind, lp) in enumerate(zip(self.kinds, params["layers"])):
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
            c = jnp.stack(counted)                      # (layers, 4)
            cache = dataclasses.replace(cache, stats=jnp.concatenate(
                [c[:, :2].sum(axis=0), c[:, 2:3].max(axis=0),
                 c[:, 3:].sum(axis=0)]))
        return logits, cache.inc_offset(1)

    # ------------------------------------------------------------------
    # mesh-level entry points
    # ------------------------------------------------------------------

    def _pool_specs(self):
        """(the full layers' pools, the window layers' or None)."""
        pools = lambda n: [P(None, None, None, None)] * n or None  # noqa: E731
        return pools(self.num_full), pools(self.num_window)

    def _cache_specs(self):
        full, win = self._pool_specs()
        return KVCache(ks=full, vs=full, offset=P(None), wks=win, wvs=win)

    def _paged_cache_specs(self, page_size: int):
        full, win = self._pool_specs()
        return PagedKVCache(
            ks=full, vs=full, page_table=P(None, None), offset=P(None),
            stats=P(None), page_size=page_size, wks=win, wvs=win,
            window_table=P(None, None) if win else None)

    def make_prefill_fn(self):
        return jax.shard_map(
            self.prefill_shard, mesh=self.mesh,
            in_specs=(self.param_specs(), P(None, None),
                      self._cache_specs()),
            out_specs=(P(None, self.axis), self._cache_specs()),
            check_vma=False)

    def make_prefill_suffix_fn(self):
        """``(params, ids (1, C), start, row_cache, (ks, vs, wks, wvs),
        page_ids (2, T)) -> row_cache``: `prefill_shard_suffix`.  The
        program's name starts like the whole prefill's, and its kernels
        are the prefill's, so a device trace reads both alike."""
        full, win = self._pool_specs()
        return jax.shard_map(
            self.prefill_shard_suffix, mesh=self.mesh,
            in_specs=(self.param_specs(), P(None, None), P(),
                      self._cache_specs(), (full, full, win, win),
                      P(None, None)),
            out_specs=self._cache_specs(), check_vma=False)

    def make_paged_decode_fn(self, page_size: int = 16):
        cspecs = self._paged_cache_specs(page_size)
        return jax.shard_map(
            self.decode_shard, mesh=self.mesh,
            in_specs=(self.param_specs(), P(None), cspecs),
            out_specs=(P(None, self.axis), cspecs),
            check_vma=False)

    def create_paged_cache(self, batch: int, num_pages: int,
                           page_size: int, max_pages_per_seq: int,
                           window_pages: int = 2):
        """``window_pages``: the page count of the window layers' pools
        (the null page among them; `serving.pages.PagedKV` sizes it by
        slots)."""
        cfg = self.config
        make = functools.partial(
            PagedKVCache.create, self.num_full, num_pages, batch,
            cfg.num_kv_heads, page_size, cfg.head_dim,
            max_pages_per_seq, self.dtype, num_stats=len(self.STATS),
            window_layers=self.num_window, window_pages=window_pages)
        return jax.jit(make, out_shardings=self._named(
            self._paged_cache_specs(page_size)))()

    def create_cache(self, batch: int, max_seq: Optional[int] = None):
        """The single-row cache a bucketed prefill (or a chunk) fills:
        every attention layer's rows, each kind in its own lists; the
        dense-slot decode layout is not built for this family."""
        cfg = self.config
        make = functools.partial(
            KVCache.create, self.num_full, batch, cfg.num_kv_heads,
            max_seq or cfg.max_seq_len, cfg.head_dim, self.dtype,
            window_layers=self.num_window)
        return jax.jit(make, out_shardings=self._named(
            self._cache_specs()))()
