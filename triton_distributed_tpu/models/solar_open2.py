"""Solar-Open2 family (`model_type` ``solar_open2``): a hybrid of
softmax and linear attention over a sparse feed-forward in every layer.

    x <- x + Mixer_l(RMSNorm(x));  x <- x + MoE_l(RMSNorm(x))
    final RMSNorm, untied head

``Mixer_l`` is grouped-query softmax attention with no positional
encoding and an output gate for ``l`` in ``config.gqa_layers``
(`layers.tp_attn.TPAttention`, ``rope=False, gate=True``) and Kimi
Delta Attention everywhere else (`layers.kda_attn.KDAttention`): a
fixed-size recurrent state a sequence in place of K and V a token.
``MoE_l`` is `layers.moe_mlp.SparseMoE`, told which experts of the
layer this chip holds (``config.experts_held``); the embedding and the
head are over the vocabulary this chip holds (``config.vocab_size``).

It stands behind the entry points the scheduler calls on `Qwen3` and
`Glm4MoeLite` (`make_prefill_fn`, `make_paged_decode_fn`,
`create_paged_cache`, `create_cache`), so the scheduler, the page
pool, the radix cache and the pipelined step are shared.  Its cache
(`models.kv_cache`) holds both kinds of layer state: pages for the
attention layers, a state a slot for the delta-rule layers.  The
prefill reads ``cache.length``: the tokens of each row the state is to
absorb (never a bucket's padded tail).  A decode step updates the
state of the LIVE rows only (`PagedKVCache.live_rows`).

It also offers `make_prefill_suffix_fn`: a prefill of ONE CHUNK of a
prompt that begins where its predecessor ended — the delta-rule layers
from the state and the convolution's tail that one returned (they ride
in and out in the row cache's ``states`` / ``convs``), the softmax
layers attending the K/V rows already in the page pool — which is how
the scheduler carries a long prompt out a chunk a step
(``prefill_chunk`` tokens: `PREFILL_CHUNK`).  A state has no snapshot,
so the chunks of a prompt always start at position 0.

ONE device (``tp`` of size 1); tensor parallelism for this family, the
exchange that would make the held expert layer expert-parallel and a
snapshot of the state are not built (ROADMAP Reach).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_distributed_tpu.kernels.matmul import MatmulConfig
from triton_distributed_tpu.layers.kda_attn import KDAttention
from triton_distributed_tpu.layers.moe_mlp import HELD_STATS, SparseMoE
from triton_distributed_tpu.layers.tp_attn import TPAttention, rms_norm
from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.models.kv_cache import KVCache, PagedKVCache

__all__ = ["SolarOpen2", "PREFILL_CHUNK"]

#: Tokens of a prompt the scheduler prefills between two decode steps
#: (`make_prefill_suffix_fn`); a multiple of `kernels.kda.CHUNK` and of
#: the page.  A chunk streams the held experts once, so a shorter one
#: costs device time and a longer one lengthens the token gap of every
#: running row: settled on the chip by PR 40's rule (of 256 / 512 /
#: 1024 the shortest that keeps the tokens a second within 5% of the
#: unchunked prefill's on two seeds: none of the three lost any, a
#: chunk having no head, no last expert block and no bucket's padding;
#: PERF.md section 5 has the sweep of PR 45).
PREFILL_CHUNK = 256


class SolarOpen2:
    #: What a decode step leaves in the cache's `stats`, in order: the
    #: held experts' counters summed over the layers (the busiest
    #: expert's share in the worst), and the rows whose state it
    #: updated.
    STATS = HELD_STATS + ("live_slots",)

    def __init__(self, config: ModelConfig, mesh: Mesh, axis: str = "tp",
                 mode: str = "fused", interpret: Optional[bool] = None,
                 gemm: Optional[MatmulConfig] = None):
        assert config.kda_num_heads and config.is_moe, config
        assert config.experts_held is not None, "which experts are here?"
        assert mesh.shape[axis] == 1, (
            f"{type(self).__name__} runs on one device; "
            f"{axis}={mesh.shape[axis]} is not built")
        assert not config.quantize_kv_cache, "no int8 cache beside a state"
        self.config = config
        self.mesh = mesh
        self.axis = axis
        self.world = 1
        self.mode = mode
        self.interpret = interpret
        self.dtype = jnp.dtype(config.dtype)
        self.prefill_chunk = PREFILL_CHUNK
        self.attn = TPAttention(
            axis=axis, world_size=1, hidden=config.hidden_size,
            num_heads=config.num_heads,
            num_kv_heads=config.num_kv_heads, head_dim=config.head_dim,
            rope_theta=config.rope_theta, qk_norm=False,
            rope=config.use_rope, gate=config.use_gqa_gate, mode=mode,
            gemm=gemm or MatmulConfig(), interpret=interpret)
        self.kda = KDAttention(
            hidden=config.hidden_size, num_heads=config.kda_num_heads,
            head_dim=config.kda_head_dim, conv=config.kda_conv_size,
            rank=config.kda_rank,
            neg_eigval=config.kda_allow_neg_eigval,
            eps=config.rms_norm_eps, mode=mode, interpret=interpret)
        self.moe = SparseMoE(
            hidden=config.hidden_size, ffn=config.moe_intermediate_size,
            num_experts=config.num_experts,
            topk=config.num_experts_per_tok,
            n_shared=config.n_shared_experts,
            routed_scaling=config.routed_scaling_factor,
            norm_topk_prob=config.norm_topk_prob, mode=mode,
            interpret=interpret, held=tuple(config.experts_held))
        #: Each layer's place among the layers of its kind: the index
        #: of its pools (``ks`` / ``vs``) or of its state (``states``).
        self._index = []
        count = {True: 0, False: 0}
        for layer in range(config.num_layers):
            gqa = self.is_gqa(layer)
            self._index.append(count[gqa])
            count[gqa] += 1
        self.num_gqa, self.num_kda = count[True], count[False]

    def is_gqa(self, layer: int) -> bool:
        return layer in self.config.gqa_layers

    @property
    def _state_shapes(self):
        k = self.kda
        return [((k.num_heads, k.head_dim, k.head_dim),
                 ((k.conv - 1) * 3 * k.width,))] * self.num_kda

    # ------------------------------------------------------------------
    # parameters
    # ------------------------------------------------------------------

    def set_mode(self, mode: str):
        self.mode = mode
        self.attn = dataclasses.replace(self.attn, mode=mode)
        self.kda = dataclasses.replace(self.kda, mode=mode)
        self.moe = dataclasses.replace(self.moe, mode=mode)

    def _named(self, specs):
        return jax.tree.map(
            lambda sp: NamedSharding(self.mesh, sp), specs,
            is_leaf=lambda x: isinstance(x, P))

    def _layer_specs(self, layer: int):
        return {"ln1": P(None), "ln2": P(None),
                "attn": (self.attn.global_param_specs()
                         if self.is_gqa(layer)
                         else self.kda.param_specs()),
                "mlp": self.moe.param_specs()}

    def param_specs(self):
        return {"embed": P(None, None),
                "layers": [self._layer_specs(i)
                           for i in range(self.config.num_layers)],
                "ln_f": P(None),
                "lm_head": P(None, self.axis)}

    def init_params(self, key):
        """Seeded parameters, made on the device a layer at a time."""
        cfg = self.config
        h = cfg.hidden_size
        specs = self.param_specs()

        def one_layer(k, gqa):
            k1, k2 = jax.random.split(k)
            mixer = self.attn if gqa else self.kda
            return {"ln1": jnp.ones((h,), self.dtype),
                    "ln2": jnp.ones((h,), self.dtype),
                    "attn": mixer.init_params(k1, self.dtype),
                    "mlp": self.moe.init_params(k2, self.dtype)}

        def ends(k_embed, k_head):
            normal = jax.random.normal
            return {"embed": (normal(k_embed, (cfg.vocab_size, h))
                              * h ** -0.5).astype(self.dtype),
                    "ln_f": jnp.ones((h,), self.dtype),
                    "lm_head": (normal(k_head, (h, cfg.vocab_size))
                                * h ** -0.5).astype(self.dtype)}

        keys = jax.random.split(key, cfg.num_layers + 2)
        params = jax.jit(ends, out_shardings=self._named(
            {k: specs[k] for k in ("embed", "ln_f", "lm_head")}))(
                keys[-1], keys[-2])
        make = {}
        layers = []
        for i in range(cfg.num_layers):
            gqa = self.is_gqa(i)
            if gqa not in make:
                make[gqa] = jax.jit(
                    functools.partial(one_layer, gqa=gqa),
                    out_shardings=self._named(specs["layers"][i]))
            layers.append(make[gqa](keys[i]))
        params["layers"] = layers
        return params

    # ------------------------------------------------------------------
    # per-device forward bodies (called inside shard_map)
    # ------------------------------------------------------------------

    def _layer_fwd_prefill(self, x, lp, length, kept=(), page_ids=None,
                           start=None, *, batch, gqa):
        """(x, what the layer leaves in the cache): (k, v) or (state,
        conv inputs).  ``kept``: nothing where the rows start their
        sequences; for a chunk of one sequence what the layer reads of
        its earlier tokens — the (k pool, v pool), whose pages
        ``page_ids`` names, below position ``start``, or the (state,
        conv inputs) the chunk before it returned."""
        eps = self.config.rms_norm_eps
        h = rms_norm(x, lp["ln1"], eps)
        if not gqa:
            h, *kept = self.kda.prefill(h, lp["attn"], batch, length,
                                        *kept)
        elif kept:
            h, kept = self.attn.prefill_suffix(h, lp["attn"], start, kept,
                                               page_ids)
        else:
            h, kept = self.attn.prefill(h, lp["attn"], batch)
        x = x + h
        h, _ = self.moe(rms_norm(x, lp["ln2"], eps), lp["mlp"],
                        phase="prefill")
        return x + h, tuple(kept)

    def _layer_fwd_decode(self, x, lp, kept, page_table, offset, live, *,
                          gqa):
        """``kept``: the layer's (k pool, v pool) or (state, conv)."""
        eps = self.config.rms_norm_eps
        h = rms_norm(x, lp["ln1"], eps)
        if gqa:
            h, kept, _ = self.attn.decode_paged(
                h, lp["attn"], kept, page_table, offset)
        else:
            h, *kept = self.kda.decode(h, lp["attn"], *kept, live)
        x = x + h
        h, stats = self.moe(rms_norm(x, lp["ln2"], eps), lp["mlp"],
                            phase="decode")
        return x + h, tuple(kept), stats

    def _per_layer(self, fn, **static):
        """One jitted body for each KIND of layer (`Qwen3._per_layer`):
        the loop over layers traces each kind once."""
        return {gqa: jax.jit(functools.partial(fn, gqa=gqa, **static))
                for gqa in (False, True)}

    def prefill_shard(self, params, input_ids, cache: Optional[KVCache]):
        """input_ids: (B, S).  Returns (logits (B, V) float32 of each
        sequence's last position, cache).  The delta-rule layers'
        state absorbs ``cache.length`` tokens of each row (all S
        without a cache)."""
        cfg = self.config
        b, s = input_ids.shape
        length = (cache.length if cache is not None
                  else jnp.full((b,), s, jnp.int32))
        x = params["embed"][input_ids].reshape(b * s, -1)
        layer = self._per_layer(self._layer_fwd_prefill, batch=b)
        for li, lp in enumerate(params["layers"]):
            gqa = self.is_gqa(li)
            x, kept = layer[gqa](x, lp, length)
            if cache is None:
                continue
            if gqa:
                cache = cache.write_prefill(self._index[li], *kept)
            else:
                cache = cache.set_state(self._index[li], *kept)
        x = rms_norm(x, params["ln_f"], cfg.rms_norm_eps)
        logits = jnp.dot(x.reshape(b, s, -1)[:, -1], params["lm_head"],
                         preferred_element_type=jnp.float32)
        if cache is not None:
            cache = cache.set_offset(s)
        return logits, cache

    def prefill_shard_suffix(self, params, input_ids, start,
                             cache: KVCache, pools, page_ids):
        """One chunk of one prompt.  input_ids: (1, C), the tokens at
        positions ``start + arange(C)`` (a last chunk right-padded);
        ``cache``: the single-row cache of `create_cache`, C long,
        whose ``states`` / ``convs`` hold what the chunk before this
        one returned (zeros in front of the first) and whose ``length``
        says how many of THIS chunk's tokens the state absorbs;
        ``pools``: the paged cache's (ks, vs), read and not written —
        the softmax layers' rows below ``start`` lie there, at the
        pages ``page_ids`` (T,) names in logical order.  Returns
        ``cache`` holding the chunk's K/V rows at LOCAL positions
        [0, C) — the paged insert puts them into their pages — and the
        state and convolution tail after the chunk: the next chunk's
        to start from, or the last one's insert's to write into the
        slot.  No logits: the first decode step recomputes the prompt's
        last position — so the last layer's expert block is read by
        nothing and the compiler leaves it out with the head."""
        b, s = input_ids.shape
        assert b == 1, "a chunk is one sequence's"
        ks, vs = pools
        x = params["embed"][input_ids].reshape(s, -1)
        start = jnp.asarray(start, jnp.int32).reshape(())
        layer = self._per_layer(self._layer_fwd_prefill, batch=1)
        for li, lp in enumerate(params["layers"]):
            gqa, i = self.is_gqa(li), self._index[li]
            kept = ((ks[i], vs[i]) if gqa
                    else (cache.states[i], cache.convs[i]))
            x, kept = layer[gqa](x, lp, cache.length, kept, page_ids,
                                 start)
            cache = (cache.write_prefill(i, *kept) if gqa
                     else cache.set_state(i, *kept))
        return cache.set_offset(s)

    def decode_shard(self, params, tokens, cache: PagedKVCache):
        """One decode step.  tokens: (B,).  Returns (logits (B, V),
        cache) — the cache's `stats` hold what the step counted
        (`STATS`)."""
        cfg = self.config
        live = cache.live_rows
        x = params["embed"][tokens]
        layer = self._per_layer(self._layer_fwd_decode)
        counted = []
        for li, lp in enumerate(params["layers"]):
            gqa, i = self.is_gqa(li), self._index[li]
            kept = ((cache.ks[i], cache.vs[i]) if gqa
                    else (cache.states[i], cache.convs[i]))
            x, kept, stats = layer[gqa](
                x, lp, kept, cache.page_table, cache.offset, live)
            cache = (cache.set_layer(i, *kept) if gqa
                     else cache.set_state(i, *kept))
            counted.append(stats)
        x = rms_norm(x, params["ln_f"], cfg.rms_norm_eps)
        logits = jnp.dot(x, params["lm_head"],
                         preferred_element_type=jnp.float32)
        if cache.stats is not None:
            c = jnp.stack(counted)                      # (layers, 4)
            cache = dataclasses.replace(cache, stats=jnp.concatenate(
                [c[:, :2].sum(axis=0), c[:, 2:3].max(axis=0),
                 c[:, 3:].sum(axis=0),
                 jnp.sum(live).astype(jnp.float32)[None]]))
        return logits, cache.inc_offset(1)

    # ------------------------------------------------------------------
    # mesh-level entry points
    # ------------------------------------------------------------------

    def _state_specs(self):
        if not self.num_kda:     # a cut that kept no delta-rule layer
            return {}
        return dict(states=[P(None, None, None, None)] * self.num_kda,
                    convs=[P(None, None)] * self.num_kda)

    def _cache_specs(self):
        pools = [P(None, None, None, None)] * self.num_gqa
        state = self._state_specs()
        return KVCache(ks=pools, vs=pools, offset=P(None),
                       length=P(None) if state else None, **state)

    def _paged_cache_specs(self, page_size: int):
        pools = [P(None, None, None, None)] * self.num_gqa
        return PagedKVCache(
            ks=pools, vs=pools, page_table=P(None, None),
            offset=P(None), stats=P(None), page_size=page_size,
            **self._state_specs())

    def make_prefill_fn(self):
        return jax.shard_map(
            self.prefill_shard, mesh=self.mesh,
            in_specs=(self.param_specs(), P(None, None),
                      self._cache_specs()),
            out_specs=(P(None, self.axis), self._cache_specs()),
            check_vma=False)

    def make_prefill_suffix_fn(self):
        """``(params, ids (1, C), start, row_cache, (ks, vs), page_ids
        (T,)) -> row_cache``: `prefill_shard_suffix`.  The program's
        name starts like the whole prefill's, and its kernels are the
        prefill's, so a device trace reads both alike."""
        pools = [P(None, None, None, None)] * self.num_gqa
        return jax.shard_map(
            self.prefill_shard_suffix, mesh=self.mesh,
            in_specs=(self.param_specs(), P(None, None), P(),
                      self._cache_specs(), (pools, pools), P(None)),
            out_specs=self._cache_specs(), check_vma=False)

    def make_paged_decode_fn(self, page_size: int = 16):
        cspecs = self._paged_cache_specs(page_size)
        return jax.shard_map(
            self.decode_shard, mesh=self.mesh,
            in_specs=(self.param_specs(), P(None), cspecs),
            out_specs=(P(None, self.axis), cspecs),
            check_vma=False)

    def create_paged_cache(self, batch: int, num_pages: int,
                           page_size: int, max_pages_per_seq: int):
        cfg = self.config
        make = functools.partial(
            PagedKVCache.create, self.num_gqa, num_pages, batch,
            cfg.num_kv_heads, page_size, cfg.head_dim,
            max_pages_per_seq, self.dtype, num_stats=len(self.STATS),
            state_shapes=self._state_shapes)
        return jax.jit(make, out_shardings=self._named(
            self._paged_cache_specs(page_size)))()

    def create_cache(self, batch: int, max_seq: Optional[int] = None):
        """The single-row cache a bucketed prefill fills: the attention
        layers' rows and the delta-rule layers' state; the dense-slot
        decode layout is not built for this family."""
        cfg = self.config
        make = functools.partial(
            KVCache.create, self.num_gqa, batch, cfg.num_kv_heads,
            max_seq or cfg.max_seq_len, cfg.head_dim, self.dtype,
            state_shapes=self._state_shapes)
        return jax.jit(make, out_shardings=self._named(
            self._cache_specs()))()
