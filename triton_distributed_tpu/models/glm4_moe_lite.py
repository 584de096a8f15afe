"""GLM-4-MoE-Lite family (`model_type` ``glm4_moe_lite``): latent
attention in every layer, a dense feed-forward in the leading
``first_k_dense_replace`` layers and a sparse one (routed experts
beside a shared expert) in the rest.

    x <- x + MLA(RMSNorm(x));  x <- x + FFN_l(RMSNorm(x))
    final RMSNorm, untied head

It stands behind the same entry points the scheduler calls on `Qwen3`
(`make_prefill_fn`, `make_paged_decode_fn`, `create_paged_cache`,
`create_cache`, ``config.max_seq_len``), so the scheduler, the page
pool, the radix cache and the step loop are shared unchanged.  It also
offers `make_prefill_suffix_fn`: a prefill of ONE CHUNK of a prompt
whose earlier rows already lie in the page pool, which is how the
scheduler carries a long prompt out a chunk a step (``prefill_chunk``
tokens: `PREFILL_CHUNK`) and prefills only what a prefix hit left.  What
differs is below them: the mixer (`layers.mla_attn.MLAttention`), the
cache's layer state (one latent row a token, `models.kv_cache`) and the
sparse feed-forward (`layers.moe_mlp.SparseMoE`, dropless).  The dense
feed-forward is `TPMLP`, as in `Qwen3`.

ONE device: the mesh's ``tp`` axis must have size 1.  Tensor or expert
parallelism for this family is not built (ROADMAP Reach).  The
multi-token-prediction block the published model carries for
self-drafting is not part of the served forward pass and has no
weights here.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_distributed_tpu.kernels.matmul import MatmulConfig
from triton_distributed_tpu.layers.mla_attn import MLAttention
from triton_distributed_tpu.layers.moe_mlp import MOE_STATS, SparseMoE
from triton_distributed_tpu.layers.tp_attn import rms_norm
from triton_distributed_tpu.layers.tp_mlp import TPMLP
from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.models.kv_cache import KVCache, PagedKVCache

__all__ = ["Glm4MoeLite", "MOE_STATS", "PREFILL_CHUNK"]

#: Tokens of a prompt the scheduler prefills between two decode steps
#: (`make_prefill_suffix_fn`).  A chunk streams the experts once, so a
#: shorter one costs tokens a second and a longer one lengthens the
#: token gap of every running row: settled on the chip, PERF.md
#: section 6 (PR 40) has the sweep.
PREFILL_CHUNK = 2048


class Glm4MoeLite:
    #: What a decode step leaves in the cache's `stats`, in order.
    STATS = MOE_STATS

    def __init__(self, config: ModelConfig, mesh: Mesh, axis: str = "tp",
                 mode: str = "fused", interpret: Optional[bool] = None,
                 gemm: Optional[MatmulConfig] = None):
        assert config.is_mla and config.is_moe, config
        assert mesh.shape[axis] == 1, (
            f"{type(self).__name__} runs on one device; "
            f"{axis}={mesh.shape[axis]} is not built")
        assert not config.quantize_kv_cache, "no int8 latent cache"
        self.config = config
        self.mesh = mesh
        self.axis = axis
        self.world = 1
        self.mode = mode
        self.interpret = interpret
        self.dtype = jnp.dtype(config.dtype)
        self.prefill_chunk = PREFILL_CHUNK
        self.attn = MLAttention(
            hidden=config.hidden_size, num_heads=config.num_heads,
            q_rank=config.q_lora_rank, lat=config.kv_lora_rank,
            nope=config.qk_nope_head_dim, rope=config.qk_rope_head_dim,
            v_dim=config.v_head_dim, rope_theta=config.rope_theta,
            eps=config.rms_norm_eps, mode=mode, interpret=interpret)
        self.dense = TPMLP(
            axis=axis, world_size=1, hidden=config.hidden_size,
            ffn=config.intermediate_size, mode=mode,
            gemm=gemm or MatmulConfig(), interpret=interpret)
        self.moe = SparseMoE(
            hidden=config.hidden_size,
            ffn=config.moe_intermediate_size,
            num_experts=config.num_experts,
            topk=config.num_experts_per_tok,
            n_shared=config.n_shared_experts,
            routed_scaling=config.routed_scaling_factor,
            norm_topk_prob=config.norm_topk_prob, mode=mode,
            interpret=interpret)

    def is_sparse(self, layer: int) -> bool:
        return layer >= self.config.first_k_dense_replace

    @property
    def latent_bytes_per_token(self) -> int:
        """Bytes of a cached token that carry information, over all
        layers (the pool's rows are padded to whole lanes beyond it)."""
        return (self.config.num_layers * self.attn.row_used
                * self.dtype.itemsize)

    # ------------------------------------------------------------------
    # parameters
    # ------------------------------------------------------------------

    def set_mode(self, mode: str):
        self.mode = mode
        self.attn = dataclasses.replace(self.attn, mode=mode)
        self.dense = dataclasses.replace(self.dense, mode=mode)
        self.moe = dataclasses.replace(self.moe, mode=mode)

    def _named(self, specs):
        return jax.tree.map(
            lambda sp: NamedSharding(self.mesh, sp), specs,
            is_leaf=lambda x: isinstance(x, P))

    def _layer_specs(self, layer: int):
        return {"ln1": P(None), "ln2": P(None),
                "attn": self.attn.param_specs(),
                "mlp": (self.moe.param_specs() if self.is_sparse(layer)
                        else self.dense.global_param_specs())}

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

        def one_layer(k, sparse):
            k1, k2 = jax.random.split(k)
            ffn = self.moe if sparse else self.dense
            return {"ln1": jnp.ones((h,), self.dtype),
                    "ln2": jnp.ones((h,), self.dtype),
                    "attn": self.attn.init_params(k1, self.dtype),
                    "mlp": ffn.init_params(k2, self.dtype)}

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
            sparse = self.is_sparse(i)
            if sparse not in make:
                make[sparse] = jax.jit(
                    functools.partial(one_layer, sparse=sparse),
                    out_shardings=self._named(specs["layers"][i]))
            layers.append(make[sparse](keys[i]))
        params["layers"] = layers
        return params

    # ------------------------------------------------------------------
    # per-device forward bodies (called inside shard_map)
    # ------------------------------------------------------------------

    def _ffn(self, h, lp, sparse: bool, phase: str):
        """(y, stats or None)."""
        if sparse:
            return self.moe(h, lp, phase=phase)
        return self.dense(h, lp), None

    def _layer_fwd_prefill(self, x, lp, *, batch, sparse):
        eps = self.config.rms_norm_eps
        h, rows = self.attn.prefill(rms_norm(x, lp["ln1"], eps),
                                    lp["attn"], batch)
        x = x + h
        h, _ = self._ffn(rms_norm(x, lp["ln2"], eps), lp["mlp"], sparse,
                         "prefill")
        return x + h, rows

    def _layer_fwd_suffix(self, x, lp, pool, page_ids, start, bufs, *,
                          sparse):
        eps = self.config.rms_norm_eps
        h, rows, bufs = self.attn.prefill_suffix(
            rms_norm(x, lp["ln1"], eps), lp["attn"], start, pool,
            page_ids, bufs)
        x = x + h
        h, _ = self._ffn(rms_norm(x, lp["ln2"], eps), lp["mlp"], sparse,
                         "prefill")
        return x + h, rows, bufs

    def _layer_fwd_decode(self, x, lp, pool, page_table, offset, *,
                          sparse):
        eps = self.config.rms_norm_eps
        h, pool = self.attn.decode_paged(
            rms_norm(x, lp["ln1"], eps), lp["attn"], pool, page_table,
            offset)
        x = x + h
        h, stats = self._ffn(rms_norm(x, lp["ln2"], eps), lp["mlp"],
                             sparse, "decode")
        return x + h, pool, stats

    def _per_layer(self, fn, **static):
        """One jitted body for each KIND of layer (`Qwen3._per_layer`):
        the loop over layers traces each kind once."""
        return {sparse: jax.jit(functools.partial(
            fn, sparse=sparse, **static)) for sparse in (False, True)}

    def prefill_shard(self, params, input_ids, cache: Optional[KVCache]):
        """input_ids: (B, S).  Returns (logits (B, V) float32 of each
        sequence's last position, cache)."""
        cfg = self.config
        b, s = input_ids.shape
        x = params["embed"][input_ids].reshape(b * s, -1)
        layer = self._per_layer(self._layer_fwd_prefill, batch=b)
        for li, lp in enumerate(params["layers"]):
            x, rows = layer[self.is_sparse(li)](x, lp)
            if cache is not None:
                cache = cache.write_prefill(li, rows)
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
        ``pools``: the paged cache's (ks, vs), read and not written —
        the rows below ``start`` lie there, at the pages ``page_ids``
        (T,) names in logical order.  Returns ``cache`` (the single-row
        cache of `create_cache`, C long) holding the chunk's rows at
        LOCAL positions [0, C): the paged insert puts them into their
        pages.  No logits: the first decode step recomputes the
        prompt's last position — so nothing reads the last layer's
        attention output or feed-forward, and the compiler leaves both
        out (a chunk at ``start`` 0 is cheaper than the same bucket's
        whole prefill)."""
        b, s = input_ids.shape
        assert b == 1, "a chunk is one sequence's"
        ks, _ = pools
        x = params["embed"][input_ids].reshape(s, -1)
        start = jnp.asarray(start, jnp.int32).reshape(())
        layer = self._per_layer(self._layer_fwd_suffix)
        bufs = self.attn.suffix_buffers(
            page_ids.shape[0] * ks[0].shape[2], s, self.dtype)
        for li, lp in enumerate(params["layers"]):
            x, rows, bufs = layer[self.is_sparse(li)](
                x, lp, ks[li], page_ids, start, bufs)
            cache = cache.write_prefill(li, rows)
        return cache.set_offset(s)

    def decode_shard(self, params, tokens, cache: PagedKVCache):
        """One decode step.  tokens: (B,).  Returns (logits (B, V),
        cache) — the cache's `stats` hold what the sparse layers
        counted in this step (`MOE_STATS`: pairs and experts hit summed
        over the layers, the busiest expert's share in the worst)."""
        cfg = self.config
        x = params["embed"][tokens]
        layer = self._per_layer(self._layer_fwd_decode)
        counted = []
        for li, lp in enumerate(params["layers"]):
            x, pool, stats = layer[self.is_sparse(li)](
                x, lp, cache.ks[li], cache.page_table, cache.offset)
            cache = cache.set_layer(li, pool)
            if stats is not None:
                counted.append(stats)
        x = rms_norm(x, params["ln_f"], cfg.rms_norm_eps)
        logits = jnp.dot(x, params["lm_head"],
                         preferred_element_type=jnp.float32)
        if counted and cache.stats is not None:
            c = jnp.stack(counted)                      # (layers, 3)
            cache = dataclasses.replace(cache, stats=jnp.concatenate(
                [c[:, :2].sum(axis=0), c[:, 2:].max(axis=0)]))
        return logits, cache.inc_offset(1)

    # ------------------------------------------------------------------
    # mesh-level entry points
    # ------------------------------------------------------------------

    def _cache_specs(self):
        n = self.config.num_layers
        return KVCache(ks=[P(None, None, None, None)] * n, vs=None,
                       offset=P(None))

    def _paged_cache_specs(self, page_size: int):
        n = self.config.num_layers
        return PagedKVCache(
            ks=[P(None, None, None, None)] * n, vs=None,
            page_table=P(None, None), offset=P(None), stats=P(None),
            page_size=page_size)

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
        name starts like the whole prefill's, and its expert layers run
        in the prefill phase, so a device trace reads both alike."""
        n = self.config.num_layers
        return jax.shard_map(
            self.prefill_shard_suffix, mesh=self.mesh,
            in_specs=(self.param_specs(), P(None, None), P(),
                      self._cache_specs(),
                      ([P(None, None, None, None)] * n, None), P(None)),
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
        make = functools.partial(
            PagedKVCache.create, self.config.num_layers, num_pages,
            batch, 1, page_size, self.attn.row_width, max_pages_per_seq,
            self.dtype, latent=True, num_stats=len(MOE_STATS))
        return jax.jit(make, out_shardings=self._named(
            self._paged_cache_specs(page_size)))()

    def create_cache(self, batch: int, max_seq: Optional[int] = None):
        """The single-row cache a bucketed prefill fills (latent rows);
        the dense-slot decode layout is not built for this family."""
        make = functools.partial(
            KVCache.create, self.config.num_layers, batch, 1,
            max_seq or self.config.max_seq_len, self.attn.row_width,
            self.dtype, latent=True)
        return jax.jit(make, out_shardings=self._named(
            self._cache_specs()))()
