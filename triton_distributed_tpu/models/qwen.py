"""Qwen3-family tensor-parallel model.

Reference: `python/triton_dist/models/qwen.py` (229 LoC) — `Qwen3Layer`
(`:54`, fwd `:98-113`: rmsnorm → TP_Attn → rmsnorm → TP_MLP with
residuals), `Qwen3` (`:115`) loading HF weights, `set_fwd` switching
torch / triton_dist / triton_dist_AR backends.

TPU: the model is a pytree of global weights + pure per-device forward
functions run under shard_map over the `tp` axis.  `set_mode` switches
the per-op backend ("xla" golden ↔ "fused" Pallas overlap kernels) —
the analogue of the reference's backend switch.  Activations between
layers are sequence(M)-sharded, the layout the fused AG-GEMM/GEMM-RS
pair maintains.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_distributed_tpu.kernels.matmul import MatmulConfig
from triton_distributed_tpu.layers.tp_attn import TPAttention, rms_norm
from triton_distributed_tpu.layers.tp_mlp import TPMLP
from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.models.kv_cache import KVCache, PagedKVCache


class Qwen3:
    def __init__(self, config: ModelConfig, mesh: Mesh, axis: str = "tp",
                 mode: str = "fused", interpret: Optional[bool] = None,
                 gemm: Optional[MatmulConfig] = None):
        self.config = config
        self.mesh = mesh
        self.axis = axis
        self.world = mesh.shape[axis]
        # KV-head replication is not implemented: weights, cache and
        # sharding specs all assume an exact per-rank split.  Fail
        # loudly here rather than numerically downstream (ADVICE r1).
        assert config.num_heads % self.world == 0, (
            f"num_heads={config.num_heads} not divisible by "
            f"tp={self.world}")
        assert config.num_kv_heads % self.world == 0, (
            f"num_kv_heads={config.num_kv_heads} not divisible by "
            f"tp={self.world}; KV-head replication is unsupported")
        self.mode = mode
        self.interpret = interpret
        self.dtype = jnp.dtype(config.dtype)
        gemm = gemm or MatmulConfig()
        self.attn = TPAttention(
            axis=axis, world_size=self.world, hidden=config.hidden_size,
            num_heads=config.num_heads, num_kv_heads=config.num_kv_heads,
            head_dim=config.head_dim, rope_theta=config.rope_theta,
            qk_norm=config.qk_norm, mode=mode, gemm=gemm,
            interpret=interpret)
        if config.is_moe:
            from triton_distributed_tpu.layers.moe_mlp import MoEMLP
            self.mlp = MoEMLP(
                axis=axis, world_size=self.world,
                hidden=config.hidden_size,
                ffn=(config.moe_intermediate_size
                     or config.intermediate_size),
                num_experts=config.num_experts,
                topk=config.num_experts_per_tok,
                capacity_factor=config.moe_capacity_factor,
                mode=mode, gemm=gemm, interpret=interpret)
        else:
            self.mlp = TPMLP(
                axis=axis, world_size=self.world,
                hidden=config.hidden_size,
                ffn=config.intermediate_size, mode=mode, gemm=gemm,
                interpret=interpret)

    # ------------------------------------------------------------------
    # parameters
    # ------------------------------------------------------------------

    def set_mode(self, mode: str):
        """Backend switch (reference `set_fwd`, `models/qwen.py`)."""
        self.mode = mode
        self.attn = dataclasses.replace(self.attn, mode=mode)
        self.mlp = dataclasses.replace(
            self.mlp, mode=mode if mode == "xla" else "fused")

    def _named(self, specs):
        """PartitionSpec tree -> NamedSharding tree on this mesh."""
        return jax.tree.map(
            lambda sp: NamedSharding(self.mesh, sp), specs,
            is_leaf=lambda x: isinstance(x, P))

    def init_params(self, key):
        """Global parameter pytree, created ALREADY SHARDED to
        `param_specs()`: every rank generates only its own shard
        (jitted shard_maps), so no device ever holds a global weight
        and the jitted prefill/decode programs take the params as they
        lie.  The global layout is the concatenation of the per-rank
        shards, which is exactly what the per-device forward bodies
        expect.  One small program makes a layer and is run once per
        layer (a single program for the whole depth took 43 s to
        compile at 12 layers on the v5e)."""
        cfg = self.config
        h = cfg.hidden_size
        specs = self.param_specs()

        def one_layer(k):
            r = jax.lax.axis_index(self.axis)
            k1, k2 = jax.random.split(k)
            attn_p = self.attn.init_params(jax.random.fold_in(k1, r),
                                           self.dtype)
            mlp_p = self.mlp.init_params(jax.random.fold_in(k2, r),
                                         self.dtype)
            if cfg.is_moe:
                # the router is replicated: every rank takes rank 0's
                mlp_p["router"] = self.mlp.init_params(
                    jax.random.fold_in(k2, 0), self.dtype)["router"]
            return {"ln1": jnp.ones((h,), self.dtype),
                    "ln2": jnp.ones((h,), self.dtype),
                    "attn": attn_p, "mlp": mlp_p}

        def ends(k_embed, k_head):
            r = jax.lax.axis_index(self.axis)
            embed = (jax.random.normal(k_embed, (cfg.vocab_size, h))
                     * h ** -0.5).astype(self.dtype)
            lm_head = (embed.T if cfg.tie_word_embeddings else
                       (jax.random.normal(k_head, (h, cfg.vocab_size))
                        * h ** -0.5).astype(self.dtype))
            v_loc = cfg.vocab_size // self.world
            return {"embed": embed,
                    "ln_f": jnp.ones((h,), self.dtype),
                    "lm_head": jax.lax.dynamic_slice_in_dim(
                        lm_head, r * v_loc, v_loc, 1)}

        def sharded(fn, out_specs):
            return jax.jit(jax.shard_map(
                fn, mesh=self.mesh, in_specs=P(), out_specs=out_specs,
                check_vma=False))

        keys = jax.random.split(key, cfg.num_layers + 2)
        make_layer = sharded(one_layer, specs["layers"][0])
        params = sharded(ends, {k: specs[k] for k in
                                ("embed", "ln_f", "lm_head")})(
            keys[-1], keys[-2])
        params["layers"] = [make_layer(keys[i])
                            for i in range(cfg.num_layers)]
        return params

    def param_specs(self):
        cfg = self.config
        layer = {
            "ln1": P(None),
            "ln2": P(None),
            "attn": {"wqkv": P(None, self.axis),
                     "wo": P(self.axis, None)},
            "mlp": self.mlp.global_param_specs(),
        }
        if cfg.qk_norm:
            layer["attn"]["q_norm"] = P(None)
            layer["attn"]["k_norm"] = P(None)
        return {
            "embed": P(None, None),
            "layers": [layer] * cfg.num_layers,
            "ln_f": P(None),
            "lm_head": P(None, self.axis),
        }

    def load_hf_weights(self, model_name_or_path: str):
        """Load HF safetensors into the global layout (reference:
        `Qwen3Layer.init_parameters`, `models/qwen.py:73-83`)."""
        import numpy as np
        from transformers import AutoModelForCausalLM
        hf = AutoModelForCausalLM.from_pretrained(model_name_or_path,
                                                  torch_dtype="float32")
        sd = {k: np.asarray(v) for k, v in hf.state_dict().items()}
        cfg = self.config
        d = cfg.head_dim

        def t(name):
            return jnp.asarray(sd[name].T, self.dtype)

        layers = []
        for i in range(cfg.num_layers):
            pre = f"model.layers.{i}."
            wq = t(pre + "self_attn.q_proj.weight")
            wk = t(pre + "self_attn.k_proj.weight")
            wv = t(pre + "self_attn.v_proj.weight")
            # interleave per rank: [q_r | k_r | v_r] for each rank r
            hq = cfg.num_heads // self.world * d
            hkv = cfg.num_kv_heads // self.world * d
            wqkv = jnp.concatenate([
                jnp.concatenate([wq[:, r*hq:(r+1)*hq],
                                 wk[:, r*hkv:(r+1)*hkv],
                                 wv[:, r*hkv:(r+1)*hkv]], axis=1)
                for r in range(self.world)], axis=1)
            layer = {
                "ln1": jnp.asarray(sd[pre + "input_layernorm.weight"],
                                   self.dtype),
                "ln2": jnp.asarray(
                    sd[pre + "post_attention_layernorm.weight"],
                    self.dtype),
                "attn": {"wqkv": wqkv,
                         "wo": t(pre + "self_attn.o_proj.weight")},
                "mlp": {
                    "gate_up": _interleave_gate_up(
                        t(pre + "mlp.gate_proj.weight"),
                        t(pre + "mlp.up_proj.weight"), self.world),
                    "down": t(pre + "mlp.down_proj.weight"),
                },
            }
            if cfg.qk_norm:
                layer["attn"]["q_norm"] = jnp.asarray(
                    sd[pre + "self_attn.q_norm.weight"], self.dtype)
                layer["attn"]["k_norm"] = jnp.asarray(
                    sd[pre + "self_attn.k_norm.weight"], self.dtype)
            layers.append(layer)

        embed = jnp.asarray(sd["model.embed_tokens.weight"], self.dtype)
        lm = (embed.T if cfg.tie_word_embeddings
              else t("lm_head.weight"))
        return {"embed": embed, "layers": layers,
                "ln_f": jnp.asarray(sd["model.norm.weight"], self.dtype),
                "lm_head": lm}

    # ------------------------------------------------------------------
    # per-device forward bodies (called inside shard_map)
    # ------------------------------------------------------------------

    # Each layer body below is wrapped in ONE `jax.jit` per traced
    # program (`_per_layer`): the Python loop over layers then traces
    # and lowers the body — ring kernels, pipelines and all — once
    # instead of once per layer (measured ~3 s of lowering per layer
    # per prefill program at tp=4, paid again by every fresh process
    # even when the persistent compile cache hits).  XLA inlines the
    # calls, so the compiled program is unchanged.

    @staticmethod
    def _per_layer(fn, **static):
        return jax.jit(functools.partial(fn, **static))

    def _layer_fwd_prefill(self, x, lp, *, batch):
        cfg = self.config
        res = x
        h = rms_norm(x, lp["ln1"], cfg.rms_norm_eps)
        h, kv = self.attn.prefill(h, lp["attn"], batch)
        x = res + h
        res = x
        h = rms_norm(x, lp["ln2"], cfg.rms_norm_eps)
        h = self.mlp(h, lp["mlp"])
        return res + h, kv

    def _layer_fwd_decode(self, x, lp, kv, scales, page_table, offset):
        """One decode layer; ``page_table`` None = dense cache."""
        cfg = self.config
        res = x
        h = rms_norm(x, lp["ln1"], cfg.rms_norm_eps)
        if page_table is None:
            h, kv, scales = self.attn.decode(
                h, lp["attn"], kv, offset, kv_scales=scales)
        else:
            h, kv, scales = self.attn.decode_paged(
                h, lp["attn"], kv, page_table, offset, kv_scales=scales)
        x = res + h
        res = x
        h = rms_norm(x, lp["ln2"], cfg.rms_norm_eps)
        h = self.mlp(h, lp["mlp"])
        return res + h, kv, scales

    def prefill_shard(self, params, input_ids, cache: Optional[KVCache]):
        """Runs inside shard_map.  input_ids: (B, S) replicated.
        Returns (logits_local (B, V/world), cache)."""
        cfg = self.config
        b, s = input_ids.shape
        my = jax.lax.axis_index(self.axis)
        m = b * s
        m_loc = m // self.world
        x = params["embed"][input_ids].reshape(m, -1)
        x = jax.lax.dynamic_slice_in_dim(x, my * m_loc, m_loc, 0)

        layer = self._per_layer(self._layer_fwd_prefill, batch=b)
        for li, lp in enumerate(params["layers"]):
            x, (k, v) = layer(x, lp)
            if cache is not None:
                cache = cache.write_prefill(li, k, v)

        x = rms_norm(x, params["ln_f"], cfg.rms_norm_eps)
        # logits for the last position of each sequence
        x_full = jax.lax.all_gather(x, self.axis, tiled=True)
        last = x_full.reshape(b, s, -1)[:, -1]
        logits = jnp.dot(last, params["lm_head"],
                         preferred_element_type=jnp.float32)
        if cache is not None:
            cache = cache.set_offset(s)
        return logits, cache

    def decode_shard(self, params, tokens, cache):
        """One decode step inside shard_map.  tokens: (B,) replicated.
        ``cache`` is a `KVCache` or — the serving-scale layout — a
        `PagedKVCache`, whose per-layer pools are page-indexed (KV
        heads sharded over tp like the dense cache) and whose
        attention is `flash_decode_paged`'s page-table-indirected
        split-KV kernel.  Returns (logits_local (B, V/world), cache)."""
        cfg = self.config
        b = tokens.shape[0]
        my = jax.lax.axis_index(self.axis)
        b_loc = b // self.world
        x = params["embed"][tokens]                 # (B, h)
        x = jax.lax.dynamic_slice_in_dim(x, my * b_loc, b_loc, 0)

        page_table = getattr(cache, "page_table", None)
        layer = self._per_layer(self._layer_fwd_decode)
        for li, lp in enumerate(params["layers"]):
            scales = ((cache.kss[li], cache.vss[li])
                      if cache.quantized else None)
            x, (nk, nv), nscales = layer(
                x, lp, (cache.ks[li], cache.vs[li]), scales, page_table,
                cache.offset)
            cache = cache.set_layer(li, nk, nv,
                                    *(nscales or (None, None)))

        x = rms_norm(x, params["ln_f"], cfg.rms_norm_eps)
        x_full = jax.lax.all_gather(x, self.axis, tiled=True)  # (B, h)
        logits = jnp.dot(x_full, params["lm_head"],
                         preferred_element_type=jnp.float32)
        return logits, cache.inc_offset(1)

    # ------------------------------------------------------------------
    # mesh-level entry points
    # ------------------------------------------------------------------

    def _cache_specs(self, cache):
        n = self.config.num_layers
        q = self.config.quantize_kv_cache
        return KVCache(
            ks=[P(None, self.axis, None, None)] * n,
            vs=[P(None, self.axis, None, None)] * n,
            offset=P(None),
            kss=[P(None, self.axis, None)] * n if q else None,
            vss=[P(None, self.axis, None)] * n if q else None,
        )

    def make_prefill_fn(self):
        specs = self.param_specs()

        def fn(params, input_ids, cache):
            return self.prefill_shard(params, input_ids, cache)

        return jax.shard_map(
            fn, mesh=self.mesh,
            in_specs=(specs, P(None, None), self._cache_specs(None)),
            out_specs=(P(None, self.axis), self._cache_specs(None)),
            check_vma=False)

    def make_decode_fn(self):
        specs = self.param_specs()

        def fn(params, tokens, cache):
            return self.decode_shard(params, tokens, cache)

        return jax.shard_map(
            fn, mesh=self.mesh,
            in_specs=(specs, P(None), self._cache_specs(None)),
            out_specs=(P(None, self.axis), self._cache_specs(None)),
            check_vma=False)

    def _paged_cache_specs(self, page_size: int):
        n = self.config.num_layers
        q = self.config.quantize_kv_cache
        # page_size is a pytree META field: the spec's must match the
        # cache's for the shard_map treedefs to line up.
        return PagedKVCache(
            ks=[P(None, self.axis, None, None)] * n,
            vs=[P(None, self.axis, None, None)] * n,
            page_table=P(None, None),
            offset=P(None),
            kss=[P(None, self.axis, None)] * n if q else None,
            vss=[P(None, self.axis, None)] * n if q else None,
            page_size=page_size,
        )

    def make_paged_decode_fn(self, page_size: int = 16):
        specs = self.param_specs()
        cspecs = self._paged_cache_specs(page_size)

        def fn(params, tokens, cache):
            return self.decode_shard(params, tokens, cache)

        return jax.shard_map(
            fn, mesh=self.mesh,
            in_specs=(specs, P(None), cspecs),
            out_specs=(P(None, self.axis), cspecs),
            check_vma=False)

    def create_paged_cache(self, batch: int, num_pages: int,
                           page_size: int, max_pages_per_seq: int):
        cfg = self.config
        # pool pages replicated in batch, KV heads sharded over tp —
        # same head split as the dense cache, page axis shared.  Zeros
        # are made under jit with the cache's own shardings: each
        # device allocates its head shard and nothing else.
        make = functools.partial(
            PagedKVCache.create,
            cfg.num_layers, num_pages, batch, cfg.num_kv_heads,
            page_size, cfg.head_dim, max_pages_per_seq, self.dtype,
            quantized=cfg.quantize_kv_cache)
        return jax.jit(make, out_shardings=self._named(
            self._paged_cache_specs(page_size)))()

    def create_cache(self, batch: int, max_seq: Optional[int] = None):
        cfg = self.config
        # global cache: kv heads sharded over tp (see create_paged_cache)
        make = functools.partial(
            KVCache.create,
            cfg.num_layers, batch, cfg.num_kv_heads,
            max_seq or cfg.max_seq_len, cfg.head_dim, self.dtype,
            quantized=cfg.quantize_kv_cache)
        return jax.jit(make, out_shardings=self._named(
            self._cache_specs(None)))()


def _interleave_gate_up(gate, up, world: int):
    """Stack gate/up as [gate_r | up_r] per rank so each rank's column
    shard contains its own gate and up halves."""
    ffn = gate.shape[1]
    f_loc = ffn // world
    return jnp.concatenate([
        jnp.concatenate([gate[:, r*f_loc:(r+1)*f_loc],
                         up[:, r*f_loc:(r+1)*f_loc]], axis=1)
        for r in range(world)], axis=1)
