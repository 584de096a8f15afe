"""SDAR-MoE family (`model_type` ``sdar_moe``): a Qwen3-MoE-shaped
decoder that GENERATES BY DIFFUSION OVER BLOCKS.

    x <- x + Attn(RMSNorm(x));  x <- x + MoE(RMSNorm(x))
    final RMSNorm, untied head

``Attn`` is grouped-query attention with q/k RMSNorm a head and RoPE
(`layers.tp_attn.TPAttention`) under a BLOCK-causal mask: with block
length ``B`` position i sees position j iff ``j // B <= i // B`` —
causal across blocks, bidirectional inside one, in the prompt too.
``MoE`` is `layers.moe_mlp.SparseMoE` routed by softmax scores with no
selection bias and no shared expert, dropless, in every layer.

A sequence grows a block of ``B`` positions at a time.  A new block is
``B`` mask tokens; a DENOISE pass runs the model over the block's
positions (which attend every committed earlier block through the
pages, and the whole of their own block), takes the arg-max token and
its probability at each still-masked position and reveals some of
them.  Once none is masked the block is FINISHED, and later blocks may
read its K/V only as its final tokens give it: the finished block goes
through the layers once more — its COMMIT — and that rides on the next
block's first denoise pass.  So a pass feeds TWO block-widths a
sequence, the block just finished in front (where there is one: else
that half is dead) and the block in flight behind it, and yields
between 1 and ``B`` tokens; a block costs as many passes as it has
denoise steps, and no pass is a commit alone.  No position's logits are
shifted: position p's logits predict the token AT p.

The model stands behind the entry points the scheduler calls on the
other families (`make_prefill_fn`, `make_paged_decode_fn`,
`create_paged_cache`, `create_cache`); ``block_length`` tells the
scheduler that its paged step is a block pass
(`serving.engine_batched.make_block_pass_fn` composes the reveal and
the two halves' turn-over around `decode_shard`):

- the prefill covers the prompt's WHOLE blocks only (the scheduler
  sets the cursor to ``(len // B) * B``; the tail enters the first
  block in flight already revealed: its K/V depends on the tokens
  generated beside it).  Under the block-causal mask those positions
  see nothing at or past the cursor, so the padded bucket is exact;
- `decode_shard` is one pass: both halves' tokens (mask id where not
  revealed) through the layers, their K/V written into the mapped
  pages — the front half's final, the back half's provisional — and
  attention block-causal over the two blocks in one read of the pages
  (`TPAttention.block_paged`), the head over the ``B`` positions of
  the block in flight alone.  The cursor is the engine's to move.

ONE device (``tp`` of size 1); block generation at tp > 1 is not built
(ROADMAP Reach).  Greedy only.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_distributed_tpu.kernels.matmul import MatmulConfig
from triton_distributed_tpu.layers.moe_mlp import MOE_STATS, SparseMoE
from triton_distributed_tpu.layers.tp_attn import TPAttention, rms_norm
from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.models.kv_cache import KVCache, PagedKVCache

__all__ = ["SdarMoe"]

#: Published (HF) parameter names of one layer -> where they go here;
#: `{i}` the layer, `{e}` the expert.  Projections are stored
#: `(out, in)` there and `(in, out)` here.
HF_LAYER_NAMES = {
    "q": "model.layers.{i}.self_attn.q_proj.weight",
    "k": "model.layers.{i}.self_attn.k_proj.weight",
    "v": "model.layers.{i}.self_attn.v_proj.weight",
    "o": "model.layers.{i}.self_attn.o_proj.weight",
    "q_norm": "model.layers.{i}.self_attn.q_norm.weight",
    "k_norm": "model.layers.{i}.self_attn.k_norm.weight",
    "ln1": "model.layers.{i}.input_layernorm.weight",
    "ln2": "model.layers.{i}.post_attention_layernorm.weight",
    "router": "model.layers.{i}.mlp.gate.weight",
    "gate": "model.layers.{i}.mlp.experts.{e}.gate_proj.weight",
    "up": "model.layers.{i}.mlp.experts.{e}.up_proj.weight",
    "down": "model.layers.{i}.mlp.experts.{e}.down_proj.weight",
}
HF_END_NAMES = {"embed": "model.embed_tokens.weight",
                "ln_f": "model.norm.weight", "lm_head": "lm_head.weight"}


class SdarMoe:
    #: What a pass leaves in the cache's `stats`, in order.
    STATS = MOE_STATS

    def __init__(self, config: ModelConfig, mesh: Mesh, axis: str = "tp",
                 mode: str = "fused", interpret: Optional[bool] = None,
                 gemm: Optional[MatmulConfig] = None):
        assert config.is_moe and config.block_length > 1, config
        assert mesh.shape[axis] == 1, (
            f"{type(self).__name__} runs on one device; "
            f"{axis}={mesh.shape[axis]} is not built")
        assert not config.quantize_kv_cache, "no int8 cache for a block"
        assert config.block_length % config.denoising_steps == 0, (
            config.block_length, config.denoising_steps)
        assert config.remasking in ("sequential",
                                    "low_confidence_static"), (
            f"remasking {config.remasking!r}: a schedule whose yield a "
            f"pass the host cannot predict is not built")
        self.config = config
        self.mesh = mesh
        self.axis = axis
        self.world = 1
        self.mode = mode
        self.interpret = interpret
        self.dtype = jnp.dtype(config.dtype)
        self.attn = TPAttention(
            axis=axis, world_size=1, hidden=config.hidden_size,
            num_heads=config.num_heads,
            num_kv_heads=config.num_kv_heads, head_dim=config.head_dim,
            rope_theta=config.rope_theta, qk_norm=config.qk_norm,
            block=config.block_length, mode=mode,
            gemm=gemm or MatmulConfig(), interpret=interpret)
        self.moe = SparseMoE(
            hidden=config.hidden_size, ffn=config.moe_intermediate_size,
            num_experts=config.num_experts,
            topk=config.num_experts_per_tok,
            n_shared=config.n_shared_experts,
            routed_scaling=config.routed_scaling_factor,
            norm_topk_prob=config.norm_topk_prob, mode=mode,
            interpret=interpret, scoring=config.moe_scoring)

    @property
    def block_length(self) -> int:
        """What tells the scheduler and the page pool that this model
        generates by blocks; the schedule's other sizes are the
        config's (``denoising_steps``, ``remasking``,
        ``mask_token_id``)."""
        return self.config.block_length

    # ------------------------------------------------------------------
    # parameters
    # ------------------------------------------------------------------

    def set_mode(self, mode: str):
        self.mode = mode
        self.attn = dataclasses.replace(self.attn, mode=mode)
        self.moe = dataclasses.replace(self.moe, mode=mode)

    def _named(self, specs):
        return jax.tree.map(
            lambda sp: NamedSharding(self.mesh, sp), specs,
            is_leaf=lambda x: isinstance(x, P))

    def param_specs(self):
        layer = {"ln1": P(None), "ln2": P(None),
                 "attn": self.attn.global_param_specs(),
                 "mlp": self.moe.param_specs()}
        return {"embed": P(None, None),
                "layers": [layer] * self.config.num_layers,
                "ln_f": P(None),
                "lm_head": P(None, self.axis)}

    def init_params(self, key):
        """Seeded parameters, made on the device a layer at a time."""
        cfg = self.config
        h = cfg.hidden_size
        specs = self.param_specs()

        def one_layer(k):
            k1, k2 = jax.random.split(k)
            return {"ln1": jnp.ones((h,), self.dtype),
                    "ln2": jnp.ones((h,), self.dtype),
                    "attn": self.attn.init_params(k1, self.dtype),
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
        make = jax.jit(one_layer,
                       out_shardings=self._named(specs["layers"][0]))
        params["layers"] = [make(keys[i]) for i in range(cfg.num_layers)]
        return params

    def load_state_dict(self, sd):
        """The published layout (``sd``: name -> array, `HF_LAYER_NAMES`
        / `HF_END_NAMES`) into this model's parameter tree: projections
        transposed to `(in, out)`, q | k | v side by side, the experts
        stacked."""
        cfg = self.config

        def t(name, dtype=None):
            return jnp.asarray(sd[name], dtype or self.dtype).T

        def vec(name):
            return jnp.asarray(sd[name], self.dtype)

        layers = []
        for i in range(cfg.num_layers):
            n = {k: v.format(i=i, e="{e}")
                 for k, v in HF_LAYER_NAMES.items()}
            experts = {k: jnp.stack([t(n[k].format(e=e))
                                     for e in range(cfg.num_experts)])
                       for k in ("gate", "up", "down")}
            layers.append({
                "ln1": vec(n["ln1"]), "ln2": vec(n["ln2"]),
                "attn": {"wqkv": jnp.concatenate(
                    [t(n["q"]), t(n["k"]), t(n["v"])], axis=1),
                    "wo": t(n["o"]), "q_norm": vec(n["q_norm"]),
                    "k_norm": vec(n["k_norm"])},
                "mlp": {"router": t(n["router"], jnp.float32),
                        **experts}})
        return {"embed": vec(HF_END_NAMES["embed"]), "layers": layers,
                "ln_f": vec(HF_END_NAMES["ln_f"]),
                "lm_head": t(HF_END_NAMES["lm_head"])}

    def load_hf_weights(self, model_name_or_path: str):
        """`load_state_dict` of a HuggingFace checkpoint."""
        import numpy as np
        from transformers import AutoModelForCausalLM
        hf = AutoModelForCausalLM.from_pretrained(
            model_name_or_path, torch_dtype="float32",
            trust_remote_code=True)
        return self.load_state_dict(
            {k: np.asarray(v) for k, v in hf.state_dict().items()})

    # ------------------------------------------------------------------
    # per-device forward bodies (called inside shard_map)
    # ------------------------------------------------------------------

    def _layer_fwd_prefill(self, x, lp, *, batch):
        eps = self.config.rms_norm_eps
        h, kv = self.attn.prefill(rms_norm(x, lp["ln1"], eps),
                                  lp["attn"], batch)
        x = x + h
        h, _ = self.moe(rms_norm(x, lp["ln2"], eps), lp["mlp"],
                        phase="prefill")
        return x + h, kv

    def _layer_fwd_block(self, x, lp, kv, page_table, cursor, active,
                         folded):
        eps = self.config.rms_norm_eps
        h, kv = self.attn.block_paged(
            rms_norm(x, lp["ln1"], eps), lp["attn"], kv, page_table,
            cursor, active, folded)
        x = x + h
        # (a pass's rows are a decode step's times two blocks: the
        # grouped GEMMs keep the decode step's names)
        h, stats = self.moe(rms_norm(x, lp["ln2"], eps), lp["mlp"],
                            phase="decode")
        return x + h, kv, stats

    def prefill_shard(self, params, input_ids, cache: Optional[KVCache]):
        """input_ids: (B, S), S a multiple of the block length.
        Returns (logits (B, V) float32 AT each sequence's last position
        — no shift: what that position's token should be —, cache).
        The scheduler reads the cache alone: a request's first tokens
        come from its first block pass."""
        cfg = self.config
        b, s = input_ids.shape
        assert s % cfg.block_length == 0, (s, cfg.block_length)
        x = params["embed"][input_ids].reshape(b * s, -1)
        layer = jax.jit(functools.partial(self._layer_fwd_prefill,
                                          batch=b))
        for li, lp in enumerate(params["layers"]):
            x, (k, v) = layer(x, lp)
            if cache is not None:
                cache = cache.write_prefill(li, k, v)
        x = rms_norm(x, params["ln_f"], cfg.rms_norm_eps)
        logits = jnp.dot(x.reshape(b, s, -1)[:, -1], params["lm_head"],
                         preferred_element_type=jnp.float32)
        if cache is not None:
            cache = cache.set_offset(s)
        return logits, cache

    def decode_shard(self, params, tokens, cache: PagedKVCache, active,
                     folded):
        """One pass over every row's two block-widths.  tokens: (B, 2n)
        — the block just finished in front, the block in flight behind
        it, as they are fed (mask id where not revealed); ``folded``
        (B,) bool: the row HAS a finished block in front, standing at
        ``cache.offset`` with the block in flight a block further on —
        else the front half is dead and the block in flight stands at
        ``cache.offset``, which does NOT move here; ``active`` (B,)
        bool (an inactive row writes to the trash page).  Returns
        (logits (B, n, V) float32 OF THE BLOCK IN FLIGHT, cache) — the
        cache's `stats` hold what the expert layers counted
        (`MOE_STATS`; every row fed, a dead half's too)."""
        cfg = self.config
        b, w = tokens.shape
        n = cfg.block_length
        assert w == 2 * n, (w, n)
        x = params["embed"][tokens].reshape(b * w, -1)
        layer = jax.jit(self._layer_fwd_block)
        counted = []
        for li, lp in enumerate(params["layers"]):
            x, (k, v), stats = layer(
                x, lp, (cache.ks[li], cache.vs[li]), cache.page_table,
                cache.offset, active, folded)
            cache = cache.set_layer(li, k, v)
            counted.append(stats)
        # the finished block's tokens are known: no head over them
        x = x.reshape(b, w, -1)[:, n:].reshape(b * n, -1)
        x = rms_norm(x, params["ln_f"], cfg.rms_norm_eps)
        logits = jnp.dot(x, params["lm_head"],
                         preferred_element_type=jnp.float32)
        if cache.stats is not None:
            c = jnp.stack(counted)                      # (layers, 3)
            cache = dataclasses.replace(cache, stats=jnp.concatenate(
                [c[:, :2].sum(axis=0), c[:, 2:].max(axis=0)]))
        return logits.reshape(b, n, -1), cache

    # ------------------------------------------------------------------
    # mesh-level entry points
    # ------------------------------------------------------------------

    def _cache_specs(self):
        n = self.config.num_layers
        pools = [P(None, None, None, None)] * n
        return KVCache(ks=pools, vs=pools, offset=P(None))

    def _paged_cache_specs(self, page_size: int):
        n = self.config.num_layers
        pools = [P(None, None, None, None)] * n
        return PagedKVCache(
            ks=pools, vs=pools, page_table=P(None, None),
            offset=P(None), stats=P(None), page_size=page_size)

    def make_prefill_fn(self):
        return jax.shard_map(
            self.prefill_shard, mesh=self.mesh,
            in_specs=(self.param_specs(), P(None, None),
                      self._cache_specs()),
            out_specs=(P(None, self.axis), self._cache_specs()),
            check_vma=False)

    def make_paged_decode_fn(self, page_size: int = 16):
        """The block pass's model half: ``(params, tokens (B, 2n),
        cache, active (B,), folded (B,)) -> (logits (B, n, V),
        cache)``."""
        assert page_size % self.block_length == 0, (
            "a block must not straddle a page", page_size,
            self.block_length)
        cspecs = self._paged_cache_specs(page_size)
        return jax.shard_map(
            self.decode_shard, mesh=self.mesh,
            in_specs=(self.param_specs(), P(None, None), cspecs,
                      P(None), P(None)),
            out_specs=(P(None, None, self.axis), cspecs),
            check_vma=False)

    def create_paged_cache(self, batch: int, num_pages: int,
                           page_size: int, max_pages_per_seq: int):
        cfg = self.config
        make = functools.partial(
            PagedKVCache.create, cfg.num_layers, num_pages, batch,
            cfg.num_kv_heads, page_size, cfg.head_dim,
            max_pages_per_seq, self.dtype, num_stats=len(MOE_STATS))
        return jax.jit(make, out_shardings=self._named(
            self._paged_cache_specs(page_size)))()

    def create_cache(self, batch: int, max_seq: Optional[int] = None):
        """The single-row cache a bucketed prefill fills; the dense-slot
        decode layout is not built for this family."""
        cfg = self.config
        make = functools.partial(
            KVCache.create, cfg.num_layers, batch, cfg.num_kv_heads,
            max_seq or cfg.max_seq_len, cfg.head_dim, self.dtype)
        return jax.jit(make, out_shardings=self._named(
            self._cache_specs()))()
