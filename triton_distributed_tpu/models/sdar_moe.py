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

The model is a `models.base.ServedModel`; ``block_length`` tells the
scheduler that its paged step is a block pass
(`serving.engine_batched.make_block_pass_fn` composes the reveal and
the two halves' turn-over around `decode_shard`):

- the prefill covers the prompt's WHOLE blocks only (the scheduler
  sets the cursor to ``(len // B) * B``; the tail enters the first
  block in flight already revealed: its K/V depends on the tokens
  generated beside it).  Under the block-causal mask those positions
  see nothing at or past the cursor, so the padded bucket is exact;
- `prefill_shard_suffix` is the prefill of ONE CHUNK of a prompt over
  the rows its earlier chunks (or a prefix hit) left in the page pool,
  under the same mask (`TPAttention.prefill_suffix`): the scheduler
  carries a prompt longer than ``prefill_chunk`` tokens
  (`PREFILL_CHUNK`) out a chunk between two block passes, one piece a
  pass, and prefills only what a prefix hit left.  A chunk and a page
  hold whole blocks, so every chunk starts at a block's edge;
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
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from triton_distributed_tpu.kernels.matmul import MatmulConfig
from triton_distributed_tpu.layers.moe_mlp import MOE_STATS, SparseMoE
from triton_distributed_tpu.layers.tp_attn import TPAttention, rms_norm
from triton_distributed_tpu.models.base import ServedModel
from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.models.kv_cache import KVCache, PagedKVCache

__all__ = ["SdarMoe", "PREFILL_CHUNK"]

#: Tokens of a prompt the scheduler prefills between two block passes
#: (`prefill_shard_suffix`); a multiple of the page and of the block.
#: A chunk streams the experts once, so a shorter one costs device time
#: and a longer one lengthens the token gap of every running row:
#: settled on the chip by PR 40's rule (of 256 / 384 / 512 the shortest
#: that keeps the tokens a second within 5% of the unchunked prefill's
#: on two seeds: 512 lost 3.3-3.8%, 256 lost 12%, and 384 is no prefill
#: bucket — prompts of 257-384 tokens then run the 512 bucket WHOLE,
#: a program the benchmark's warm-up, which sends each bucket's full
#: length, never meets; PERF.md section 5 has the sweep of PR 49).
PREFILL_CHUNK = 512

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


class SdarMoe(ServedModel):
    #: What a pass leaves in the cache's `stats`, in order.
    STATS = MOE_STATS

    def __init__(self, config: ModelConfig, mesh: Mesh, axis: str = "tp",
                 mode: str = "fused", interpret: Optional[bool] = None,
                 gemm: Optional[MatmulConfig] = None):
        assert config.is_moe and config.block_length > 1, config
        assert not config.quantize_kv_cache, "no int8 cache for a block"
        assert config.block_length % config.denoising_steps == 0, (
            config.block_length, config.denoising_steps)
        assert config.remasking in ("sequential",
                                    "low_confidence_static"), (
            f"remasking {config.remasking!r}: a schedule whose yield a "
            f"pass the host cannot predict is not built")
        super().__init__(config, mesh, axis, mode, interpret)
        #: What tells the scheduler and the page pool that this model
        #: generates by blocks; the schedule's other sizes are the
        #: config's (``denoising_steps``, ``remasking``,
        #: ``mask_token_id``).
        self.block_length = config.block_length
        self.prefill_chunk = PREFILL_CHUNK
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

    # ------------------------------------------------------------------
    # parameters
    # ------------------------------------------------------------------

    def param_specs(self):
        layer = {"ln1": P(None), "ln2": P(None),
                 "attn": self.attn.global_param_specs(),
                 "mlp": self.moe.param_specs()}
        return {"embed": P(None, None),
                "layers": [layer] * self.config.num_layers,
                "ln_f": P(None),
                "lm_head": P(None, self.axis)}

    def init_layer(self, key, kind):
        h = self.config.hidden_size
        k1, k2 = jax.random.split(key)
        return {"ln1": jnp.ones((h,), self.dtype),
                "ln2": jnp.ones((h,), self.dtype),
                "attn": self.attn.init_params(k1, self.dtype),
                "mlp": self.moe.init_params(k2, self.dtype)}

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

    def _layer_fwd_prefill(self, x, lp, kept=(), page_ids=None,
                           start=None, *, batch):
        """(x, the layer's (k, v) for the cache).  ``kept``: nothing
        where the rows start their sequences; for a chunk of one
        sequence the layer's (k pool, v pool), whose pages ``page_ids``
        names hold its rows below position ``start``."""
        eps = self.config.rms_norm_eps
        h = rms_norm(x, lp["ln1"], eps)
        if kept:
            h, kv = self.attn.prefill_suffix(h, lp["attn"], start, kept,
                                             page_ids)
        else:
            h, kv = self.attn.prefill(h, lp["attn"], batch)
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
        layer = self._per_layer(self._layer_fwd_prefill, batch=b)
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

    def prefill_shard_suffix(self, params, input_ids, start,
                             cache: KVCache, pools, page_ids):
        """One chunk of one prompt.  input_ids: (1, C), the tokens at
        positions ``start + arange(C)`` (a last chunk right-padded), C
        and ``start`` multiples of the block length; ``cache``: the
        single-row cache of `create_cache`, C long; ``pools``: the
        paged cache's (ks, vs), read and not written — the prompt's
        rows below ``start`` lie there, at the pages ``page_ids`` (T,)
        names in logical order.  Under the block-causal mask a row
        sees all of them and its own block, which ends inside the
        chunk: what lies behind a prompt's last whole block (its tail,
        the padding) is seen by no row below the cursor, as in the
        padded bucket of `prefill_shard`.  Returns ``cache`` holding
        the chunk's K/V rows at LOCAL positions [0, C) — the paged
        insert puts them into their pages.  No logits: a request's
        first tokens come from its first block pass — so the last
        layer's expert block is read by nothing and the compiler
        leaves it out with the head."""
        b, s = input_ids.shape
        assert b == 1, "a chunk is one sequence's"
        ks, vs = pools
        x = params["embed"][input_ids].reshape(s, -1)
        start = jnp.asarray(start, jnp.int32).reshape(())
        layer = self._per_layer(self._layer_fwd_prefill, batch=1)
        for li, lp in enumerate(params["layers"]):
            x, (k, v) = layer(x, lp, (ks[li], vs[li]), page_ids, start)
            cache = cache.write_prefill(li, k, v)
        return cache.set_offset(s)

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
