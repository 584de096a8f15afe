"""SmallThinker family (`model_name` ``smallthinker_*``): full layers
without positions and sliding-window layers mixed 1 : 3, in a
SEQUENTIAL block whose router reads the stream BEFORE attention.

    r = h W_r                       (float32; h as it ENTERS the layer)
    h' = h + Attn_l(RMS_1(h))
    h'' = h' + sum over top-k e of softmax(r[chosen])_e expert_e(RMS_2(h'))

``Attn_l`` is named by ``config.layer_types[l]`` (the published
``sliding_window_layout`` / ``rope_layout``, which are one list):
``sliding_attention`` — grouped-query attention that rotates a
dimension with the one half a head away and sees the last
``sliding_window`` tokens (`TPAttention` with ``window``);
``full_attention`` — causal, NO positional encoding.  A KV head serves
``num_heads / num_kv_heads`` query heads — 7 as published, no power of
two: the kernels take the group as it is (`kernels.flash_decode`,
`kernels.flash_attention`).  The feed-forward of EVERY layer is a
dropless sparse one (`layers.moe_mlp.SparseMoE`): softmax scores, the
top-k renormalised, gated-ReLU experts (``relu(x W_g) * (x W_u)``), no
shared expert, every expert on this chip — and its route is taken from
the layer's INPUT (``route_from``), not from what the experts read.  Final RMSNorm; the head is
its own matrix unless the config ties it.

It is a `models.window_layers.WindowAndFullLayers`: the pools by layer
kind, the walk over the layers and the chunk program are written there,
once, for this family and `models.cohere2_moe`.  ``window`` tells the
scheduler of the window layers' pool; nothing else is a knob.

ONE device (``tp`` of size 1); tensor parallelism, a window-aware prefix
hit and the int8 pool under a window are not built (ROADMAP Reach).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from triton_distributed_tpu.kernels.matmul import MatmulConfig
from triton_distributed_tpu.layers.moe_mlp import MOE_STATS, SparseMoE
from triton_distributed_tpu.layers.tp_attn import rms_norm
from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.models.window_layers import (
    FULL, WindowAndFullLayers)

__all__ = ["SmallThinker", "PREFILL_CHUNK"]

#: Tokens of a prompt the scheduler prefills between two decode steps
#: (`prefill_shard_suffix`).  A chunk streams every expert it hits
#: once, so a shorter one costs tokens a second and a longer one
#: lengthens the token gap of every running row: settled on the chip by
#: PR 40's rule (past the knee 512 costs 7.4% of the tokens a second
#: for a gap of 33 against 41 ms: PERF.md section 5, PR 48).
PREFILL_CHUNK = 1024


class SmallThinker(WindowAndFullLayers):
    #: What a decode step leaves in the cache's `stats`: the expert
    #: layers' counters summed over the layers (the busiest expert's
    #: share in the worst).
    STATS = MOE_STATS

    def __init__(self, config: ModelConfig, mesh: Mesh, axis: str = "tp",
                 mode: str = "fused", interpret: Optional[bool] = None,
                 gemm: Optional[MatmulConfig] = None):
        assert not config.n_shared_experts and config.experts_held is None
        super().__init__(config, mesh, axis, mode, interpret)
        self._set_layer_kinds(config.layer_types, config.sliding_window,
                              gemm)
        self.prefill_chunk = PREFILL_CHUNK
        self.moe = SparseMoE(
            hidden=config.hidden_size, ffn=config.moe_intermediate_size,
            num_experts=config.num_experts,
            topk=config.num_experts_per_tok, n_shared=0,
            routed_scaling=config.routed_scaling_factor,
            norm_topk_prob=config.norm_topk_prob, mode=mode,
            interpret=interpret, scoring=config.moe_scoring,
            act=config.moe_act)

    # ------------------------------------------------------------------
    # parameters
    # ------------------------------------------------------------------

    def _layer_specs(self):
        return {"ln1": P(None), "ln2": P(None),
                "attn": self.attn[FULL].global_param_specs(),
                "moe": self.moe.param_specs()}

    def param_specs(self):
        specs = {"embed": P(None, None),
                 "layers": [self._layer_specs() for _ in self.layer_kinds],
                 "ln_f": P(None)}
        if not self.config.tie_word_embeddings:
            specs["lm_head"] = P(None, None)
        return specs

    def init_layer(self, key, kind):
        """(Both kinds of layer hold the same parameters.)"""
        ka, km = jax.random.split(key)
        ones = jnp.ones((self.config.hidden_size,), self.dtype)
        return {"ln1": ones, "ln2": ones,
                "attn": self.attn[FULL].init_params(ka, self.dtype),
                "moe": self.moe.init_params(km, self.dtype)}

    # ------------------------------------------------------------------
    # the block (called inside shard_map)
    # ------------------------------------------------------------------

    def _ffn(self, entered, h, lp, phase):
        """``h`` (the stream after attention) through the expert layer,
        routed by ``entered`` — the stream as it entered the layer."""
        m = rms_norm(h, lp["ln2"], self.config.rms_norm_eps)
        f, stats = self.moe(m, lp["moe"], phase=phase, route_from=entered)
        return h + f, stats

    def _layer_fwd_prefill(self, x, lp, *, batch, kind):
        u = rms_norm(x, lp["ln1"], self.config.rms_norm_eps)
        a, kept = self.attn[kind].prefill(u, lp["attn"], batch)
        return self._ffn(x, x + a, lp, "prefill")[0], kept

    def _layer_fwd_suffix(self, x, lp, kept, page_ids, start, *, kind):
        u = rms_norm(x, lp["ln1"], self.config.rms_norm_eps)
        a, kept = self.attn[kind].prefill_suffix(u, lp["attn"], start,
                                                 kept, page_ids)
        return self._ffn(x, x + a, lp, "prefill")[0], kept

    def _layer_fwd_decode(self, x, lp, kept, table, offset, *, kind):
        u = rms_norm(x, lp["ln1"], self.config.rms_norm_eps)
        a, kept, _ = self.attn[kind].decode_paged(u, lp["attn"], kept,
                                                  table, offset)
        y, stats = self._ffn(x, x + a, lp, "decode")
        return y, kept, stats

    def _logits(self, x, params):
        """The final norm's rows through the head, float32."""
        x = rms_norm(x, params["ln_f"], self.config.rms_norm_eps)
        if self.config.tie_word_embeddings:
            return jax.lax.dot_general(
                x, params["embed"], (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        return jnp.dot(x, params["lm_head"],
                       preferred_element_type=jnp.float32)
