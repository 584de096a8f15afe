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

It is a `models.window_layers.WindowAndFullLayers`: its cache holds
TWO kinds of attention state, and the pools by layer kind, the three
per-device programs that walk the layers and the chunk program are
written THERE, once, for this family and `models.smallthinker`.  Here
are its parameters, its block and its head.  ``window`` tells the
scheduler of the window layers' pool; nothing else is a knob.

ONE device (``tp`` of size 1); tensor parallelism for this family (the
window pools sharded by KV head), the exchange that would make the held
expert layer expert-parallel, a window-aware prefix hit and the int8
pool under a window are not built (ROADMAP Reach).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from triton_distributed_tpu.kernels.matmul import MatmulConfig
from triton_distributed_tpu.layers.moe_mlp import HELD_STATS, SparseMoE
from triton_distributed_tpu.layers.tp_attn import layer_norm
from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.models.window_layers import (
    FULL, WindowAndFullLayers)

__all__ = ["Cohere2Moe", "PREFILL_CHUNK"]

#: Tokens of a prompt the scheduler prefills between two decode steps
#: (`prefill_shard_suffix`).  A chunk streams the held experts once,
#: so a shorter one costs tokens a second and a longer one lengthens the
#: token gap of every running row: settled on the chip by PR 40's rule
#: (PERF.md section 5 has what 1024 and 2048 read, PR 44).
PREFILL_CHUNK = 1024


class Cohere2Moe(WindowAndFullLayers):
    #: What a decode step leaves in the cache's `stats`: the held
    #: experts' counters summed over the layers (the busiest expert's
    #: share in the worst).
    STATS = HELD_STATS

    def __init__(self, config: ModelConfig, mesh: Mesh, axis: str = "tp",
                 mode: str = "fused", interpret: Optional[bool] = None,
                 gemm: Optional[MatmulConfig] = None):
        assert config.experts_held is not None, "which experts are here?"
        super().__init__(config, mesh, axis, mode, interpret)
        self._set_layer_kinds(config.layer_types, config.sliding_window,
                              gemm)
        self.prefill_chunk = PREFILL_CHUNK
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

    # ------------------------------------------------------------------
    # parameters
    # ------------------------------------------------------------------

    def _layer_specs(self):
        return {"ln": P(None),
                "attn": self.attn[FULL].global_param_specs(),
                "moe": self.moe.param_specs()}

    def param_specs(self):
        return {"embed": P(None, None),
                "layers": [self._layer_specs() for _ in self.layer_kinds],
                "ln_f": P(None)}

    def init_layer(self, key, kind):
        """(Both kinds of layer hold the same parameters.)"""
        ka, km = jax.random.split(key)
        return {"ln": jnp.ones((self.config.hidden_size,), self.dtype),
                "attn": self.attn[FULL].init_params(ka, self.dtype),
                "moe": self.moe.init_params(km, self.dtype)}

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

    def _logits(self, x, params):
        """Tied head: the final norm's rows over the held rows of the
        embedding, float32."""
        x = layer_norm(x, params["ln_f"], self.config.rms_norm_eps)
        logits = jax.lax.dot_general(
            x, params["embed"], (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return logits * self.config.logit_scale
