"""The system under test for the SDAR-MoE family: the program's
`SdarMoe` model (grouped-query attention under the block-causal mask, a
dropless softmax-routed expert layer in every block, generation by
diffusion over blocks) behind the same `ContinuousBatchingScheduler`,
pipelined dispatch and paged KV layout as the other adapters.  This is
the only file of this family's benchmark that imports the program;
everything it hands back is counts, clock readings and the program's
own objects.

Weights are the benchmark's (`cellbench.references.sdar_moe`, published
layout, from the seed); this file lays them into the program's
parameter tree on the device, one jitted call a layer: q | k | v side
by side, the experts stacked block by block.  The generation's sizes
(block length, denoise passes a block, schedule, mask id) are the
configuration's `generation` and `mask_token_id`.

One chip: the family is not built for tp > 1 (the program asserts it).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cellbench.references import sdar_moe as published
# importing the program places JAX's persistent compile cache; a
# program without this family fails here, before any device is touched
from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.models.sdar_moe import SdarMoe
from triton_distributed_tpu.serving import (
    ContinuousBatchingScheduler, FinishReason, Request, SchedulerConfig)

#: Names the device trace gives the programs of this path ("XLA
#: Modules" line), as prefixes: the block pass (this family's decode
#: step: every slot's block in flight, denoise or commit), the bucketed
#: prefill, and the paged insert (the scheduler's, as for the others).
TRACE_MODULES = {"decode": "jit_block_pass",
                 "prefill": "jit_prefill_shard", "insert": "jit_insert"}


class System:
    """One served model.  ``config`` is the configuration file's
    object; ``devices`` the chips of the cell (one)."""

    def __init__(self, config: dict, seed: int, devices,
                 weights: str = "served"):
        self.config = config
        self.dims = published.dims_of(config)
        serving = config["serving"]
        self.num_slots = int(serving["num_slots"])
        self.max_seq = int(serving["max_seq"])
        self.world = len(devices)
        gen = config["generation"]
        self.model_cfg = ModelConfig(
            architecture=config["model_type"],
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            intermediate_size=config["intermediate_size"],
            num_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            rms_norm_eps=config["rms_norm_eps"],
            rope_theta=config["rope_theta"], qk_norm=True,
            tie_word_embeddings=config["tie_word_embeddings"],
            max_seq_len=self.max_seq, dtype=config["torch_dtype"],
            num_experts=config["num_experts"],
            num_experts_per_tok=config["num_experts_per_tok"],
            moe_intermediate_size=config["moe_intermediate_size"],
            n_shared_experts=0, routed_scaling_factor=1.0,
            norm_topk_prob=config["norm_topk_prob"],
            moe_scoring="softmax",
            block_length=gen["block_length"],
            denoising_steps=gen["denoising_steps"],
            remasking=gen["remasking"],
            mask_token_id=config["mask_token_id"])
        self.mesh = Mesh(np.array(devices), ("tp",))
        self.model = SdarMoe(self.model_cfg, self.mesh, mode="fused")
        self.params = self._make_params(seed, weights)
        jax.block_until_ready(self.params)
        self.weight_bytes = sum(x.nbytes
                                for x in jax.tree.leaves(self.params))
        self.sched = ContinuousBatchingScheduler(
            self.model, self.params,
            SchedulerConfig(
                num_slots=self.num_slots, max_seq=self.max_seq,
                kv_layout="paged",
                kv_budget_bytes=int(serving["kv_budget_bytes_per_chip"]
                                    * self.world),
                max_queue=int(serving["max_queue"])),
            clock=time.monotonic)
        self.buckets = self.sched.buckets
        self.usable_pages = self.sched.slots.usable_pages
        self.page_size = self.sched.slots.page_size
        self.kv_budget_bytes = self.sched.slots.kv_budget_bytes

    # -- weights ----------------------------------------------------------

    def _make_params(self, seed: int, weights: str = "served"):
        """``weights``: "served" (the configuration's bfloat16) or, for
        the control alone, "fp8" (every matmul weight rounded to
        float8_e4m3 before the program gets it)."""
        if weights not in ("served", "fp8"):
            raise ValueError(f"unknown weights {weights!r}")
        dims = self.dims
        rounded = (published.fp8_rounded if weights == "fp8"
                   else lambda w: w)
        specs = self.model.param_specs()
        named = lambda tree: jax.tree.map(       # noqa: E731
            lambda sp: NamedSharding(self.mesh, sp), tree,
            is_leaf=lambda x: isinstance(x, P))

        def layer(key):
            w = rounded(published.layer_weights(key, dims))
            blocks = [rounded(published.expert_weights(key, dims, b))
                      for b in range(dims["num_experts"]
                                     // published.EXPERT_BLOCK)]
            return {"ln1": w["ln1"], "ln2": w["ln2"],
                    "attn": {"wqkv": jnp.concatenate(
                        [w["q"], w["k"], w["v"]], axis=1),
                        "wo": w["o"], "q_norm": w["q_norm"],
                        "k_norm": w["k_norm"]},
                    "mlp": {"router": w["router"],
                            **{k: jnp.concatenate([b[k] for b in blocks])
                               for k in ("gate", "up", "down")}}}

        make = jax.jit(layer, out_shardings=named(specs["layers"][0]))
        make_ends = jax.jit(
            lambda key: rounded(published.end_weights(key, dims)),
            out_shardings=named({k: specs[k] for k in
                                 ("embed", "ln_f", "lm_head")}))
        key = published.base_key(seed)
        params = make_ends(key)
        params["layers"] = [make(published.layer_key(key, i))
                            for i in range(dims["num_hidden_layers"])]
        return params

    def reseed(self, seed: int, weights: str = "served") -> None:
        """Other weights under the same compiled programs (for reading
        many seeds in one process; a run never calls it)."""
        self.params = None
        self.sched.params = None
        self.params = self._make_params(seed, weights)
        jax.block_until_ready(self.params)
        self.sched.params = self.params

    # -- requests ---------------------------------------------------------

    def submit(self, prompt, max_new: int, due: float, on_token):
        """Hand one request to the scheduler, due (and timed from)
        ``due`` on `time.monotonic`'s clock.  Returns the program's
        request, or None with the reason when it was refused."""
        req = Request(prompt, max_new, eos_token_ids=(), seed=0,
                      arrival_time=due, on_token=on_token)
        if self.sched.submit(req):
            return req, None
        return None, req.reject_reason.value

    def step(self) -> dict:
        return self.sched.step()

    def has_work(self) -> bool:
        return self.sched.has_work()

    @staticmethod
    def admitted_at(req):
        """The scheduler's reading of the clock it was given, at the
        step that admitted ``req`` (None while queued)."""
        return req.t_admitted

    @staticmethod
    def finished_ok(req, max_new: int) -> bool:
        return (req.finish_reason == FinishReason.LENGTH
                and len(req.generated) == max_new)

    def used_pages(self) -> int:
        return self.sched.slots.used_pages
