"""The system under test for the Nemotron-H family: the program's
`NemotronH` model (one mixer a layer, named by the published pattern:
Mamba-2 over a recurrent state a slot, softmax attention without
positions over paged K/V, a share of a latent expert layer of
two-matrix squared-ReLU experts) behind the same
`ContinuousBatchingScheduler` and paged KV layout as the other
adapters.  This is the only file of this family's benchmark that
imports the program; everything it hands back is counts, clock readings
and the program's own objects.

Weights are the benchmark's (`cellbench.references.nemotron_h`,
published layout, from the seed); this file lays them into the
program's parameter tree on the device, one jitted call a layer: a
state-space layer's as they are, an attention layer's q | k | v side by
side, the HELD experts of an expert layer stacked block by block.

One chip: the family is not built for tp > 1 (the program asserts it).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cellbench.references import nemotron_h as published
# importing the program places JAX's persistent compile cache; a
# program without this family fails here, before any device is touched
from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.models.nemotron_h import NemotronH
from triton_distributed_tpu.serving import (
    ContinuousBatchingScheduler, FinishReason, Request, SchedulerConfig)

#: Names the device trace gives the programs of this path ("XLA
#: Modules" line), as prefixes: the masked decode step, the bucketed
#: prefill, and the paged insert (the scheduler's, as for the others).
TRACE_MODULES = {"decode": "jit_body", "prefill": "jit_prefill_shard",
                 "insert": "jit_insert"}


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
        self.model_cfg = ModelConfig(
            architecture=config["model_type"],
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            intermediate_size=config["intermediate_size"],
            num_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            rms_norm_eps=config["layer_norm_epsilon"],
            rope_theta=config["rope_theta"], qk_norm=False,
            # the family rotates nothing (the file's `assumed`)
            use_rope=False,
            tie_word_embeddings=config["tie_word_embeddings"],
            max_seq_len=self.max_seq, dtype=config["torch_dtype"],
            num_experts=self.dims["experts_of_layer"],
            experts_held=self.dims["held"],
            num_experts_per_tok=config["num_experts_per_tok"],
            moe_intermediate_size=config["moe_intermediate_size"],
            n_shared_experts=config["n_shared_experts"],
            routed_scaling_factor=config["routed_scaling_factor"],
            norm_topk_prob=config["norm_topk_prob"],
            layer_pattern=config["hybrid_override_pattern"],
            mamba_num_heads=config["mamba_num_heads"],
            mamba_head_dim=config["mamba_head_dim"],
            mamba_n_groups=config["n_groups"],
            ssm_state_size=config["ssm_state_size"],
            mamba_conv_size=config["conv_kernel"],
            moe_act=config["mlp_hidden_act"],
            moe_latent_size=config["moe_latent_size"],
            moe_shared_intermediate_size=config[
                "moe_shared_expert_intermediate_size"])
        self.mesh = Mesh(np.array(devices), ("tp",))
        self.model = NemotronH(self.model_cfg, self.mesh, mode="fused")
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
        side = lambda w, *names: jnp.concatenate(       # noqa: E731
            [w[n] for n in names], axis=1)

        def layer(key, kind):
            w = rounded(published.layer_weights(key, dims, kind))
            ln = w.pop("ln")
            if kind == published.SSM:
                mixer = w
            elif kind == published.ATTN:
                mixer = {"wqkv": side(w, "q", "k", "v"), "wo": w["o"]}
            else:
                blocks = [rounded(published.expert_weights(key, dims, b))
                          for b in published.held_blocks(dims)]
                mixer = {
                    "router": w["router"], "router_bias": w["e_bias"],
                    **{k: jnp.concatenate([b[k] for b in blocks])
                       for k in ("up", "down")},
                    "shared": {"up": w["shared_up"],
                               "down": w["shared_down"]},
                    "latent_down": w["latent_down"],
                    "latent_up": w["latent_up"]}
            return {"ln": ln, "mixer": mixer}

        kinds = dims["hybrid_override_pattern"]
        make = {kind: jax.jit(
            lambda key, kind=kind: layer(key, kind),
            out_shardings=named(specs["layers"][kinds.index(kind)]))
            for kind in set(kinds)}
        make_ends = jax.jit(
            lambda key: rounded(published.end_weights(key, dims)),
            out_shardings=named({k: specs[k] for k in
                                 ("embed", "ln_f", "lm_head")}))
        key = published.base_key(seed)
        params = make_ends(key)
        params["layers"] = [make[kind](published.layer_key(key, i))
                            for i, kind in enumerate(kinds)]
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
