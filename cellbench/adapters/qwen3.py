"""The system under test for the Qwen3 dense family: the program's
`Qwen3` model behind its `ContinuousBatchingScheduler` with the paged
KV layout.  This is the only file of the benchmark that imports the
program; everything it hands back is counts, clock readings and the
program's own objects.

Weights are the benchmark's (`cellbench.references.qwen3`, published
layout, from the seed); this file lays them into the parameter tree
the program's `load_hf_weights` would build — per-rank interleaved
`[q_r | k_r | v_r]` and `[gate_r | up_r]` columns — on the devices,
already sharded, one jitted call a layer.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cellbench.references import qwen3 as published
# importing the program places JAX's persistent compile cache
from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.models.qwen import Qwen3
from triton_distributed_tpu.serving import (
    ContinuousBatchingScheduler, FinishReason, Request, SchedulerConfig)

#: Names the device trace gives the programs of this path ("XLA
#: Modules" line), as prefixes: the masked decode step, the bucketed
#: prefill, and the paged insert.
TRACE_MODULES = {"decode": "jit_body", "prefill": "jit_fn",
                 "insert": "jit_insert"}


def _interleave(parts, world: int):
    """Columns of each part split over ranks, laid `[a_r | b_r | ...]`
    rank by rank (what each rank's column shard must hold)."""
    cols = []
    for r in range(world):
        for p in parts:
            n = p.shape[1] // world
            cols.append(p[:, r * n:(r + 1) * n])
    return jnp.concatenate(cols, axis=1)


class System:
    """One served model.  ``config`` is the configuration file's
    object; ``devices`` the chips of the cell."""

    def __init__(self, config: dict, seed: int, devices,
                 weights: str = "served"):
        self.config = config
        self.dims = published.dims_of(config)
        serving = config["serving"]
        self.num_slots = int(serving["num_slots"])
        self.max_seq = int(serving["max_seq"])
        self.world = len(devices)
        self.model_cfg = ModelConfig(
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
            max_seq_len=self.max_seq, dtype=config["torch_dtype"])
        self.mesh = Mesh(np.array(devices), ("tp",))
        self.model = Qwen3(self.model_cfg, self.mesh, mode="fused")
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
        dims, world = self.dims, self.world
        rounded = (published.fp8_rounded if weights == "fp8"
                   else lambda w: w)
        specs = self.model.param_specs()
        named = lambda tree: jax.tree.map(       # noqa: E731
            lambda sp: NamedSharding(self.mesh, sp), tree,
            is_leaf=lambda x: isinstance(x, P))

        def layer(key):
            w = rounded(published.layer_weights(key, dims))
            return {"ln1": w["ln1"], "ln2": w["ln2"],
                    "attn": {"wqkv": _interleave(
                                 [w["q"], w["k"], w["v"]], world),
                             "wo": w["o"], "q_norm": w["q_norm"],
                             "k_norm": w["k_norm"]},
                    "mlp": {"gate_up": _interleave(
                                [w["gate"], w["up"]], world),
                            "down": w["down"]}}

        make_layer = jax.jit(layer,
                             out_shardings=named(specs["layers"][0]))
        make_ends = jax.jit(
            lambda key: rounded(published.end_weights(key, dims)),
            out_shardings=named({k: specs[k] for k in
                                 ("embed", "ln_f", "lm_head")}))
        key = published.base_key(seed)
        params = make_ends(key)
        params["layers"] = [
            make_layer(published.layer_key(key, i))
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
