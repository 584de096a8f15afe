"""The system under test for the SmallThinker family: the program's
`SmallThinker` model (a sequential block whose router scores the stream
as it ENTERS the layer, full layers without positions beside window
layers that rotate, 7 query heads a KV head, gated-ReLU experts routed
by softmax with no shared expert, an untied head) behind the same
`ContinuousBatchingScheduler` and paged KV layout as the other
adapters.  This is the only file of this family's benchmark that
imports the program; everything it hands back is counts, clock readings
and the program's own objects.

Weights are the benchmark's (`cellbench.references.smallthinker`,
published layout, from the seed); this file lays them into the
program's parameter tree on the device, one jitted call a layer: q | k |
v side by side, the experts stacked block by block.

One chip: the family is not built for tp > 1 (the program asserts it).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cellbench.references import smallthinker as published
from triton_distributed_tpu.models.config import ModelConfig
# importing the program places JAX's persistent compile cache; a
# program without this family fails here, before any device is touched
from triton_distributed_tpu.models.smallthinker import SmallThinker
from triton_distributed_tpu.serving import (
    ContinuousBatchingScheduler, FinishReason, Request, SchedulerConfig)

#: Names the device trace gives the programs of this path ("XLA
#: Modules" line), as prefixes: the masked decode step, the bucketed
#: prefill and the chunk program, and the paged insert.
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
        # the published keys, mapped by the program's own reader
        self.model_cfg = ModelConfig.from_smallthinker(
            config, max_seq_len=self.max_seq, dtype=config["torch_dtype"])
        self.mesh = Mesh(np.array(devices), ("tp",))
        self.model = SmallThinker(self.model_cfg, self.mesh, mode="fused")
        self.params = self._make_params(seed, weights)
        jax.block_until_ready(self.params)
        self.weight_bytes = sum(x.nbytes
                                for x in jax.tree.leaves(self.params))
        self.sched = ContinuousBatchingScheduler(
            self.model, self.params,
            SchedulerConfig(
                num_slots=self.num_slots, max_seq=self.max_seq,
                kv_layout="paged",
                **({"prefill_buckets": tuple(serving["prefill_buckets"])}
                   if "prefill_buckets" in serving else {}),
                kv_budget_bytes=int(serving["kv_budget_bytes_per_chip"]
                                    * self.world),
                max_queue=int(serving["max_queue"])),
            clock=time.monotonic)
        self.buckets = self.sched.buckets
        slots = self.sched.slots
        #: Pages of BOTH kinds: the full layers' pool and the window
        #: layers' (what `kv_pool_peak` / `kv_live_peak` divide by).
        self.usable_pages = slots.usable_pages + slots.window_usable_pages
        self.window_usable_pages = slots.window_usable_pages
        self.page_size = slots.page_size
        self.kv_budget_bytes = slots.kv_budget_bytes

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
            return {"ln1": w["ln1"], "ln2": w["ln2"],
                    "attn": {"wqkv": jnp.concatenate(
                        [w["q"], w["k"], w["v"]], axis=1), "wo": w["o"]},
                    "moe": {"router": w["router"]}}

        def experts(key, block):
            return rounded(published.expert_weights(key, dims, block))

        def ends(key):
            w = rounded(published.end_weights(key, dims))
            return {k: w[k] for k in ("embed", "ln_f", "lm_head")}

        lspec = specs["layers"][0]
        make = jax.jit(layer, out_shardings=named(dict(
            lspec, moe={"router": lspec["moe"]["router"]})))
        make_block = jax.jit(experts, static_argnums=1)
        stack = jax.jit(lambda blocks: {
            k: jnp.concatenate([b[k] for b in blocks])
            for k in ("gate", "up", "down")})
        make_ends = jax.jit(ends, out_shardings=named(
            {k: specs[k] for k in ("embed", "ln_f", "lm_head")}))
        key = published.base_key(seed)
        params = make_ends(key)
        params["layers"] = []
        for i in range(dims["num_hidden_layers"]):
            k = published.layer_key(key, i)
            lp = make(k)
            lp["moe"].update(stack([make_block(k, b) for b in
                                    published.expert_blocks(dims)]))
            params["layers"].append(lp)
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
        slots = self.sched.slots
        return slots.used_pages + slots.window_pages_live
