"""`ServedModel`: what a model family signs to be served.

The serving path (`serving.scheduler`, `serving.pages`,
`serving.engine_batched`) and `models.engine.Engine` know a model
through this class alone.  The seven families (`Qwen3`, `Glm4MoeLite`,
`SolarOpen2`, `SdarMoe`, `NemotronH`, and — through
`models.window_layers.WindowAndFullLayers`, which writes the programs
of a model with window and full layers once — `Cohere2Moe` and
`SmallThinker`) derive from it; the
tests' fakes (`serving.toy.ToyModel`, `analysis.serving_model`'s stub)
take its defaults and write the entry points themselves.

A family MUST write

- ``__init__(config, mesh, axis="tp", mode="fused", interpret=None,
  gemm=None)``: its own assertions, ``super().__init__(config, mesh,
  axis, mode, interpret)``, its layers;
- `param_specs()`: the `PartitionSpec` tree of its parameters —
  ``embed``, ``layers`` (a list, one tree a layer), ``ln_f`` and,
  unless the head is tied to the embedding, ``lm_head``;
- `init_layer(key, kind)`: one layer's seeded parameters (`init_params`
  makes the embedding, the final norm and the head around them, each
  kind of layer by one jitted program placed to `param_specs()`);
- the per-device bodies, run inside `shard_map` over ``axis``:
  `prefill_shard(params, ids (B, S), cache or None) -> (logits (B, V)
  float32 of each row's last position, cache)` and `decode_shard(
  params, tokens (B,), paged cache) -> (logits (B, V), cache)` with the
  offset moved on by one;
- the DECLARATION OF ITS CACHE, from which the cache, its sharding and
  every program's in and out specs are made (`models.kv_cache`):
  `cache_layout()` — the keywords `KVCache.create` / `.specs` share:
  how many pooled attention layers (``num_layers``), whether they hold
  latent rows (``latent``) or int8 (``quantized``), the recurrent
  layers' ``state_shapes``, the ``window_layers`` —; ``kv_row``, the
  (KV heads, width) of a pooled row; ``STATS``, the names of what a
  step leaves in the paged cache's ``stats``; and ``shards``: whether
  the class splits its KV heads (and its parameters) over ``axis`` —
  `Qwen3` does; a class that does not is refused a mesh wider than one
  device;
- ``layer_kinds`` where its layers are of more than one kind: one
  hashable a layer.  `_per_layer` and `init_params` then make one
  jitted body a kind, passed as the keyword ``kind``.

A family MAY write

- `prefill_shard_suffix(params, ids (1, C), start, row cache, pools,
  page_ids) -> row cache`: one chunk of one prompt over the rows the
  page pool already holds (``pools``: the paged cache's ``(ks, vs)``,
  and ``(wks, wvs)`` behind them where ``window`` > 0, with
  ``page_ids`` then (2, T)), no logits.  `make_prefill_suffix_fn` is
  there exactly where it is written, else None; with ``prefill_chunk``
  > 0 the scheduler carries a long prompt out in chunks of that many
  tokens;
- ``block_length`` > 1: the model generates by blocks, and
  `decode_shard(params, tokens (B, 2n), cache, active, folded) ->
  (logits (B, n, V), cache)` is a block pass's model half
  (`models.sdar_moe`, which chunks too: a chunk and a page hold whole
  blocks, and its `prefill_shard_suffix` attends the pool under the
  block-causal mask);
- ``window`` > 0: its ``window_layers`` see that many tokens back and
  the page manager keeps their pages by it;
- ``latent_bytes_per_token``: the bytes of a cached token that carry
  information (a latent cache's rows are padded beyond them);
- `make_decode_fn`: the step over the DENSE-SLOT layout
  (``kv_layout="slots"``, `Engine`).  `Qwen3` and `ToyModel` alone
  (ROADMAP D5).

It GETS the mesh-level entry points the serving path calls —
`make_prefill_fn`, `make_paged_decode_fn`, `make_prefill_suffix_fn`,
`create_cache`, `create_paged_cache` — and `init_params`, `_named`,
`_per_layer`.  The benchmark reads the compiled programs by name in the
device trace: `jax.jit` names a program after the function it is
given, so the prefill is ``jit_prefill_shard``, a chunk
``jit_prefill_shard_suffix`` (the same prefix), the step ``jit_body``
/ ``jit_block_pass`` (`serving.engine_batched`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_distributed_tpu.models.kv_cache import KVCache, PagedKVCache


class ServedModel:
    # What the scheduler and the page pool read, with the value that
    # says "this model does not offer it".
    #: > 1: positions a block of block generation.
    block_length = 0
    #: Tokens a chunk of a long prompt's prefill.
    prefill_chunk = 0
    #: Tokens a sliding-window layer sees back.
    window = 0
    #: What a step leaves in the paged cache's `stats`, in order.
    STATS = ()
    #: Bytes of a latent cache's token that carry information.
    latent_bytes_per_token = 0
    #: The class splits KV heads and parameters over ``axis``.
    shards = False
    #: One hashable a layer; None: every layer is of one kind.
    layer_kinds = None
    #: The per-device body of a chunk; None: there is no such program.
    prefill_shard_suffix = None

    def __init__(self, config, mesh: Mesh, axis: str = "tp",
                 mode: str = "fused", interpret: Optional[bool] = None):
        assert self.shards or mesh.shape[axis] == 1, (
            f"{type(self).__name__} runs on one device; "
            f"{axis}={mesh.shape[axis]} is not built")
        self.config = config
        self.mesh = mesh
        self.axis = axis
        self.world = mesh.shape[axis]
        self.mode = mode
        self.interpret = interpret
        self.dtype = jnp.dtype(config.dtype)

    # ------------------------------------------------------------------
    # the declaration of the cache
    # ------------------------------------------------------------------

    def cache_layout(self) -> dict:
        """Every layer a pooled attention layer, K and V a KV head."""
        return dict(num_layers=self.config.num_layers,
                    quantized=self.config.quantize_kv_cache)

    @property
    def kv_row(self):
        """(KV heads, width) of a pooled row."""
        return self.config.num_kv_heads, self.config.head_dim

    def _cache_specs(self, page_size: Optional[int] = None):
        """The spec tree of `create_cache`'s cache or, given a page
        size, of `create_paged_cache`'s."""
        kv_axis = self.axis if self.shards else None
        if page_size is None:
            return KVCache.specs(**self.cache_layout(), kv_axis=kv_axis)
        return PagedKVCache.specs(
            page_size=page_size, num_stats=len(self.STATS),
            **self.cache_layout(), kv_axis=kv_axis)

    def _named(self, specs):
        """PartitionSpec tree -> NamedSharding tree on this mesh."""
        return jax.tree.map(
            lambda sp: NamedSharding(self.mesh, sp), specs,
            is_leaf=lambda x: isinstance(x, P))

    def create_cache(self, batch: int, max_seq: Optional[int] = None):
        """The cache a bucketed prefill (or a chunk) fills, a row a
        sequence — and the dense-slot decode layout of a family that
        wrote `make_decode_fn`.  Zeros are made under jit with the
        cache's own shardings: each device allocates its head shard and
        nothing else."""
        heads, width = self.kv_row
        make = functools.partial(
            KVCache.create, batch=batch, num_kv_heads=heads,
            max_seq=max_seq or self.config.max_seq_len, head_dim=width,
            dtype=self.dtype, **self.cache_layout())
        return jax.jit(make, out_shardings=self._named(
            self._cache_specs()))()

    def create_paged_cache(self, batch: int, num_pages: int,
                           page_size: int, max_pages_per_seq: int,
                           window_pages: int = 2):
        """Pool pages replicated in batch, KV heads split like the
        dense cache's, the page axis shared.  ``window_pages``: the
        page count of the window layers' pools where there are any (the
        null page among them; `serving.pages.PagedKV` sizes it by
        slots)."""
        heads, width = self.kv_row
        make = functools.partial(
            PagedKVCache.create, num_pages=num_pages, batch=batch,
            num_kv_heads=heads, page_size=page_size, head_dim=width,
            max_pages_per_seq=max_pages_per_seq, dtype=self.dtype,
            num_stats=len(self.STATS), window_pages=window_pages,
            **self.cache_layout())
        return jax.jit(make, out_shardings=self._named(
            self._cache_specs(page_size)))()

    # ------------------------------------------------------------------
    # parameters
    # ------------------------------------------------------------------

    def init_params(self, key):
        """Seeded parameters, made on the device a layer at a time,
        each placed to `param_specs()`."""
        cfg = self.config
        h = cfg.hidden_size
        specs = self.param_specs()

        def ends(k_embed, k_head=None):
            normal = jax.random.normal
            out = {"embed": (normal(k_embed, (cfg.vocab_size, h))
                             * h ** -0.5).astype(self.dtype),
                   "ln_f": jnp.ones((h,), self.dtype)}
            if k_head is not None:
                out["lm_head"] = (normal(k_head, (h, cfg.vocab_size))
                                  * h ** -0.5).astype(self.dtype)
            return out

        n = cfg.num_layers
        keys = jax.random.split(key, n + (2 if "lm_head" in specs else 1))
        params = jax.jit(ends, out_shardings=self._named(
            {k: v for k, v in specs.items() if k != "layers"}))(
                *keys[n:][::-1])
        kinds = self.layer_kinds or (None,) * n
        make = {kind: jax.jit(
            functools.partial(self.init_layer, kind=kind),
            out_shardings=self._named(specs["layers"][kinds.index(kind)]))
            for kind in set(kinds)}
        params["layers"] = [make[kind](keys[i])
                            for i, kind in enumerate(kinds)]
        return params

    # Each layer body is wrapped in ONE `jax.jit` per traced program:
    # the Python loop over layers then traces and lowers the body —
    # ring kernels, pipelines and all — once for each kind of layer
    # instead of once per layer (measured ~3 s of lowering per layer
    # per prefill program at tp=4, paid again by every fresh process
    # even when the persistent compile cache hits).  XLA inlines the
    # calls, so the compiled program is unchanged.

    def _per_layer(self, fn, **static):
        """The jitted body — or, where the family names `layer_kinds`,
        ``{kind: the body jitted for that kind}``."""
        if self.layer_kinds is None:
            return jax.jit(functools.partial(fn, **static))
        return {kind: jax.jit(functools.partial(fn, kind=kind, **static))
                for kind in set(self.layer_kinds)}

    # ------------------------------------------------------------------
    # mesh-level entry points
    # ------------------------------------------------------------------

    def _over_mesh(self, body, in_specs, out_specs):
        """``body(params, ...)`` a device, over this model's mesh."""
        return jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(self.param_specs(), *in_specs),
            out_specs=out_specs, check_vma=False)

    def make_prefill_fn(self):
        """``(params, ids (B, S), cache) -> (logits (B, V), cache)``."""
        cspecs = self._cache_specs()
        return self._over_mesh(
            self.prefill_shard, (P(None, None), cspecs),
            (P(None, self.axis), cspecs))

    @property
    def make_prefill_suffix_fn(self):
        """None, or the maker of ``(params, ids (1, C), start,
        row_cache, pools, page_ids) -> row_cache``:
        `prefill_shard_suffix`.  The program's name starts like the
        whole prefill's, and its kernels are the prefill's, so a device
        trace reads both alike."""
        if self.prefill_shard_suffix is None:
            return None

        def make():
            row = self._cache_specs()
            pools, page_ids = (row.ks, row.vs), P(None)
            if self.window:
                pools, page_ids = (*pools, row.wks, row.wvs), P(None, None)
            return self._over_mesh(
                self.prefill_shard_suffix,
                (P(None, None), P(), row, pools, page_ids), row)
        return make

    def make_paged_decode_fn(self, page_size: int = 16):
        """``(params, tokens (B,), cache) -> (logits (B, V), cache)``
        or, of a model that generates by blocks, the block pass's model
        half: ``(params, tokens (B, 2n), cache, active (B,), folded
        (B,)) -> (logits (B, n, V), cache)``."""
        cspecs = self._cache_specs(page_size)
        if self.block_length > 1:
            assert page_size % self.block_length == 0, (
                "a block must not straddle a page", page_size,
                self.block_length)
            return self._over_mesh(
                self.decode_shard,
                (P(None, None), cspecs, P(None), P(None)),
                (P(None, None, self.axis), cspecs))
        return self._over_mesh(
            self.decode_shard, (P(None), cspecs),
            (P(None, self.axis), cspecs))
