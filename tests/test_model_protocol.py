"""The model protocol (`models.base.ServedModel`), held for every
family and for the tests' own fake: each is a `ServedModel` and
declares what the scheduler and the page pool read; the spec tree
`models.kv_cache` makes lines up with the cache `create` returns for
every combination a family uses; and each family's prefill program has
the name the benchmark reads in the device trace.

Nothing is compiled: shapes (`jax.eval_shape`) and lowered text only.
"""

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from triton_distributed_tpu.models import AutoLLM, ModelConfig
from triton_distributed_tpu.models import (
    cohere2_moe, glm4_moe_lite, nemotron_h, sdar_moe, smallthinker,
    solar_open2)
from triton_distributed_tpu.models.base import ServedModel
from triton_distributed_tpu.models.kv_cache import KVCache, PagedKVCache
from triton_distributed_tpu.serving import (
    ContinuousBatchingScheduler, SchedulerConfig)
from triton_distributed_tpu.serving.toy import ToyConfig, ToyModel


def _tiny_cohere2_moe():
    """One period of the layer pattern at test size (the keys of
    `tests/test_cohere2_moe.py`'s configuration)."""
    return ModelConfig(
        architecture="cohere2_moe", vocab_size=256, hidden_size=128,
        intermediate_size=64, num_layers=4, num_heads=8, num_kv_heads=2,
        head_dim=16, rms_norm_eps=1e-5, rope_theta=50000, qk_norm=False,
        rope_pairs=True, tie_word_embeddings=True, max_seq_len=128,
        num_experts=32, experts_held=(0, 8), num_experts_per_tok=4,
        moe_intermediate_size=64, n_shared_experts=4,
        moe_shared_combine="average", moe_selection_bias=False,
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        sliding_window=16)


def _tiny_smallthinker():
    """One period of the layer pattern (full first) at test size, from
    the published keys of `tests/test_smallthinker.py`."""
    from tests.test_smallthinker import TINY
    return ModelConfig.from_smallthinker(TINY)


class Family(NamedTuple):
    #: Its test-size config's maker (None: the toy).
    config: Optional[Callable]
    #: The module whose `PREFILL_CHUNK` it reads at construction.
    module: object
    #: The chunk as published (0: it does not chunk).
    chunk: int
    #: The name `jax.jit` gives its prefill program.
    program: str


FAMILIES = {
    "qwen3": Family(ModelConfig.tiny, None, 0, "jit_fn"),
    "qwen3_int8": Family(
        functools.partial(ModelConfig.tiny, quantize_kv_cache=True), None,
        0, "jit_fn"),
    "glm4_moe_lite": Family(ModelConfig.tiny_glm4_moe_lite, glm4_moe_lite,
                            2048, "jit_prefill_shard"),
    "solar_open2": Family(ModelConfig.tiny_solar_open2, solar_open2, 256,
                          "jit_prefill_shard"),
    "sdar_moe": Family(ModelConfig.tiny_sdar_moe, sdar_moe, 512,
                       "jit_prefill_shard"),
    "nemotron_h": Family(ModelConfig.tiny_nemotron_h, nemotron_h, 512,
                         "jit_prefill_shard"),
    "cohere2_moe": Family(_tiny_cohere2_moe, cohere2_moe, 1024,
                          "jit_prefill_shard"),
    "smallthinker": Family(_tiny_smallthinker, smallthinker, 1024,
                           "jit_prefill_shard"),
    "toy": Family(None, None, 0, "jit_prefill"),
}
#: The chunk the lowered chunk programs are cut to.
CHUNK = 16


def _model(family, devices, chunk=None):
    """The family's model over one device; ``chunk``: its module's
    `PREFILL_CHUNK` while it is built."""
    f = FAMILIES[family]
    if f.config is None:
        return ToyModel(ToyConfig(prefill_chunk=chunk or 0))
    mesh = Mesh(np.array(devices[:1]), ("tp",))
    with pytest.MonkeyPatch.context() as mp:
        if chunk is not None and f.module is not None:
            mp.setattr(f.module, "PREFILL_CHUNK", chunk)
        return AutoLLM(f.config(), mesh)


@pytest.mark.parametrize("family", list(FAMILIES))
def test_declares_what_the_scheduler_reads(family, devices):
    model = _model(family, devices)
    assert isinstance(model, ServedModel)
    assert (model.block_length > 1) == (family == "sdar_moe")
    assert (model.window > 0) == (family in ("cohere2_moe",
                                             "smallthinker"))
    assert model.prefill_chunk == FAMILIES[family].chunk
    # a chunk length is named exactly where there is a chunk program
    # (the toy's serves a prefix hit's suffix too, chunk or none)
    offers = model.make_prefill_suffix_fn is not None
    assert offers == bool(model.prefill_chunk) or family == "toy"
    assert (model.latent_bytes_per_token > 0) == (
        family == "glm4_moe_lite")
    pool = jax.eval_shape(lambda: model.create_paged_cache(2, 5, 16, 4))
    assert isinstance(model.STATS, tuple)
    assert len(model.STATS) == (0 if pool.stats is None
                                else pool.stats.shape[0])
    assert (pool.wks is not None) == (model.window > 0)
    assert callable(model.make_prefill_fn)
    assert callable(model.make_paged_decode_fn)


def test_what_is_not_a_served_model_is_refused_by_name():
    """A class that merely LOOKS like a model — here with every entry
    point and a mistyped declaration — is no `ServedModel`: nothing is
    found by `getattr` any more, so nothing can be silently absent."""
    class LooksLikeOne:
        config = ToyConfig()
        blok_length = 4
        make_prefill_fn = create_cache = create_paged_cache = (
            make_paged_decode_fn) = staticmethod(lambda *a, **k: None)

    for layout in ("paged", "slots"):
        with pytest.raises(ValueError, match="ServedModel"):
            ContinuousBatchingScheduler(
                LooksLikeOne(), {}, SchedulerConfig(kv_layout=layout))


def _same_tree(specs, cache):
    """The spec tree lines up with the cache, a spec as long as its
    array has dimensions."""
    is_spec = lambda x: isinstance(x, P)              # noqa: E731
    assert (jax.tree.structure(specs, is_leaf=is_spec)
            == jax.tree.structure(cache))
    for spec, x in zip(jax.tree.leaves(specs, is_leaf=is_spec),
                       jax.tree.leaves(cache)):
        assert len(spec) == x.ndim, (spec, x.shape)


STATE = [((4, 8, 8), (24,))] * 2
#: Every combination of `create`'s layout arguments a family uses (and
#: a hybrid cut that kept no recurrent layer).
LAYOUTS = {
    "dense": dict(),
    "int8": dict(quantized=True),
    "latent": dict(latent=True),
    "state": dict(state_shapes=STATE),
    "state_cut_away": dict(state_shapes=[]),
    "window": dict(window_layers=3),
}


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("num_stats", [0, 4])
def test_the_spec_tree_is_the_caches_tree(layout, num_stats):
    kw = LAYOUTS[layout]
    heads = 1 if kw.get("latent") else 2
    row = jax.eval_shape(functools.partial(
        KVCache.create, 2, 1, heads, 32, 16, **kw))
    _same_tree(KVCache.specs(2, kv_axis="tp", **kw), row)
    pool = jax.eval_shape(functools.partial(
        PagedKVCache.create, 2, 5, 3, heads, 16, 16, 4,
        num_stats=num_stats, window_pages=4, **kw))
    specs = PagedKVCache.specs(2, 16, num_stats=num_stats, kv_axis="tp",
                               **kw)
    _same_tree(specs, pool)
    # KV heads, and nothing else, are split
    for field in ("ks", "vs", "kss", "vss", "wks", "wvs"):
        for spec in getattr(specs, field) or ():
            assert spec[1] == "tp" and set(spec) == {None, "tp"}
    for field in ("states", "convs"):
        for spec in getattr(specs, field) or ():
            assert set(spec) == {None}
    one = PagedKVCache.specs(2, 16, num_stats=num_stats, **kw)
    assert all(set(spec) <= {None} for spec in jax.tree.leaves(
        one, is_leaf=lambda x: isinstance(x, P)))


@pytest.mark.parametrize("family", [f for f in FAMILIES if f != "toy"])
def test_a_familys_specs_are_its_caches_trees(family, devices):
    model = _model(family, devices)
    _same_tree(model._cache_specs(),
               jax.eval_shape(lambda: model.create_cache(1, 32)))
    _same_tree(model._cache_specs(16), jax.eval_shape(
        lambda: model.create_paged_cache(2, 5, 16, 4)))
    _same_tree(model.param_specs(),
               jax.eval_shape(model.init_params, jax.random.key(0)))


def test_the_sharded_family_splits_kv_heads_over_its_axis(devices):
    mesh = Mesh(np.array(devices[:4]), ("tp",))
    model = AutoLLM(ModelConfig.tiny(quantize_kv_cache=True), mesh)
    assert model.shards and model.world == 4
    specs = model._cache_specs(16)
    assert specs.ks[0] == P(None, "tp", None, None)
    assert specs.kss[0] == P(None, "tp", None)
    for family in ("glm4_moe_lite", "sdar_moe"):
        with pytest.raises(AssertionError, match="one device"):
            AutoLLM(FAMILIES[family].config(), mesh)


@pytest.mark.parametrize(
    "family", [f for f in FAMILIES if f != "qwen3_int8"])
def test_program_names_the_benchmark_reads(family, devices):
    """`cellbench/adapters/*.py` `TRACE_MODULES` find a family's
    prefill — and its chunk program, under the same prefix — in the
    device trace by the name `jax.jit` gives it."""
    model = _model(family, devices, chunk=CHUNK)
    name = FAMILIES[family].program
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    ids = jnp.zeros((1, 2 * CHUNK), jnp.int32)
    row = jax.eval_shape(lambda: model.create_cache(1, 2 * CHUNK))
    first = lambda lowered: lowered.as_text().splitlines()[0]  # noqa: E731
    assert first(jax.jit(model.make_prefill_fn()).lower(
        params, ids, row)).startswith(f"module @{name} ")
    if model.make_prefill_suffix_fn is None:
        assert family == "qwen3"
        return
    pool = jax.eval_shape(lambda: model.create_paged_cache(2, 9, 16, 4))
    pools, pages = (pool.ks, pool.vs), jnp.zeros((4,), jnp.int32)
    if model.window:
        pools, pages = (*pools, pool.wks, pool.wvs), jnp.stack([pages] * 2)
    row = jax.eval_shape(lambda: model.create_cache(1, CHUNK))
    assert first(jax.jit(model.make_prefill_suffix_fn()).lower(
        params, ids[:, :CHUNK], jnp.int32(CHUNK), row, pools,
        pages)).startswith(f"module @{name}_suffix ")
