"""No pool-sized copy in a compiled decode step or admission.

A decode step changes ONE row a slot of each layer's KV pools.  Until
PR 29 the compiled step (`jit_body`) copied every pool twice: XLA's
TPU scatter wants its window dimensions minor, and the write
`pool.at[phys, :, within]` put a window (the heads) between the two
scattered dimensions, so the pool was copied into another layout,
scattered into, and copied back — 6.8 of 15 ms a step at 12 layers
(PERF.md section 6, PR 29).  Donation was never the fault.  This file
compiles the programs the scheduler runs — the masked decode step and
the paged insert, pools donated — for a DESCRIBED v5e:2x2 (nothing
executes) at published widths, two layers, and asserts that no `copy`
in the compiled module has a pool's shape.

Pinned for both families: `Qwen3` (one device, and the tp=4 mesh whose
shard holds 2 of the 8 KV heads) and `Glm4MoeLite`'s latent pool, whose
write was always in the leading-dimensions form.
"""

import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.models.glm4_moe_lite import (
    MOE_STATS, Glm4MoeLite)
from triton_distributed_tpu.models.kv_cache import KVCache, PagedKVCache
from triton_distributed_tpu.models.qwen import Qwen3
from triton_distributed_tpu.serving.engine_batched import (
    make_masked_step_fn, make_paged_insert_fn, make_paged_rows_fn)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS, PAGE, SLOTS = 2, 16, 8
#: The Qwen cells' pool: 8 slots x 2768 tokens / 16 + the trash page.
PAGES = 1385
BUCKET = 2048


@pytest.fixture(scope="module")
def topo_devices():
    from jax.experimental import topologies
    try:
        return tuple(topologies.get_topology_desc("v5e:2x2", "tpu").devices)
    except Exception as e:     # noqa: BLE001 — whatever PJRT raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _config(name):
    with open(os.path.join(ROOT, "cellbench", "configs", name)) as f:
        return json.load(f)


def _qwen(devices):
    c = _config("qwen3-8b-1c.json")
    cfg = ModelConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"], num_layers=LAYERS,
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        rms_norm_eps=c["rms_norm_eps"], rope_theta=c["rope_theta"],
        qk_norm=True, tie_word_embeddings=c["tie_word_embeddings"],
        max_seq_len=4096, dtype=c["torch_dtype"])
    model = Qwen3(cfg, Mesh(np.array(devices), ("tp",)), mode="fused",
                  interpret=False)
    return model, cfg.num_kv_heads, cfg.head_dim, False


def _glm(devices):
    c = _config("glm-4.7-flash-1c.json")
    cfg = ModelConfig(
        architecture=c["model_type"], vocab_size=c["vocab_size"],
        hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"], num_layers=LAYERS,
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=0,
        rms_norm_eps=c["rms_norm_eps"], rope_theta=c["rope_theta"],
        qk_norm=False, tie_word_embeddings=c["tie_word_embeddings"],
        max_seq_len=4096, dtype=c["torch_dtype"],
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], num_experts=c["n_routed_experts"],
        num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"],
        first_k_dense_replace=c["first_k_dense_replace"],
        n_shared_experts=c["n_shared_experts"],
        routed_scaling_factor=c["routed_scaling_factor"],
        norm_topk_prob=c["norm_topk_prob"])
    model = Glm4MoeLite(cfg, Mesh(np.array(devices), ("tp",)),
                        mode="fused", interpret=False)
    return model, 1, model.attn.row_width, True


def _shaped(model, make, specs):
    """The shapes ``make`` would build, each with its sharding on the
    model's (described) mesh: nothing can be placed on such devices."""
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(make), model._named(specs))


def _programs(model, heads, width, latent):
    """Compiled text of the step and the insert as the scheduler runs
    them, and the per-device shape of a pool of ``heads`` x ``width``
    rows."""
    world = model.mesh.shape["tp"]
    rep = NamedSharding(model.mesh, P())
    arg = lambda shape, dt: jax.ShapeDtypeStruct(     # noqa: E731
        shape, dt, sharding=rep)
    pool = _shaped(model, functools.partial(
        PagedKVCache.create, LAYERS, PAGES, SLOTS, heads, PAGE, width,
        4096 // PAGE, model.dtype, latent=latent,
        num_stats=len(MOE_STATS) if latent else 0),
        model._cache_specs(PAGE))
    row = _shaped(model, functools.partial(
        KVCache.create, LAYERS, 1, heads, BUCKET, width, model.dtype,
        latent=latent),
        model._cache_specs())
    params = _shaped(
        model, lambda: model.init_params(jax.random.key(0)),
        model.param_specs())
    keys = arg((SLOTS, 2), jnp.uint32)
    step = make_masked_step_fn(model.make_paged_decode_fn(PAGE)).lower(
        params, arg((SLOTS,), jnp.int32), pool, keys,
        arg((SLOTS,), jnp.bool_)).compile().as_text()
    insert = make_paged_insert_fn().lower(
        pool, keys, row, arg((2,), jnp.uint32), arg((), jnp.int32),
        arg((BUCKET // PAGE,), jnp.int32),
        arg((), jnp.int32)).compile().as_text()
    return step, insert, (PAGES, heads // world, PAGE, width)


def pool_copies(hlo_text: str, shape) -> dict:
    """Instructions of a compiled module that copy an array of
    ``shape``, by name.  ``layout``: a `copy` (alone or as the root a
    fusion is named for) — the transposing copies around the old
    scatter, a `copy` row of the device trace.  ``staged``:
    `copy-done` — XLA's memory-space assignment moving a whole array
    into or out of VMEM, asynchronous and at the speed of a read."""
    dims = ",".join(str(d) for d in shape)
    pat = re.compile(
        r"^\s*(?:ROOT\s+)?%?(\S+)\s*=\s*\w+\[" + re.escape(dims)
        + r"\]\S*\s+([\w-]+)\(", re.M)
    found = {"layout": [], "staged": []}
    for name, opcode in pat.findall(hlo_text):
        if opcode == "copy" or (opcode == "fusion" and "copy" in name):
            found["layout"].append(name)
        elif opcode == "copy-done":
            found["staged"].append(name)
    return found


def test_the_reader_sees_a_pool_copy():
    text = """
ENTRY %main {
  %copy.7 = bf16[1385,8,16,128]{3,1,2,0:T(8,128)(2,1)} copy(%p), metadata={}
  %fusion.1 = bf16[1385,8,16,128]{3,2,1,0} fusion(%copy.7), kind=kLoop
  ROOT %copy_fusion.2 = bf16[1385,8,16,128]{3,2,1,0} fusion(%fusion.1)
  %copy-done.1 = bf16[1385,8,16,128]{3,2,1,0:S(1)} copy-done(%copy-start.1)
  %copy.9 = bf16[8,128]{1,0} copy(%q)
}"""
    assert pool_copies(text, (1385, 8, 16, 128)) == {
        "layout": ["copy.7", "copy_fusion.2"], "staged": ["copy-done.1"]}


#: One pool may be staged through VMEM and back (two `copy-done`): at
#: tp=4 the compiler does that to ONE of the 72 11 MB pools, 28 us of
#: HBM traffic a step.  More than that is worth reading.
STAGED_MAX = 2


@pytest.mark.parametrize("family,world", [
    ("qwen3", 1), ("qwen3", 4), ("glm4_moe_lite", 1)])
def test_no_pool_sized_copy_in_step_or_insert(topo_devices, family,
                                              world):
    build = _qwen if family == "qwen3" else _glm
    step, insert, shard = _programs(*build(topo_devices[:world]))
    assert "tpu_custom_call" in step, "the attention kernel is missing"
    dims = ",".join(str(d) for d in shard)
    assert f"[{dims}]" in step and f"[{dims}]" in insert, (
        f"no array of the pool's per-device shape {shard} in the "
        f"compiled programs: the reader would pass on anything")
    found = {"step": pool_copies(step, shard),
             "insert": pool_copies(insert, shard)}
    print(f"{family} tp={world} pool shard {shard}: {found}")
    for program, copies in found.items():
        assert not copies["layout"], (program, copies)
        assert len(copies["staged"]) <= STAGED_MAX, (program, copies)


def test_no_pool_sized_copy_in_a_chunk_or_in_its_rows(topo_devices):
    """A long prompt's prefill in chunks (`Glm4MoeLite.
    make_prefill_suffix_fn`): the chunk program READS the latent pools
    through the request's page ids — gathered rows, never the pool —
    and the scatter of its rows (`make_paged_rows_fn`) writes the
    donated pools where they lie.  At the published widths and the
    model's own chunk length, for the described v5e: Mosaic takes the
    attention at a traced offset, and neither program copies a pool."""
    model, heads, width, _ = _glm(topo_devices[:1])
    chunk = model.prefill_chunk
    rep = NamedSharding(model.mesh, P())
    arg = lambda shape, dt: jax.ShapeDtypeStruct(     # noqa: E731
        shape, dt, sharding=rep)
    pool = _shaped(model, functools.partial(
        PagedKVCache.create, LAYERS, PAGES, SLOTS, heads, PAGE, width,
        4096 // PAGE, model.dtype, latent=True,
        num_stats=len(MOE_STATS)), model._cache_specs(PAGE))
    row = _shaped(model, functools.partial(
        KVCache.create, LAYERS, 1, heads, chunk, width, model.dtype,
        latent=True), model._cache_specs())
    params = _shaped(
        model, lambda: model.init_params(jax.random.key(0)),
        model.param_specs())
    programs = {
        "chunk": jax.jit(model.make_prefill_suffix_fn()).lower(
            params, arg((1, chunk), jnp.int32), arg((), jnp.int32), row,
            (pool.ks, None), arg((4096 // PAGE,), jnp.int32)),
        "rows": make_paged_rows_fn().lower(
            (pool.ks, None, None, None), pool.offset, row,
            arg((chunk // PAGE,), jnp.int32))}
    shard = (PAGES, heads, PAGE, width)
    dims = ",".join(str(d) for d in shard)
    for name, lowered in programs.items():
        text = lowered.compile().as_text()
        assert f"[{dims}]" in text, name
        found = pool_copies(text, shard)
        print(f"glm4_moe_lite {name} of {chunk}, pool {shard}: {found}")
        assert not found["layout"], (name, found)
        assert len(found["staged"]) <= STAGED_MAX, (name, found)
        if name == "chunk":
            # (the LAST layer's attention and feed-forward are not in
            # the program: it returns rows, no logits, so nothing
            # reads them — at this depth that is the expert layer)
            assert "flash_attention_fwd" in text


def _solar(devices, layers=LAYERS):
    """The delta-rule hybrid at the cell's own widths: a softmax layer
    and ``layers - 1`` delta-rule layers behind it."""
    from triton_distributed_tpu.models.solar_open2 import SolarOpen2

    c = _config("solar-open2-250b-1c.json")
    lin = c["linear_attn_config"]
    cfg = ModelConfig(
        architecture=c["model_type"], vocab_size=c["vocab_size"],
        hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"], num_layers=layers,
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        rms_norm_eps=c["rms_norm_eps"], qk_norm=False,
        tie_word_embeddings=False, max_seq_len=4096,
        dtype=c["torch_dtype"],
        num_experts=c["share"]["experts_of_layer"],
        experts_held=tuple(c["share"]["experts_held"]),
        num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"],
        gqa_layers=(0,), use_rope=c["use_rope"],
        use_gqa_gate=c["use_gqa_gate"], kda_num_heads=lin["num_heads"],
        kda_head_dim=lin["head_dim"],
        kda_conv_size=lin["short_conv_kernel_size"],
        kda_rank=lin["head_dim"])
    return SolarOpen2(cfg, Mesh(np.array(devices), ("tp",)),
                      mode="fused", interpret=False)


def test_no_state_sized_copy_in_step_insert_or_reset(topo_devices):
    """The hybrid family (`models/solar_open2.py`): a decode step
    rewrites every live slot's recurrent state — 4 MB a slot a layer —
    through a kernel that aliases the pool, the insert and the reset
    write one slot's rows on the leading dimension.  None of the three
    programs may copy the state pool or the softmax layer's page pools.
    (The convolution's kept inputs, 0.15 MB a slot a layer, are
    rewritten whole by every step anyway — the shift — and are not
    held to it.)"""
    from triton_distributed_tpu.models.kv_cache import zero_state_rows

    model = _solar(topo_devices[:1])
    slots = 16
    rep = NamedSharding(model.mesh, P())
    arg = lambda shape, dt: jax.ShapeDtypeStruct(     # noqa: E731
        shape, dt, sharding=rep)
    pool = _shaped(model, functools.partial(
        PagedKVCache.create, 1, PAGES, slots, 8, PAGE, 128,
        4096 // PAGE, model.dtype, num_stats=len(model.STATS),
        state_shapes=model._state_shapes),
        model._cache_specs(PAGE))
    row = _shaped(model, functools.partial(
        KVCache.create, 1, 1, 8, BUCKET, 128, model.dtype,
        state_shapes=model._state_shapes), model._cache_specs())
    params = _shaped(
        model, lambda: model.init_params(jax.random.key(0)),
        model.param_specs())
    keys = arg((slots, 2), jnp.uint32)
    programs = {
        "step": make_masked_step_fn(model.make_paged_decode_fn(PAGE))
        .lower(params, arg((slots,), jnp.int32), pool, keys,
               arg((slots,), jnp.bool_)),
        "insert": make_paged_insert_fn().lower(
            pool, keys, row, arg((2,), jnp.uint32), arg((), jnp.int32),
            arg((BUCKET // PAGE,), jnp.int32), arg((), jnp.int32)),
        "reset": jax.jit(zero_state_rows, donate_argnums=(0, 1)).lower(
            pool.states, pool.convs, arg((), jnp.int32))}
    shapes = {"state": (slots, 64, 128, 128),
              "pages": (PAGES, 8, PAGE, 128)}
    for name, lowered in programs.items():
        text = lowered.compile().as_text()
        if name == "step":
            assert "kda_decode_step" in text
        for what, shape in shapes.items():
            if name == "reset" and what == "pages":
                continue
            dims = ",".join(str(d) for d in shape)
            assert f"[{dims}]" in text, (name, what)
            found = pool_copies(text, shape)
            print(f"solar_open2 {name} {what} {shape}: {found}")
            assert not found["layout"], (name, what, found)
            assert len(found["staged"]) <= STAGED_MAX, (name, what, found)


def test_the_delta_rule_chunk_reads_the_pools_and_copies_none(
        topo_devices):
    """A long prompt of the delta-rule hybrid in chunks
    (`SolarOpen2.make_prefill_suffix_fn`) at the cell's widths, slots
    and pool, and the model's own chunk length, for the described v5e:
    Mosaic takes the chunked delta rule with a carried state as an
    operand and the gated attention at a traced offset; the chunk
    program reads the softmax layer's page pools through the request's
    page ids — gathered rows, never a pool — and the scatter of a
    middle chunk's rows writes the donated pools where they lie.  The
    state pool (`f32[192,64,128,128]` a layer) is no argument of
    either and nothing of its shape is made: a chunk's state rides in
    the row cache, and the last chunk's insert (the whole prefill's,
    pinned above) writes it."""
    model = _solar(topo_devices[:1], layers=3)
    c = _config("solar-open2-250b-1c.json")["serving"]
    chunk, slots = model.prefill_chunk, c["num_slots"]
    per_state = 3 * (64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2)
    #: the cell's own pool: what its budget leaves of pages beside 192
    #: slots' states, and the trash page
    pages = (c["kv_budget_bytes_per_chip"] - slots * per_state) // (
        4096 * PAGE) + 1
    table = c["max_seq"] // PAGE
    assert chunk % PAGE == 0 and chunk < BUCKET and slots == 192
    rep = NamedSharding(model.mesh, P())
    arg = lambda shape, dt: jax.ShapeDtypeStruct(     # noqa: E731
        shape, dt, sharding=rep)
    pool = _shaped(model, functools.partial(
        PagedKVCache.create, 1, pages, slots, 8, PAGE, 128, table,
        model.dtype, num_stats=len(model.STATS),
        state_shapes=model._state_shapes),
        model._cache_specs(PAGE))
    assert pool.states[0].shape == (192, 64, 128, 128)
    row = _shaped(model, functools.partial(
        KVCache.create, 1, 1, 8, chunk, 128, model.dtype,
        state_shapes=model._state_shapes), model._cache_specs())
    params = _shaped(
        model, lambda: model.init_params(jax.random.key(0)),
        model.param_specs())
    programs = {
        "chunk": jax.jit(model.make_prefill_suffix_fn()).lower(
            params, arg((1, chunk), jnp.int32), arg((), jnp.int32), row,
            (pool.ks, pool.vs), arg((table,), jnp.int32)),
        "rows": make_paged_rows_fn().lower(
            (pool.ks, pool.vs, None, None), pool.offset, row,
            arg((chunk // PAGE,), jnp.int32))}
    shard = (pages, 8, PAGE, 128)
    dims = ",".join(str(d) for d in shard)
    for name, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        assert f"[{dims}]" in text, name
        assert "[192,64,128,128]" not in text, name
        found = pool_copies(text, shard)
        print(f"solar_open2 {name} of {chunk}, pool {shard}: {found}; "
              f"temporaries "
              f"{compiled.memory_analysis().temp_size_in_bytes >> 20} MB")
        assert not found["layout"], (name, found)
        assert len(found["staged"]) <= STAGED_MAX, (name, found)
        if name == "chunk":
            assert text.startswith("HloModule jit_prefill_shard"), text[:80]
            for kernel in ("kda_prefill_chunk", "flash_attention_fwd",
                           "moe_prefill_gate_up", "moe_prefill_down"):
                assert kernel in text, kernel
            assert "kda_decode_step" not in text


def _nemotron(devices, pattern="MEM*E"):
    """The state-space hybrid at the cell's own widths, five layers
    with every kind among them."""
    from triton_distributed_tpu.models.nemotron_h import NemotronH

    c = _config("nemotron-3-super-120b-1c.json")
    cfg = ModelConfig(
        architecture=c["model_type"], vocab_size=c["vocab_size"],
        hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        num_layers=len(pattern), num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        rms_norm_eps=c["layer_norm_epsilon"], qk_norm=False,
        use_rope=False, tie_word_embeddings=False, max_seq_len=4096,
        dtype=c["torch_dtype"],
        num_experts=c["share"]["experts_of_layer"],
        experts_held=tuple(c["share"]["experts_held"]),
        num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"],
        routed_scaling_factor=c["routed_scaling_factor"],
        layer_pattern=pattern, mamba_num_heads=c["mamba_num_heads"],
        mamba_head_dim=c["mamba_head_dim"], mamba_n_groups=c["n_groups"],
        ssm_state_size=c["ssm_state_size"],
        mamba_conv_size=c["conv_kernel"], moe_act=c["mlp_hidden_act"],
        moe_latent_size=c["moe_latent_size"],
        moe_shared_intermediate_size=c[
            "moe_shared_expert_intermediate_size"])
    return NemotronH(cfg, Mesh(np.array(devices), ("tp",)),
                     mode="fused", interpret=False)


def test_no_state_sized_copy_in_the_state_space_step(topo_devices):
    """The state-space hybrid (`models/nemotron_h.py`) at the cell's own
    widths, five layers with every kind among them (`MEM*E`): a decode
    step rewrites every live slot's Mamba-2 state —
    4 MB a slot a layer, two heads side by side — through a kernel that
    aliases the pool; the insert and the reset write one slot's rows.
    None of the three programs may copy the state pool or the attention
    layer's page pools, and the step names its kernels."""
    from triton_distributed_tpu.models.kv_cache import zero_state_rows

    model = _nemotron(topo_devices[:1])
    slots = 16
    rep = NamedSharding(model.mesh, P())
    arg = lambda shape, dt: jax.ShapeDtypeStruct(     # noqa: E731
        shape, dt, sharding=rep)
    pool = _shaped(model, functools.partial(
        PagedKVCache.create, 1, PAGES, slots, 2, PAGE, 128,
        4096 // PAGE, model.dtype, num_stats=len(model.STATS),
        state_shapes=model._state_shapes),
        model._cache_specs(PAGE))
    row = _shaped(model, functools.partial(
        KVCache.create, 1, 1, 2, BUCKET, 128, model.dtype,
        state_shapes=model._state_shapes), model._cache_specs())
    params = _shaped(
        model, lambda: model.init_params(jax.random.key(0)),
        model.param_specs())
    keys = arg((slots, 2), jnp.uint32)
    programs = {
        "step": make_masked_step_fn(model.make_paged_decode_fn(PAGE))
        .lower(params, arg((slots,), jnp.int32), pool, keys,
               arg((slots,), jnp.bool_)),
        "prefill": jax.jit(model.make_prefill_fn()).lower(
            params, arg((1, BUCKET), jnp.int32), row),
        "insert": make_paged_insert_fn().lower(
            pool, keys, row, arg((2,), jnp.uint32), arg((), jnp.int32),
            arg((BUCKET // PAGE,), jnp.int32), arg((), jnp.int32)),
        "reset": jax.jit(zero_state_rows, donate_argnums=(0, 1)).lower(
            pool.states, pool.convs, arg((), jnp.int32))}
    named = {"step": ("mamba2_decode_step", "moe_decode_relu2_up",
                      "moe_decode_relu2_down", "flash_decode_paged"),
             "prefill": ("mamba2_prefill_chunk", "moe_prefill_relu2_up",
                         "moe_prefill_relu2_down")}
    shapes = {"state": (slots, 64, 128, 128),
              "pages": (PAGES, 2, PAGE, 128)}
    for name, lowered in programs.items():
        text = lowered.compile().as_text()
        for kernel in named.get(name, ()):
            assert kernel in text, (name, kernel)
        if name == "prefill":
            continue
        for what, shape in shapes.items():
            if name == "reset" and what == "pages":
                continue
            dims = ",".join(str(d) for d in shape)
            assert f"[{dims}]" in text, (name, what)
            found = pool_copies(text, shape)
            print(f"nemotron_h {name} {what} {shape}: {found}")
            assert not found["layout"], (name, what, found)
            assert len(found["staged"]) <= STAGED_MAX, (name, what, found)


def test_the_state_space_chunk_reads_the_pools_and_copies_none(
        topo_devices):
    """A long prompt of the state-space hybrid in chunks
    (`NemotronH.make_prefill_suffix_fn`) at the cell's widths and the
    model's own chunk length, for the described v5e: Mosaic takes the
    scan kernel with a carried state as an operand and the attention at
    a traced offset; the chunk program reads the attention layer's page
    pools through the request's page ids — gathered rows, never a pool
    — and the scatter of a middle chunk's rows writes the donated pools
    where they lie.  The state pool is no argument of either: a chunk's
    state rides in the row cache, and the last chunk's insert (the
    whole prefill's, pinned above) writes it.  (A state-space layer
    stands BEHIND the attention layer here, as in the cell's pattern:
    a chunk returns rows and states and no logits, so whatever only the
    head would read — an attention layer's output too — is left out.)"""
    model = _nemotron(topo_devices[:1], "M*EM")
    chunk = model.prefill_chunk
    #: the cell's own pool: what its budget leaves of pages beside 128
    #: slots' states, and the trash page
    pages = (3059220480 - 128 * 21278720) // (1024 * PAGE) + 1
    assert chunk % PAGE == 0 and chunk < BUCKET
    rep = NamedSharding(model.mesh, P())
    arg = lambda shape, dt: jax.ShapeDtypeStruct(     # noqa: E731
        shape, dt, sharding=rep)
    pool = _shaped(model, functools.partial(
        PagedKVCache.create, 1, pages, 16, 2, PAGE, 128,
        2560 // PAGE, model.dtype, num_stats=len(model.STATS),
        state_shapes=model._state_shapes),
        model._cache_specs(PAGE))
    row = _shaped(model, functools.partial(
        KVCache.create, 1, 1, 2, chunk, 128, model.dtype,
        state_shapes=model._state_shapes), model._cache_specs())
    params = _shaped(
        model, lambda: model.init_params(jax.random.key(0)),
        model.param_specs())
    programs = {
        "chunk": jax.jit(model.make_prefill_suffix_fn()).lower(
            params, arg((1, chunk), jnp.int32), arg((), jnp.int32), row,
            (pool.ks, pool.vs), arg((2560 // PAGE,), jnp.int32)),
        "rows": make_paged_rows_fn().lower(
            (pool.ks, pool.vs, None, None), pool.offset, row,
            arg((chunk // PAGE,), jnp.int32))}
    shard = (pages, 2, PAGE, 128)
    dims = ",".join(str(d) for d in shard)
    for name, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        assert f"[{dims}]" in text, name
        found = pool_copies(text, shard)
        print(f"nemotron_h {name} of {chunk}, pool {shard}: {found}; "
              f"temporaries "
              f"{compiled.memory_analysis().temp_size_in_bytes >> 20} MB")
        assert not found["layout"], (name, found)
        assert len(found["staged"]) <= STAGED_MAX, (name, found)
        if name == "chunk":
            assert text.startswith("HloModule jit_prefill_shard"), text[:80]
            for kernel in ("mamba2_prefill_chunk", "flash_attention_fwd",
                           "moe_prefill_relu2_up",
                           "moe_prefill_relu2_down"):
                assert kernel in text, kernel
            assert "mamba2_decode_step" not in text


def _sdar(devices, layers=LAYERS):
    """The block-diffusion family at the cell's widths, ``layers``
    deep: (the model, its configuration's ``generation``)."""
    from triton_distributed_tpu.models.sdar_moe import SdarMoe

    c = _config("sdar-30b-a3b-1c.json")
    gen = c["generation"]
    cfg = ModelConfig(
        architecture=c["model_type"], vocab_size=c["vocab_size"],
        hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"], num_layers=layers,
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        rms_norm_eps=c["rms_norm_eps"], rope_theta=c["rope_theta"],
        qk_norm=True, tie_word_embeddings=False, max_seq_len=3584,
        dtype=c["torch_dtype"], num_experts=c["num_experts"],
        num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"],
        n_shared_experts=0, norm_topk_prob=c["norm_topk_prob"],
        moe_scoring="softmax", block_length=gen["block_length"],
        denoising_steps=gen["denoising_steps"],
        remasking=gen["remasking"], mask_token_id=c["mask_token_id"])
    model = SdarMoe(cfg, Mesh(np.array(devices), ("tp",)), mode="fused",
                    interpret=False)
    return model, gen


def test_no_pool_sized_copy_in_a_block_pass(topo_devices):
    """The block-diffusion family (`models/sdar_moe.py`): a pass
    writes every row's two block-widths — the block it finished last
    and its block in flight, eight rows a slot a layer — into its
    mapped pages through the same scatter as a decode step's one row,
    and `flash_decode_paged` takes 64 query rows a KV head, the last
    four keys hidden from the first half of them.  Compiled at the
    published widths for the described v5e: Mosaic takes the kernel at
    those rows and with that mask, and no pool is copied."""
    from triton_distributed_tpu.serving.engine_batched import (
        make_block_pass_fn)

    model, gen = _sdar(topo_devices[:1])
    cfg = model.config
    slots, n = 64, gen["block_length"]
    pages = slots * 3584 // PAGE + 1
    rep = NamedSharding(model.mesh, P())
    arg = lambda shape, dt: jax.ShapeDtypeStruct(     # noqa: E731
        shape, dt, sharding=rep)
    pool = _shaped(model, functools.partial(
        PagedKVCache.create, LAYERS, pages, slots, 4, PAGE, 128,
        3584 // PAGE, model.dtype, num_stats=len(model.STATS)),
        model._cache_specs(PAGE))
    params = _shaped(
        model, lambda: model.init_params(jax.random.key(0)),
        model.param_specs())
    blk = arg((slots, 2, 2 * n), jnp.int32)
    flag = arg((slots,), jnp.bool_)
    step = make_block_pass_fn(
        model.make_paged_decode_fn(PAGE), n, cfg.mask_token_id,
        cfg.remasking).lower(
            params, blk, pool, blk, flag, flag,
            arg((slots,), jnp.int32)).compile().as_text()
    shard = (pages, 4, PAGE, 128)
    for kernel in ("flash_decode_paged", "moe_decode_gate_up",
                   "moe_decode_down"):
        assert f"%{kernel}" in step, kernel
    assert f"[{','.join(map(str, shard))}]" in step
    found = pool_copies(step, shard)
    print(f"sdar_moe block pass, pool {shard}: {found}")
    assert not found["layout"] and len(found["staged"]) <= STAGED_MAX, (
        found)


def test_the_block_causal_chunk_reads_the_pools_and_copies_none(
        topo_devices):
    """A long prompt of the block-diffusion family in chunks
    (`SdarMoe.make_prefill_suffix_fn`) at the cell's widths, slots and
    pool, and the model's own chunk length, for the described v5e:
    Mosaic takes the attention at a traced offset UNDER THE
    BLOCK-CAUSAL MASK; the chunk program reads the page pools through
    the request's page ids — gathered rows, never a pool — and the
    scatter of a middle chunk's rows and the insert of the last one's
    (with the cursor the scheduler gives a block model) write the
    donated pools where they lie."""
    model, gen = _sdar(topo_devices[:1])
    c = _config("sdar-30b-a3b-1c.json")["serving"]
    chunk, slots = model.prefill_chunk, c["num_slots"]
    table = c["max_seq"] // PAGE
    pages = slots * c["max_seq"] // PAGE + 1
    assert chunk % PAGE == 0 and chunk % gen["block_length"] == 0
    assert chunk < BUCKET and slots == 64
    rep = NamedSharding(model.mesh, P())
    arg = lambda shape, dt: jax.ShapeDtypeStruct(     # noqa: E731
        shape, dt, sharding=rep)
    pool = _shaped(model, functools.partial(
        PagedKVCache.create, LAYERS, pages, slots, 4, PAGE, 128, table,
        model.dtype, num_stats=len(model.STATS)),
        model._cache_specs(PAGE))
    row = _shaped(model, functools.partial(
        KVCache.create, LAYERS, 1, 4, chunk, 128, model.dtype),
        model._cache_specs())
    params = _shaped(
        model, lambda: model.init_params(jax.random.key(0)),
        model.param_specs())
    programs = {
        "chunk": jax.jit(model.make_prefill_suffix_fn()).lower(
            params, arg((1, chunk), jnp.int32), arg((), jnp.int32), row,
            (pool.ks, pool.vs), arg((table,), jnp.int32)),
        "rows": make_paged_rows_fn().lower(
            (pool.ks, pool.vs, None, None), pool.offset, row,
            arg((chunk // PAGE,), jnp.int32)),
        "insert": make_paged_insert_fn().lower(
            pool, arg((slots, 2), jnp.uint32), row, arg((2,), jnp.uint32),
            arg((), jnp.int32), arg((chunk // PAGE,), jnp.int32),
            arg((), jnp.int32))}
    shard = (pages, 4, PAGE, 128)
    dims = ",".join(str(d) for d in shard)
    for name, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        assert f"[{dims}]" in text, name
        found = pool_copies(text, shard)
        print(f"sdar_moe {name} of {chunk}, pool {shard}: {found}; "
              f"temporaries "
              f"{compiled.memory_analysis().temp_size_in_bytes >> 20} MB")
        assert not found["layout"], (name, found)
        assert len(found["staged"]) <= STAGED_MAX, (name, found)
        if name == "chunk":
            assert text.startswith("HloModule jit_prefill_shard"), text[:80]
            for kernel in ("flash_attention_fwd", "moe_prefill_gate_up",
                           "moe_prefill_down"):
                assert kernel in text, kernel
            assert "moe_decode" not in text
            # (no logits: the LAST layer's expert block is read by
            # nothing and is not in the program — one call of each
            # grouped GEMM at two layers)
            assert text.count("custom_call_target=\"tpu_custom_call\"") \
                >= 3


def _cohere(devices, kinds=None):
    """The window / full hybrid at the cell's own widths, one period."""
    from triton_distributed_tpu.models.cohere2_moe import Cohere2Moe

    c = _config("command-a-plus-218b-1c.json")
    kinds = tuple(kinds or c["layer_types"])
    cfg = ModelConfig(
        architecture=c["model_type"], vocab_size=c["vocab_size"],
        hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"], num_layers=len(kinds),
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        rms_norm_eps=c["layer_norm_eps"], rope_theta=c["rope_theta"],
        qk_norm=False, rope_pairs=True, max_seq_len=25600,
        dtype=c["torch_dtype"],
        num_experts=c["share"]["experts_of_layer"],
        experts_held=tuple(c["share"]["experts_held"]),
        num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["intermediate_size"],
        n_shared_experts=c["num_shared_experts"],
        moe_shared_combine="average", moe_selection_bias=False,
        layer_types=kinds, sliding_window=c["sliding_window"])
    return Cohere2Moe(cfg, Mesh(np.array(devices), ("tp",)),
                      mode="fused", interpret=False), c


def _smallthinker(devices):
    """The full / window hybrid with 7 query heads a KV head, at the
    cell's own widths and depth."""
    from triton_distributed_tpu.models.smallthinker import SmallThinker

    c = _config("smallthinker-21b-1c.json")
    cfg = ModelConfig.from_smallthinker(
        c, max_seq_len=c["serving"]["max_seq"], dtype=c["torch_dtype"])
    return SmallThinker(cfg, Mesh(np.array(devices), ("tp",)),
                        mode="fused", interpret=False), c


@pytest.mark.parametrize("family", ["cohere2_moe", "smallthinker"])
def test_window_and_full_pools_step_chunk_and_inserts(topo_devices,
                                                      family):
    """A window / full hybrid's programs at its cell's widths, slots and
    pools, for the described v5e: the decode step over TWO page tables
    (Mosaic takes the paged kernel with a window's lower bound — and,
    for `smallthinker`, 7 query rows a KV head in every kernel), a chunk
    of a long prompt (the windowed rectangular grid at a traced offset
    over a window's gathered pages, the causal one over the full
    layer's), its rows' scatter into both kinds of pool, and the
    insert.  No program copies a pool of either kind."""
    model, c = (_cohere if family == "cohere2_moe" else _smallthinker)(
        topo_devices[:1])
    serving = c["serving"]
    slots, chunk = serving["num_slots"], model.prefill_chunk
    hkv, nwin = model.kv_row[0], model.num_window
    t = serving["max_seq"] // PAGE
    wpages = slots * (c["sliding_window"] // PAGE + 1) + 1
    pages = slots * t + 1
    rep = NamedSharding(model.mesh, P())
    arg = lambda shape, dt: jax.ShapeDtypeStruct(     # noqa: E731
        shape, dt, sharding=rep)
    pool = _shaped(model, functools.partial(
        PagedKVCache.create, model.num_full, pages, slots, hkv, PAGE, 128,
        t, model.dtype, num_stats=len(model.STATS), window_layers=nwin,
        window_pages=wpages), model._cache_specs(PAGE))
    row = _shaped(model, functools.partial(
        KVCache.create, model.num_full, 1, hkv, chunk, 128, model.dtype,
        window_layers=nwin), model._cache_specs())
    params = _shaped(
        model, lambda: model.init_params(jax.random.key(0)),
        model.param_specs())
    keys = arg((slots, 2), jnp.uint32)
    ids = arg((chunk // PAGE,), jnp.int32)
    programs = {
        "step": make_masked_step_fn(model.make_paged_decode_fn(PAGE))
        .lower(params, arg((slots,), jnp.int32), pool, keys,
               arg((slots,), jnp.bool_)),
        "prefill": jax.jit(model.make_prefill_fn()).lower(
            params, arg((1, chunk), jnp.int32), row),
        "chunk": jax.jit(model.make_prefill_suffix_fn()).lower(
            params, arg((1, chunk), jnp.int32), arg((), jnp.int32), row,
            (pool.ks, pool.vs, pool.wks, pool.wvs),
            arg((2, t), jnp.int32)),
        "rows": make_paged_rows_fn().lower(
            (pool.ks, pool.vs, None, None), pool.offset, row, ids,
            (pool.wks, pool.wvs), ids),
        "insert": make_paged_insert_fn().lower(
            pool, keys, row, arg((2,), jnp.uint32), arg((), jnp.int32),
            ids, arg((), jnp.int32), ids)}
    shards = ((pages, hkv, PAGE, 128), (wpages, hkv, PAGE, 128))
    for name, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        found = [pool_copies(text, shard) for shard in shards]
        print(f"{family} {name}: {found}; temporaries "
              f"{compiled.memory_analysis().temp_size_in_bytes >> 20} MB")
        for f in found:
            assert not f["layout"], (name, f)
            assert len(f["staged"]) <= STAGED_MAX, (name, f)
        if name == "step":
            for kernel in ("swa_decode_paged", "flash_decode_paged",
                           "moe_decode_gate_up", "moe_decode_down"):
                assert kernel in text, kernel
        if name == "chunk":
            assert text.startswith("HloModule jit_prefill_shard"), text[:80]
        if name in ("chunk", "prefill"):
            assert "swa_prefill_attention" in text
            assert "moe_prefill_gate_up" in text
