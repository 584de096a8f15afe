"""Compile + numerics sweep of every Pallas kernel family on real TPU.

The CPU interpret harness (tests/) proves multi-device *semantics*;
this sweep proves *Mosaic acceptance* and single-chip numerics of each
kernel family's compute core on hardware — the world=1 slice of each
op, plus the single-chip kernels in full.  (Multi-chip ICI paths need
a pod; their Mosaic-side constructs — remote DMA + semaphores — are
shared across kernels and exercised by the bench's fused ag_gemm.)

Reference analogue: the per-kernel test files under `test/nvidia/`
run on real GPUs only (SURVEY.md §4); here the hardware sweep is the
complement of the CPU semantic harness.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from triton_distributed_tpu.kernels.matmul import (
    MatmulConfig,
    matmul,
)


def _rel_err(got, ref):
    got = got.astype(jnp.float32)
    ref = ref.astype(jnp.float32)
    return float(jnp.abs(got - ref).max() / (jnp.abs(ref).max() + 1e-9))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_matmul(dtype):
    m = n = k = 1024
    a = (jax.random.normal(jax.random.key(0), (m, k)) / 16).astype(dtype)
    b = (jax.random.normal(jax.random.key(1), (k, n)) / 16).astype(dtype)
    out = jax.jit(functools.partial(matmul, config=MatmulConfig()))(a, b)
    ref = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32))
    assert _rel_err(out, ref) < (5e-3 if dtype == jnp.bfloat16 else 1e-5)


def test_emit_chunked_matmul():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from triton_distributed_tpu.kernels.matmul import emit_chunked_matmul

    chunks, mc, k, n = 8, 16, 1024, 1024

    def body(a_ref, b_ref, o_ref):
        emit_chunked_matmul(a_ref, b_ref, o_ref, chunks=chunks, mc=mc,
                            n=n, k=k, config=MatmulConfig(128, 512, 512))

    @jax.jit
    def f(a, b):
        return pl.pallas_call(
            body,
            out_shape=jax.ShapeDtypeStruct((chunks, mc, n), a.dtype),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True,
                vmem_limit_bytes=100 * 1024 * 1024),
        )(a, b)

    a = (jax.random.normal(jax.random.key(0), (chunks, mc, k)) / 16
         ).astype(jnp.bfloat16)
    b = (jax.random.normal(jax.random.key(1), (k, n)) / 16
         ).astype(jnp.bfloat16)
    ref = jnp.einsum("wmk,kn->wmn", a.astype(jnp.float32),
                     b.astype(jnp.float32))
    assert _rel_err(f(a, b), ref) < 5e-3


@pytest.mark.parametrize("sk", [1024, 960])  # 960: KV bound mask
def test_flash_attention(sk):
    from triton_distributed_tpu.kernels.flash_attention import (
        attention_reference, flash_attention)

    b, h, d = 1, 4, 128
    q = (jax.random.normal(jax.random.key(0), (b, h, sk, d)) / 4
         ).astype(jnp.bfloat16)
    k = (jax.random.normal(jax.random.key(1), (b, h, sk, d)) / 4
         ).astype(jnp.bfloat16)
    v = (jax.random.normal(jax.random.key(2), (b, h, sk, d)) / 4
         ).astype(jnp.bfloat16)
    out = jax.jit(functools.partial(flash_attention, causal=True))(q, k, v)
    ref = attention_reference(q, k, v, causal=True)
    assert _rel_err(out, ref) < 2e-2


@pytest.mark.parametrize("sub", [256, 512, 1024])
def test_flash_attention_diag_sub(sub):
    """The value-based single-diag kernel's sub-tile variants (incl.
    sub == block, the dense-masked form) must pass Mosaic and match
    the dense golden on hardware."""
    from triton_distributed_tpu.kernels.flash_attention import (
        attention_reference, flash_attention)

    b, h, d, s = 1, 4, 128, 1024
    q = (jax.random.normal(jax.random.key(0), (b, h, s, d)) / 4
         ).astype(jnp.bfloat16)
    k = (jax.random.normal(jax.random.key(1), (b, h, s, d)) / 4
         ).astype(jnp.bfloat16)
    v = (jax.random.normal(jax.random.key(2), (b, h, s, d)) / 4
         ).astype(jnp.bfloat16)
    out, lse = jax.jit(functools.partial(
        flash_attention, causal=True, diag_sub=sub,
        return_lse=True))(q, k, v)
    ref = attention_reference(q, k, v, causal=True)
    assert _rel_err(out, ref) < 2e-2
    assert bool(jnp.isfinite(lse).all())


def test_flash_decode():
    from triton_distributed_tpu.kernels.flash_decode import flash_decode

    b, h, hkv, s, d = 2, 8, 4, 1024, 128
    q = (jax.random.normal(jax.random.key(0), (b, h, d)) / 4
         ).astype(jnp.bfloat16)
    kc = (jax.random.normal(jax.random.key(1), (b, hkv, s, d)) / 4
          ).astype(jnp.bfloat16)
    vc = (jax.random.normal(jax.random.key(2), (b, hkv, s, d)) / 4
          ).astype(jnp.bfloat16)
    kv_len = jnp.array([s, s // 2], jnp.int32)
    out, lse = jax.jit(flash_decode)(q, kc, vc, kv_len)

    # dense golden with per-batch masking
    g = h // hkv
    kf = jnp.repeat(kc.astype(jnp.float32), g, axis=1)
    vf = jnp.repeat(vc.astype(jnp.float32), g, axis=1)
    s_ = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32), kf) * d ** -0.5
    mask = jnp.arange(s)[None, None, :] < kv_len[:, None, None]
    s_ = jnp.where(mask, s_, -1e30)
    ref = jnp.einsum("bhk,bhkd->bhd", jax.nn.softmax(s_, axis=-1), vf)
    assert _rel_err(out, ref) < 2e-2


def test_grouped_matmul():
    from triton_distributed_tpu.kernels.grouped_gemm import grouped_matmul

    e, m, k, n = 4, 64, 512, 512
    a = (jax.random.normal(jax.random.key(0), (e, m, k)) / 16
         ).astype(jnp.bfloat16)
    b = (jax.random.normal(jax.random.key(1), (e, k, n)) / 16
         ).astype(jnp.bfloat16)
    out = jax.jit(functools.partial(
        grouped_matmul, config=MatmulConfig(64, 512, 512)))(a, b)
    ref = jnp.einsum("emk,ekn->emn", a.astype(jnp.float32),
                     b.astype(jnp.float32))
    assert _rel_err(out, ref) < 5e-3


def test_ag_gemm_world1_paths():
    """World=1 slices of the TP overlap family on the real chip."""
    from triton_distributed_tpu.kernels.allgather_gemm import (
        AllGatherGEMMContext, ag_gemm)
    from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
        GEMMReduceScatterContext, gemm_rs)
    from triton_distributed_tpu.ops import shard_map_op

    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    m, k, n = 512, 1024, 1024
    a = (jax.random.normal(jax.random.key(0), (m, k)) / 16
         ).astype(jnp.bfloat16)
    b = (jax.random.normal(jax.random.key(1), (k, n)) / 16
         ).astype(jnp.bfloat16)
    ref = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32))

    ag_ctx = AllGatherGEMMContext(axis="tp", world_size=1, method="fused")
    fn = jax.jit(shard_map_op(
        functools.partial(ag_gemm, ctx=ag_ctx), mesh,
        in_specs=(P("tp", None), P(None, "tp")), out_specs=P(None, "tp")))
    assert _rel_err(fn(a, b), ref) < 5e-3

    rs_ctx = GEMMReduceScatterContext(axis="tp", world_size=1)
    fn2 = jax.jit(shard_map_op(
        functools.partial(gemm_rs, ctx=rs_ctx), mesh,
        in_specs=(P(None, "tp"), P("tp", None)), out_specs=P("tp", None)))
    assert _rel_err(fn2(a, b), ref) < 5e-3


def test_sp_attention_world1():
    """sp_ag_attention_fused at world=1 (flash path) on hardware."""
    from triton_distributed_tpu.kernels.flash_attention import (
        attention_reference)
    from triton_distributed_tpu.kernels.sp_ag_attention import (
        sp_ag_attention_fused)
    from triton_distributed_tpu.ops import shard_map_op

    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    b, h, s, d = 1, 4, 512, 128
    q = (jax.random.normal(jax.random.key(0), (b, h, s, d)) / 4
         ).astype(jnp.bfloat16)
    k = (jax.random.normal(jax.random.key(1), (b, h, s, d)) / 4
         ).astype(jnp.bfloat16)
    v = (jax.random.normal(jax.random.key(2), (b, h, s, d)) / 4
         ).astype(jnp.bfloat16)
    fn = jax.jit(shard_map_op(
        functools.partial(sp_ag_attention_fused, axis="sp"), mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None)))
    ref = attention_reference(q, k, v, causal=True)
    assert _rel_err(fn(q, k, v), ref) < 2e-2


def test_reduce_sum_pipeline():
    """The RS reduction pipeline (_emit_reduce_sum) on hardware via a
    direct pallas_call wrapper."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from triton_distributed_tpu.kernels.reduce_scatter import (
        _emit_reduce_sum)

    world, m, n = 8, 256, 512

    def body(x_ref, o_ref):
        _emit_reduce_sum(x_ref, o_ref, world=world, m=m, n=n)

    @jax.jit
    def f(x):
        return pl.pallas_call(
            body,
            out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True,
                vmem_limit_bytes=100 * 1024 * 1024),
        )(x)

    x = (jax.random.normal(jax.random.key(0), (world, m, n)) / 4
         ).astype(jnp.bfloat16)
    assert _rel_err(f(x), x.astype(jnp.float32).sum(0)) < 5e-3


def test_grouped_matmul_count_skipping():
    """Mosaic acceptance of the count-driven empty-tile skip path
    (SMEM scalar reads + pl.when inside emit_pipeline) on hardware."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from triton_distributed_tpu.kernels.grouped_gemm import (
        emit_grouped_matmul)

    e, cap, k, n = 4, 64, 512, 512
    counts = jnp.array([cap, 16, 0, 0], jnp.int32)

    def body(a_ref, b_ref, c_ref, o_ref):
        emit_grouped_matmul(a_ref, b_ref, o_ref, num_experts=e, m=cap,
                            n=n, k=k,
                            config=MatmulConfig(32, 512, 512),
                            count_of=lambda g: c_ref[g])

    @jax.jit
    def f(a, b, c):
        return pl.pallas_call(
            body,
            out_shape=jax.ShapeDtypeStruct((e, cap, n), a.dtype),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True,
                vmem_limit_bytes=100 * 1024 * 1024),
        )(a, b, c)

    rows = jax.lax.broadcasted_iota(jnp.int32, (e, cap), 1)
    mask = (rows < counts[:, None])[..., None]
    a = jnp.where(mask, jax.random.normal(jax.random.key(0),
                                          (e, cap, k)) / 16, 0.0
                  ).astype(jnp.bfloat16)
    b = (jax.random.normal(jax.random.key(1), (e, k, n)) / 16
         ).astype(jnp.bfloat16)
    out = f(a, b, counts)
    ref = jnp.einsum("eck,ekn->ecn", a.astype(jnp.float32),
                     b.astype(jnp.float32))
    assert _rel_err(out, ref) < 5e-3


def test_moe_fused_world1():
    """The one-chip expert layer (`SparseMoE`, dropless: rows packed by
    expert, `moe_*_gate_up` / `moe_*_down` grouped GEMMs) compiles and
    runs on hardware at decode rows and at prefill rows, under an even
    load and with every token on the same four experts, against the
    masked dense golden.  (Until PR 28 this test drove
    `moe_reduce_rs_fused` at world=1, a shape no layer ever calls it
    at and Mosaic rejects — PR 21; `MoEMLP` falls to XLA there.)"""
    import dataclasses

    from triton_distributed_tpu.layers.moe_mlp import SparseMoE

    moe = SparseMoE(hidden=1024, ffn=512, num_experts=16, topk=4,
                    routed_scaling=1.8)
    params = moe.init_params(jax.random.key(2))
    gold = dataclasses.replace(moe, mode="xla")
    for rows, skew in ((32, False), (32, True), (1024, False),
                       (1024, True)):
        p = dict(params)
        if skew:
            p["router_bias"] = jnp.asarray([9.0] * 4 + [0.0] * 12)
        x = jax.random.normal(jax.random.key(rows), (rows, 1024)
                              ).astype(jnp.bfloat16)
        out, stats = jax.jit(lambda x, p: moe(x, p, phase="decode"))(x, p)
        ref, _ = jax.jit(lambda x, p: gold(x, p))(x, p)
        assert _rel_err(out, ref) < 2e-2, (rows, skew)
        assert float(stats[0]) == rows * 4
        assert float(stats[1]) == (4 if skew else 16)


def test_w8a8_matmul_hardware():
    """Int8 MXU path compiles and matches exact int32 accumulation."""
    import jax.numpy as jnp
    from triton_distributed_tpu.kernels.quantized import (
        Int8MatmulConfig, matmul_w8a8)

    ka = jax.random.randint(jax.random.key(1), (256, 1024), -127, 127,
                            jnp.int8)
    kb = jax.random.randint(jax.random.key(2), (1024, 512), -127, 127,
                            jnp.int8)
    out = jax.jit(functools.partial(
        matmul_w8a8, out_dtype=jnp.float32,
        config=Int8MatmulConfig(128, 512, 1024)))(
        ka, kb, jnp.ones((256,), jnp.float32), jnp.ones((512,), jnp.float32))
    ref = jnp.dot(ka.astype(jnp.int32), kb.astype(jnp.int32))
    assert np.array_equal(np.asarray(out), np.asarray(ref, dtype=np.float32))


def test_flash_backward_hardware():
    """Mosaic acceptance + numerics of the flash backward kernels
    (dq and dk/dv) on the chip: grads of a scalar loss must match
    autodiff through the dense reference."""
    import jax.numpy as jnp
    from triton_distributed_tpu.kernels.flash_attention import (
        attention_reference, flash_attention_diff)

    b, h, hkv, s, d = 1, 4, 2, 512, 128
    keys = jax.random.split(jax.random.key(21), 4)
    q = jax.random.normal(keys[0], (b, h, s, d), jnp.float32) / 4
    k = jax.random.normal(keys[1], (b, hkv, s, d), jnp.float32) / 4
    v = jax.random.normal(keys[2], (b, hkv, s, d), jnp.float32) / 4
    w = jax.random.normal(keys[3], (b, h, s, d), jnp.float32)

    def loss_flash(q, k, v):
        out = flash_attention_diff(q, k, v, causal=True,
                                   block_q=256, block_k=256)
        return jnp.sum(out * w)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) * w)

    g = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for got, ref in zip(g, g_ref):
        assert _rel_err(got, ref) < 2e-2


def test_strided_slab_dma_hardware():
    """Mosaic acceptance of the torus kernels' phase-2 slab refs:
    a DMA whose source is `ref.at[:, j, q]` — full leading slice,
    DYNAMIC middle index, static trailing index — must compile and
    copy correctly (kernels/torus.py `_quarter_slab_ref`).  Local DMA
    exercises the same descriptor generation as the remote one."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    wx, wy, nq, mq, n = 2, 4, 4, 8, 128

    def kernel(j_ref, x_ref, o_ref, sem):
        j = j_ref[0]
        for q in range(nq):
            cp = pltpu.make_async_copy(
                x_ref.at[:, j, q], o_ref.at[:, 0, q], sem)
            cp.start()
            cp.wait()

    x = jax.random.normal(jax.random.key(7), (wx, wy, nq, mq, n),
                          jnp.float32)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((wx, 1, nq, mq, n), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )(jnp.array([2], jnp.int32), x)
    assert np.array_equal(np.asarray(out[:, 0]), np.asarray(x[:, 2]))


@pytest.mark.parametrize("m", [16, 48])
def test_w8a8_ragged_small_m_hardware(m):
    """Ragged / sub-32-row int8 shapes (the fused ring's per-rank
    shards at decode sizes) must compile on hardware with the int8
    (32, 128) native tiling — ADVICE r2: these ran only in interpret
    mode before."""
    import jax.numpy as jnp
    from triton_distributed_tpu.kernels.quantized import (
        matmul_w8a8, quantize_sym)

    k, n = 1024, 512
    a = jax.random.normal(jax.random.key(3), (m, k)).astype(jnp.bfloat16)
    b = jax.random.normal(jax.random.key(4), (k, n)).astype(jnp.bfloat16)
    aq, sa = quantize_sym(a, axis=1)
    bq, sb = quantize_sym(b, axis=0)
    out = jax.jit(matmul_w8a8)(aq, bq, sa, sb)
    ref = ((aq.astype(jnp.float32) * sa[:, None])
           @ (bq.astype(jnp.float32) * sb[None, :]))
    assert _rel_err(out, ref) < 2e-2


# ---------------------------------------------------------------------------
# The two things the serving path compiles that this sweep never had:
# the paged decode kernel, and one layer at Qwen3-8B widths.
# ---------------------------------------------------------------------------

def _to_pool(x, table, ps):
    """Dense (B, Hkv, S, ...) KV or scales -> page pool: row b's logical
    page j lands at physical page ``table[b, j]``; page 0 (the reserved
    NULL page) and any unmapped page stay zero."""
    b, hkv, s = x.shape[:3]
    t = s // ps
    pages = x.reshape(b, hkv, t, ps, *x.shape[3:])
    pages = jnp.moveaxis(pages, 2, 1).reshape(b * t, hkv, ps,
                                              *x.shape[3:])
    pool = jnp.zeros((1 + b * t,) + pages.shape[1:], x.dtype)
    return pool.at[np.asarray(table).reshape(-1)].set(pages)


@pytest.mark.parametrize("quantized,ps,hkv",
                         [(False, 16, 8), (False, 16, 2), (True, 32, 8),
                          (True, 16, 8)])
def test_flash_decode_paged_scattered_pool(quantized, ps, hkv):
    """`flash_decode_paged` against the dense kernel on the same logical
    KV, physically scattered over a pool — bf16 pages of 16 rows at 8
    KV heads (the one-chip cells) and 2 (a chip of the tp=4 cell), int8
    pages of 32 (the int8 sublane tile) and of 16 (the scheduler's
    page size), page table sized for max_seq 4096 (T = 4096 / page)."""
    from triton_distributed_tpu.kernels.flash_decode import (
        flash_decode, flash_decode_paged, quantize_kv)

    b, d, max_seq = 8, 128, 4096
    h = 4 * hkv
    t = max_seq // ps
    lens = np.array([1, 17, 100, 511, 512, 1500, 4095, 4096], np.int32)
    q = (jax.random.normal(jax.random.key(0), (b, h, d)) / 4
         ).astype(jnp.bfloat16)
    k = (jax.random.normal(jax.random.key(1), (b, hkv, max_seq, d)) / 4
         ).astype(jnp.bfloat16)
    v = (jax.random.normal(jax.random.key(2), (b, hkv, max_seq, d)) / 4
         ).astype(jnp.bfloat16)
    scales = None
    if quantized:
        k, v, ks, vs = quantize_kv(k, v)
        scales = (ks, vs)

    # Every logical page of every row gets its own physical page, at a
    # seeded random place in a pool with the reserved NULL page 0.
    table = np.random.default_rng(0).permutation(
        np.arange(1, 1 + b * t)).reshape(b, t).astype(np.int32)

    kw = {}
    if quantized:
        kw = dict(k_scale=_to_pool(scales[0], table, ps),
                  v_scale=_to_pool(scales[1], table, ps))
    out, lse = jax.jit(flash_decode_paged)(
        q, _to_pool(k, table, ps), _to_pool(v, table, ps),
        jnp.asarray(table), jnp.asarray(lens), **kw)
    kw = dict(k_scale=scales[0], v_scale=scales[1]) if quantized else {}
    ref, ref_lse = jax.jit(flash_decode)(q, k, v, jnp.asarray(lens), **kw)
    # same math, different KV split (page vs 4096-row block): bf16
    # rounding of p before the PV product is the only difference
    assert _rel_err(out, ref) < 1e-2
    assert float(jnp.abs(lse - ref_lse).max()) < 1e-2


@pytest.mark.parametrize("seq", [128, 2048])
def test_qwen3_8b_width_layer_fused_vs_xla(seq):
    """One Qwen3-8B-width layer (hidden 4096, 32/8 heads x 128, ffn
    12288, vocab 151936), prefill then one dense and one paged decode
    step, mode="fused" against mode="xla" on the same weights."""
    from triton_distributed_tpu.models.config import ModelConfig
    from triton_distributed_tpu.models.qwen import Qwen3

    cfg = ModelConfig.qwen3_8b()
    cfg.num_layers = 1
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    fused = Qwen3(cfg, mesh, mode="fused")
    xla = Qwen3(cfg, mesh, mode="xla")
    params = fused.init_params(jax.random.key(0))
    b = 8
    ids = jax.random.randint(jax.random.key(1), (b, seq), 0,
                             cfg.vocab_size)

    def close(got, ref, name):
        rms = float(jnp.sqrt(jnp.mean(ref.astype(jnp.float32) ** 2)))
        err = float(jnp.abs(got.astype(jnp.float32)
                            - ref.astype(jnp.float32)).max()) / rms
        assert err < 5e-2, (name, err)   # one bf16 layer, worst logit

    cache = fused.create_cache(b)
    lf, cache_f = jax.jit(fused.make_prefill_fn())(params, ids, cache)
    lx, _ = jax.jit(xla.make_prefill_fn())(params, ids, cache)
    close(lf, lx, "prefill")

    tok = jnp.argmax(lx, axis=-1).astype(jnp.int32)
    df, _ = jax.jit(fused.make_decode_fn())(params, tok, cache_f)
    dx, _ = jax.jit(xla.make_decode_fn())(params, tok, cache_f)
    close(df, dx, "decode")

    # the same KV, paged: row r's logical page j lives at 1 + r*T + j
    import dataclasses
    ps, t = 16, cfg.max_seq_len // 16
    table = 1 + np.arange(b * t, dtype=np.int32).reshape(b, t)
    pool = dataclasses.replace(
        fused.create_paged_cache(b, 1 + b * t, ps, t),
        ks=[_to_pool(cache_f.ks[0], table, ps)],
        vs=[_to_pool(cache_f.vs[0], table, ps)],
        offset=cache_f.offset).with_page_table(table)
    pf, _ = jax.jit(fused.make_paged_decode_fn(ps))(params, tok, pool)
    close(pf, dx, "paged decode")


#: The four-chip cell's fused calls a layer (Qwen3-8B at tp=4, this
#: chip's shard): name, which op, K and N as the kernel sees them.
LL_DECODE_SHAPES = [("wqkv", "ag", 4096, 1536), ("gate_up", "ag", 4096, 6144),
                    ("wo", "rs", 1024, 4096), ("down", "rs", 3072, 4096)]


@pytest.mark.parametrize("name,op,k,n", LL_DECODE_SHAPES,
                         ids=[s[0] for s in LL_DECODE_SHAPES])
def test_ll_decode_shape_timing(name, op, k, n, tmp_path):
    """Four chips: one `ll` call at a published decode shape (8 slots =
    2 rows a chip), timed from a device trace over 16 layers' worth of
    DIFFERENT weights (one matrix asked again is served from on-chip
    memory up to ~25 MB and reads 2x too fast), and printed beside its
    weight bytes at the HBM's peak — the per-kernel share without a
    cell run (the busiest chip's rows, as the cell's readers sum
    them).  Asserts only that the kernel ran and agrees with XLA."""
    from triton_distributed_tpu.kernels.allgather_gemm import (
        AllGatherGEMMContext, ag_gemm, ag_gemm_nonoverlap)
    from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
        GEMMReduceScatterContext, gemm_rs, gemm_rs_nonoverlap)

    world, rows, layers, reps = 4, 2, 16, 8
    if len(jax.devices()) < world:
        pytest.skip("needs four chips")
    mesh = Mesh(np.array(jax.devices()[:world]), ("tp",))
    if op == "ag":
        ctx = AllGatherGEMMContext(axis="tp", world_size=world)
        fused = functools.partial(ag_gemm, ctx=ctx)
        golden = functools.partial(ag_gemm_nonoverlap, axis="tp")
        xs, ws, os_ = P("tp", None), P(None, "tp"), P(None, "tp")
        kf, nf = k, world * n
    else:
        ctx = GEMMReduceScatterContext(axis="tp", world_size=world)
        fused = functools.partial(gemm_rs, ctx=ctx)
        golden = functools.partial(gemm_rs_nonoverlap, axis="tp")
        xs, ws, os_ = P(None, "tp"), P("tp", None), P("tp", None)
        kf, nf = world * k, n
    assert ctx.resolve_method(rows, jnp.bfloat16, k=k, n=n) == "ll"

    sh = lambda spec: jax.sharding.NamedSharding(mesh, spec)
    x = jax.jit(lambda: jax.random.normal(
        jax.random.key(1), (world * rows, kf)).astype(jnp.bfloat16),
        out_shardings=sh(xs))()
    gen = jax.jit(lambda key: (jax.random.normal(key, (kf, nf), jnp.bfloat16)
                               * kf ** -0.5).astype(jnp.bfloat16),
                  out_shardings=sh(ws))
    weights = [gen(key) for key in jax.random.split(jax.random.key(2),
                                                    layers)]

    def chain(fn):
        def per_chip(x, *ws_):
            s = jnp.float32(0)
            for w in ws_:
                s = fn(x + s.astype(x.dtype), w).reshape(-1)[0].astype(
                    jnp.float32) * 1e-6
            return s[None]
        return jax.jit(jax.shard_map(
            per_chip, mesh=mesh, in_specs=(xs,) + (ws,) * layers,
            out_specs=P("tp"), check_vma=False))

    one = lambda fn: jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(xs, ws), out_specs=os_, check_vma=False))
    got = one(fused)(x, weights[0]).astype(jnp.float32)
    ref = one(golden)(x, weights[0]).astype(jnp.float32)
    assert _rel_err(got, ref) < 2e-2

    f = chain(fused)
    f(x, *weights).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(reps):
        r = f(x, *weights)
    r.block_until_ready()
    jax.profiler.stop_trace()
    from cellbench import trace_reduce

    kernel = "ag_gemm_ll" if op == "ag" else "gemm_rs_ll"
    per_op = trace_reduce.reduce_planes(trace_reduce.read(
        trace_reduce.find_xplane(str(tmp_path)))).per_op
    us = sum(s for name_, s in per_op.items()
             if name_.startswith(kernel)) / (reps * layers) * 1e6
    floor = k * n * 2 / 819e9 * 1e6
    print(f"\n{kernel} {name}: {us:.1f} us a call; {k * n * 2} weight "
          f"bytes = {floor:.1f} us at 819 GB/s ({100 * floor / us:.0f}%)")
    assert us > 0
