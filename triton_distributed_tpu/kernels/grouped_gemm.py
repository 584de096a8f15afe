"""Grouped (per-expert) GEMM building blocks.

The reference implements grouped GEMM as Triton kernels over
block-aligned ragged segments (`kernels/nvidia/allgather_group_gemm.py:557`,
`moe_reduce_rs.py:1003`) with native helpers computing segment
alignment (`csrc/lib/moe_utils.cu`).

TPU re-design: experts are capacity-padded (see moe_utils), so a
grouped GEMM is a *batched* matmul with static shapes
(E, cap, k) × (E, k, n) → (E, cap, n) — exactly what the MXU wants.
Provided as a standalone pallas_call and as `emit_grouped_matmul` for
use inside overlap kernels.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.analysis import resources
from triton_distributed_tpu.kernels.matmul import MatmulConfig
from triton_distributed_tpu.utils.platform import (
    SCOPED_VMEM_LIMIT,
    default_interpret,
)


def _grouped_kernel(nk: int, a_ref, b_ref, o_ref, acc_ref):
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot_general(
        a_ref[0], b_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _():
        o_ref[0] = acc_ref[:].astype(o_ref.dtype)


def grouped_matmul(a, b, config: Optional[MatmulConfig] = None,
                   out_dtype=None, interpret: Optional[bool] = None):
    """(E, m, k) @ (E, k, n) → (E, m, n), one expert per leading grid
    step, blocked for the MXU."""
    e, m, k = a.shape
    e2, k2, n = b.shape
    assert e == e2 and k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    cfg = (config or MatmulConfig()).resolve(m, n, k)
    nk = pl.cdiv(k, cfg.block_k)
    grid = (e, pl.cdiv(m, cfg.block_m), pl.cdiv(n, cfg.block_n), nk)
    # Hardware-only pre-flight (interpret mode has no VMEM ceiling).
    interp = default_interpret(interpret)
    if interp is False:
        resources.check_vmem_fit(
            "grouped_matmul",
            [((1, cfg.block_m, cfg.block_k), a.dtype),
             ((1, cfg.block_k, cfg.block_n), b.dtype),
             ((1, cfg.block_m, cfg.block_n), out_dtype)],
            [((min(cfg.block_m, m), min(cfg.block_n, n)),
              jnp.float32)])
    return pl.pallas_call(
        functools.partial(_grouped_kernel, nk),
        name="grouped_matmul",
        out_shape=jax.ShapeDtypeStruct((e, m, n), out_dtype),
        grid_spec=pl.GridSpec(
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, cfg.block_m, cfg.block_k),
                             lambda g, i, j, kk: (g, i, kk),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, cfg.block_k, cfg.block_n),
                             lambda g, i, j, kk: (g, kk, j),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, cfg.block_m, cfg.block_n),
                                   lambda g, i, j, kk: (g, i, j),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((min(cfg.block_m, m), min(cfg.block_n, n)),
                           jnp.float32)
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=SCOPED_VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * e * m * n * k,
            bytes_accessed=(e * m * k + e * k * n) * a.dtype.itemsize
            + e * m * n * jnp.dtype(out_dtype).itemsize,
            transcendentals=0,
        ),
        interpret=interp,
    )(a, b)


def emit_grouped_matmul(a_ref, b_ref, o_ref, *, num_experts, m, n, k,
                        config: Optional[MatmulConfig] = None,
                        count_of=None):
    """Grouped matmul over HBM refs inside a kernel body:
    a_ref (E, m, k), b_ref (E, k, n), o_ref (E, m, n).

    One `emit_pipeline` with the expert index as the leading grid
    dimension — a single software pipeline whose DMA prefetch crosses
    expert boundaries (the role of the reference's cross-expert tile
    scheduler `threadblock_swizzle_ag_moe.cu`), instead of E
    independent pipelines each paying setup cost.

    ``count_of`` (optional): callable ``g -> traced int`` giving the
    true token count of expert g's bucket.  Row-blocks entirely past
    the count skip the MXU work and write zeros — the token-count-
    driven tile scheduling of the reference's dynamic swizzle, in the
    form capacity padding admits (compute only non-empty tiles;
    partially-filled blocks compute in full — their padded rows are
    zeros).
    """
    cfg = (config or MatmulConfig()).resolve(m, n, k)
    nk = pl.cdiv(k, cfg.block_k)

    def inner(a_blk, b_blk, o_blk, acc_ref):
        g = pl.program_id(0)
        i = pl.program_id(1)
        kk = pl.program_id(3)
        valid = (count_of(g) > i * cfg.block_m if count_of is not None
                 else None)

        def accumulate():
            @pl.when(kk == 0)
            def _():
                acc_ref[:] = jnp.zeros_like(acc_ref)

            acc_ref[:] += jax.lax.dot_general(
                a_blk[0], b_blk[0],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        if valid is None:
            accumulate()
        else:
            pl.when(valid)(accumulate)

        @pl.when(kk == nk - 1)
        def _():
            if valid is None:
                o_blk[0] = acc_ref[:].astype(o_blk.dtype)
            else:
                @pl.when(valid)
                def _():
                    o_blk[0] = acc_ref[:].astype(o_blk.dtype)

                # Empty tile: write zeros (never leave garbage — a NaN
                # here would survive the 0-weighted combine).
                @pl.when(jnp.logical_not(valid))
                def _():
                    o_blk[0] = jnp.zeros_like(o_blk[0])

    def run(acc_ref):
        pipeline = pltpu.emit_pipeline(
            functools.partial(inner, acc_ref=acc_ref),
            grid=(num_experts, pl.cdiv(m, cfg.block_m),
                  pl.cdiv(n, cfg.block_n), nk),
            in_specs=[
                pl.BlockSpec((1, cfg.block_m, cfg.block_k),
                             lambda g, i, j, kk: (g, i, kk)),
                pl.BlockSpec((1, cfg.block_k, cfg.block_n),
                             lambda g, i, j, kk: (g, kk, j)),
            ],
            out_specs=[
                pl.BlockSpec((1, cfg.block_m, cfg.block_n),
                             lambda g, i, j, kk: (g, i, j)),
            ],
        )
        pipeline(a_ref, b_ref, o_ref)

    pl.run_scoped(
        run,
        acc_ref=pltpu.VMEM((min(cfg.block_m, m), min(cfg.block_n, n)),
                           jnp.float32),
    )


def grouped_matmul_tunable(a, b, *, config):
    """`grouped_matmul` under the autotuner calling convention
    (``config`` = a `MatmulConfig`); see `matmul_config_space` for the
    candidate space."""
    return grouped_matmul(a, b, config=config)


#: Per-token scales ride a 128-LANE-BROADCAST buffer (E, m, 128), all
#: lanes equal: Mosaic rejects lane-width-1 slices of rank-3+ VMEM
#: buffers ("Slice shape along dimension 3 must be aligned to tiling
#: (128), but is 1" — caught by test_topology_compile at world=8, the
#: same bug class as round 4's lse lane fixes).  The kernels read
#: lane 0.
SCALE_LANES = 128


def _grouped_w8a8_kernel(nk: int, a_ref, b_ref, sa_ref, sb_ref, o_ref,
                         acc_ref):
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot_general(
        a_ref[0], b_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(kk == nk - 1)
    def _():
        # Rank-1 dequant per expert: out = acc * (sa ⊗ sb); sa's
        # lanes are broadcast copies — read lane 0.
        o_ref[0] = (acc_ref[:].astype(jnp.float32)
                    * sa_ref[0][:, :1] * sb_ref[0]).astype(o_ref.dtype)


def grouped_matmul_w8a8(a_q, b_q, scale_a, scale_b, config=None,
                        out_dtype=jnp.bfloat16,
                        interpret: Optional[bool] = None):
    """Quantized grouped matmul (E, m, k)i8 @ (E, k, n)i8 → (E, m, n).

    scale_a: (E, m) f32 per-token; scale_b: (E, n) f32 per-expert
    per-output-channel.  The int8 path doubles both the MXU ceiling
    AND the weight-streaming roofline — the binding resource at MoE
    decode shapes (E=64/cap=128 measured 65 TFLOP/s weight-bound in
    bf16, a kernel sweep from before the ledger): expert weights are
    half the bytes.  The reference stops at fp8 *payloads*
    (`kernels/nvidia/low_latency_all_to_all.py`); its grouped GEMM
    (`moe_reduce_rs.py:1003`) is half-precision only.
    """
    from triton_distributed_tpu.kernels.quantized import Int8MatmulConfig

    e, m, k = a_q.shape
    e2, k2, n = b_q.shape
    assert e == e2 and k == k2, (a_q.shape, b_q.shape)
    assert a_q.dtype == jnp.int8 and b_q.dtype == jnp.int8
    cfg = (config or Int8MatmulConfig()).resolve(m, n, k)
    nk = pl.cdiv(k, cfg.block_k)
    grid = (e, pl.cdiv(m, cfg.block_m), pl.cdiv(n, cfg.block_n), nk)
    # Hardware-only pre-flight (interpret mode has no VMEM ceiling).
    interp = default_interpret(interpret)
    if interp is False:
        resources.check_vmem_fit(
            "grouped_matmul_w8a8",
            [((1, cfg.block_m, cfg.block_k), jnp.int8),
             ((1, cfg.block_k, cfg.block_n), jnp.int8),
             ((1, cfg.block_m, SCALE_LANES), jnp.float32),
             ((1, 1, cfg.block_n), jnp.float32),
             ((1, cfg.block_m, cfg.block_n), out_dtype)],
            [((min(cfg.block_m, m), min(cfg.block_n, n)), jnp.int32)])
    sa = jnp.broadcast_to(
        scale_a.astype(jnp.float32)[:, :, None], (e, m, SCALE_LANES))
    sb = scale_b.astype(jnp.float32).reshape(e, 1, n)
    return pl.pallas_call(
        functools.partial(_grouped_w8a8_kernel, nk),
        name="grouped_matmul_w8a8",
        out_shape=jax.ShapeDtypeStruct((e, m, n), out_dtype),
        grid_spec=pl.GridSpec(
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, cfg.block_m, cfg.block_k),
                             lambda g, i, j, kk: (g, i, kk),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, cfg.block_k, cfg.block_n),
                             lambda g, i, j, kk: (g, kk, j),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, cfg.block_m, SCALE_LANES),
                             lambda g, i, j, kk: (g, i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, cfg.block_n),
                             lambda g, i, j, kk: (g, 0, j),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, cfg.block_m, cfg.block_n),
                                   lambda g, i, j, kk: (g, i, j),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((min(cfg.block_m, m), min(cfg.block_n, n)),
                           jnp.int32)
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=SCOPED_VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * e * m * n * k,
            bytes_accessed=(e * m * k + e * k * n)
            + e * m * n * jnp.dtype(out_dtype).itemsize,
            transcendentals=0,
        ),
        interpret=interp,
    )(a_q, b_q, sa, sb)


def emit_grouped_matmul_w8a8(a_ref, b_ref, sa_ref, sb_ref, o_ref, *,
                             num_experts, m, n, k, config=None,
                             count_of=None):
    """Quantized grouped matmul over HBM refs inside a kernel body
    (int8 counterpart of `emit_grouped_matmul`, same single
    cross-expert pipeline and count-driven empty-tile skipping).

    a_ref (E, m, k) int8, b_ref (E, k, n) int8, sa_ref
    (E, m, SCALE_LANES) f32 lane-broadcast (see SCALE_LANES), sb_ref
    (E, 1, n) f32, o_ref (E, m, n) float.
    """
    from triton_distributed_tpu.kernels.quantized import Int8MatmulConfig

    cfg = (config or Int8MatmulConfig()).resolve(m, n, k)
    nk = pl.cdiv(k, cfg.block_k)

    def inner(a_blk, b_blk, sa_blk, sb_blk, o_blk, acc_ref):
        g = pl.program_id(0)
        i = pl.program_id(1)
        kk = pl.program_id(3)
        valid = (count_of(g) > i * cfg.block_m if count_of is not None
                 else None)

        def accumulate():
            @pl.when(kk == 0)
            def _():
                acc_ref[:] = jnp.zeros_like(acc_ref)

            acc_ref[:] += jax.lax.dot_general(
                a_blk[0], b_blk[0],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)

        if valid is None:
            accumulate()
        else:
            pl.when(valid)(accumulate)

        @pl.when(kk == nk - 1)
        def _():
            def dequant():
                o_blk[0] = (acc_ref[:].astype(jnp.float32)
                            * sa_blk[0][:, :1]
                            * sb_blk[0]).astype(o_blk.dtype)

            if valid is None:
                dequant()
            else:
                pl.when(valid)(dequant)

                @pl.when(jnp.logical_not(valid))
                def _():
                    o_blk[0] = jnp.zeros_like(o_blk[0])

    def run(acc_ref):
        pipeline = pltpu.emit_pipeline(
            functools.partial(inner, acc_ref=acc_ref),
            grid=(num_experts, pl.cdiv(m, cfg.block_m),
                  pl.cdiv(n, cfg.block_n), nk),
            in_specs=[
                pl.BlockSpec((1, cfg.block_m, cfg.block_k),
                             lambda g, i, j, kk: (g, i, kk)),
                pl.BlockSpec((1, cfg.block_k, cfg.block_n),
                             lambda g, i, j, kk: (g, kk, j)),
                pl.BlockSpec((1, cfg.block_m, SCALE_LANES),
                             lambda g, i, j, kk: (g, i, 0)),
                pl.BlockSpec((1, 1, cfg.block_n),
                             lambda g, i, j, kk: (g, 0, j)),
            ],
            out_specs=[
                pl.BlockSpec((1, cfg.block_m, cfg.block_n),
                             lambda g, i, j, kk: (g, i, j)),
            ],
        )
        pipeline(a_ref, b_ref, sa_ref, sb_ref, o_ref)

    pl.run_scoped(
        run,
        acc_ref=pltpu.VMEM((min(cfg.block_m, m), min(cfg.block_n, n)),
                           jnp.int32),
    )


def emit_packed_combine(a_ref, b_ref, cmatb_ref, acc_scr, *,
                        block_expert, block_slot, num_blocks,
                        t_max, block, mc, n, k,
                        config: Optional[MatmulConfig] = None,
                        sa_ref=None, sb_ref=None):
    """Ragged-packed grouped GEMM with the topk-weighted combine IN
    THE EPILOGUE: ``acc_scr[mc, n] (+)= sum_t cmatb[t]ᵀ (mc, B) @
    (a[e_t, s_t] (B, k) @ b[e_t] (k, n))`` in ONE software pipeline —
    each expert row-block's down-GEMM tile is scaled-and-accumulated
    into the chunk output as it leaves the MXU.  The (E, cap, n)
    partials never exist, in VMEM or HBM, and the combine's MXU work
    hides under the weight streaming that bounds the grouped GEMM at
    decode shapes (E=64/cap=128: weights are 360 MB vs 33 MB of
    activations).

    The iteration is the *packed block schedule* of
    `moe_utils.plan_chunks`: ``block_expert`` / ``block_slot``
    (callables ``t -> traced int32``, typically SMEM table reads —
    the scalar-prefetch index-table idiom of `flash_decode_paged`)
    map packed block t onto the dense (E, cap, k) bucket tensor, so
    no data is repacked; ``num_blocks`` (traced int32 occupancy, or
    None) skips everything past the last occupied block.  Skipping is
    per B-row block, not per expert: a 5-token expert costs one block
    of MXU rows instead of its full capacity — the MegaBlocks-style
    cure for small-expert MFU.

    With int8 operands, pass ``sa_ref`` ((E, cap, SCALE_LANES) f32
    lane-broadcast per-token scales) and ``sb_ref`` ((E, 1, n) f32
    per-expert channel scales): the GEMM accumulates int32 and the
    epilogue dequantizes the tile before the combine — the w8a8 path
    gets the same single-phase fusion as bf16.

    The caller owns ``acc_scr`` ((mc, n) f32 VMEM, zeroed at this
    pipeline's first step) and converts/sends it after the pipeline
    returns.  Combine multiplies run in the cmatb dtype (bf16 in
    production) with f32 accumulation — same rounding as the staged
    form, whose stage buffer is bf16.
    """
    quantized = sa_ref is not None
    cfg = (config or MatmulConfig()).resolve(block, n, k)
    bn, bk = cfg.block_n, cfg.block_k
    nk = pl.cdiv(k, bk)
    acc_dt = jnp.int32 if quantized else jnp.float32

    def inner(gacc_ref, a_blk, b_blk, c_blk, *rest):
        i = pl.program_id(0)
        j = pl.program_id(1)
        kk = pl.program_id(2)

        @pl.when(jnp.logical_and(
            i == 0, jnp.logical_and(j == 0, kk == 0)))
        def _():
            acc_scr[:] = jnp.zeros_like(acc_scr)

        valid = i < num_blocks if num_blocks is not None else None

        def gemm_step():
            @pl.when(kk == 0)
            def _():
                gacc_ref[:] = jnp.zeros_like(gacc_ref)

            gacc_ref[:] += jax.lax.dot_general(
                a_blk[0], b_blk[0],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=acc_dt)

        def combine_step():
            cm = c_blk[0]                       # (B, mc)
            if quantized:
                sa_blk, sb_blk = rest
                tile = (gacc_ref[:].astype(jnp.float32)
                        * sa_blk[0][:, :1] * sb_blk[0])
            else:
                tile = gacc_ref[:]
            # (B, mc)ᵀ-contraction with (B, bn): sublane-sliced cmatb
            # (B is the sublane dim, mc rides the lanes whole), so
            # the pack block only needs sublane alignment, not 128.
            acc_scr[:, pl.ds(j * bn, bn)] += jax.lax.dot_general(
                cm, tile.astype(cm.dtype),
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        if valid is None:
            gemm_step()
            pl.when(kk == nk - 1)(combine_step)
        else:
            pl.when(valid)(gemm_step)
            pl.when(jnp.logical_and(valid, kk == nk - 1))(combine_step)

    in_specs = [
        pl.BlockSpec((1, block, bk),
                     lambda i, j, kk: (block_expert(i), block_slot(i),
                                       kk)),
        pl.BlockSpec((1, bk, bn),
                     lambda i, j, kk: (block_expert(i), kk, j)),
        pl.BlockSpec((1, block, mc), lambda i, j, kk: (i, 0, 0)),
    ]
    operands = [a_ref, b_ref, cmatb_ref]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, block, SCALE_LANES),
                         lambda i, j, kk: (block_expert(i),
                                           block_slot(i), 0)),
            pl.BlockSpec((1, 1, bn),
                         lambda i, j, kk: (block_expert(i), 0, j)),
        ]
        operands += [sa_ref, sb_ref]

    def run(gacc_ref):
        pipeline = pltpu.emit_pipeline(
            functools.partial(inner, gacc_ref),
            grid=(t_max, pl.cdiv(n, bn), nk),
            in_specs=in_specs,
            out_specs=[],
        )
        pipeline(*operands)

    pl.run_scoped(
        run,
        gacc_ref=pltpu.VMEM((block, min(bn, n)), acc_dt),
    )


def emit_packed_matmul(a_ref, b_ref, o_ref, *, block_expert,
                       block_slot, num_blocks, t_max, block, n, k,
                       config: Optional[MatmulConfig] = None,
                       sa_ref=None, sb_ref=None):
    """Ragged-packed grouped matmul into a PACKED stage
    ``o_ref (T, B, n)`` — the HBM-staged half of the two-phase fused
    epilogue.  Same packed block schedule, operands and optional
    int8 dequant epilogue as :func:`emit_packed_combine`, but the
    tile is written to its packed stage row instead of being combined
    in VMEM: the stage holds only occupied blocks (T·B rows, ≤ the
    dense E·cap and typically far fewer), so the HBM round-trip the
    two-phase form pays shrinks with the packing ratio.  Blocks past
    ``num_blocks`` write zeros (never garbage — the packed combine
    skips them anyway, but a NaN must not survive a schedule bug)."""
    quantized = sa_ref is not None
    cfg = (config or MatmulConfig()).resolve(block, n, k)
    bn, bk = cfg.block_n, cfg.block_k
    nk = pl.cdiv(k, bk)
    acc_dt = jnp.int32 if quantized else jnp.float32

    def inner(gacc_ref, *refs):
        (a_blk, b_blk, *rest), o_blk = refs[:-1], refs[-1]
        i = pl.program_id(0)
        kk = pl.program_id(2)
        valid = i < num_blocks if num_blocks is not None else None

        def gemm_step():
            @pl.when(kk == 0)
            def _():
                gacc_ref[:] = jnp.zeros_like(gacc_ref)

            gacc_ref[:] += jax.lax.dot_general(
                a_blk[0], b_blk[0],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=acc_dt)

        def write_step():
            if quantized:
                sa_blk, sb_blk = rest
                tile = (gacc_ref[:].astype(jnp.float32)
                        * sa_blk[0][:, :1] * sb_blk[0])
            else:
                tile = gacc_ref[:]
            o_blk[0] = tile.astype(o_blk.dtype)

        if valid is None:
            gemm_step()
            pl.when(kk == nk - 1)(write_step)
        else:
            pl.when(valid)(gemm_step)
            pl.when(jnp.logical_and(valid, kk == nk - 1))(write_step)

            @pl.when(jnp.logical_and(jnp.logical_not(valid),
                                     kk == nk - 1))
            def _():
                o_blk[0] = jnp.zeros_like(o_blk[0])

    in_specs = [
        pl.BlockSpec((1, block, bk),
                     lambda i, j, kk: (block_expert(i), block_slot(i),
                                       kk)),
        pl.BlockSpec((1, bk, bn),
                     lambda i, j, kk: (block_expert(i), kk, j)),
    ]
    operands = [a_ref, b_ref]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, block, SCALE_LANES),
                         lambda i, j, kk: (block_expert(i),
                                           block_slot(i), 0)),
            pl.BlockSpec((1, 1, bn),
                         lambda i, j, kk: (block_expert(i), 0, j)),
        ]
        operands += [sa_ref, sb_ref]

    def run(gacc_ref):
        pipeline = pltpu.emit_pipeline(
            functools.partial(inner, gacc_ref),
            grid=(t_max, pl.cdiv(n, bn), nk),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block, bn), lambda i, j, kk: (i, 0, j)),
            ],
        )
        pipeline(*operands, o_ref)

    pl.run_scoped(
        run,
        gacc_ref=pltpu.VMEM((block, min(bn, n)), acc_dt),
    )


def emit_packed_combine_matmul(cmatb_ref, stage_ref, o_ref, *,
                               num_blocks, t_max, block, mc, n,
                               block_m: int = 256, block_n: int = 512):
    """``o[mc, n] = sum_t cmatb[t]ᵀ (mc, B) @ stage[t] (B, n)`` — the
    combine half of the two-phase fused epilogue, consuming the
    PACKED stage `emit_packed_matmul` produced.  Blocks past
    ``num_blocks`` (traced occupancy, or None) are skipped.
    Multiplies run in the cmatb dtype with f32 accumulation, the same
    rounding as the single-phase epilogue."""
    bm = min(block_m, mc)
    bn = min(block_n, n)

    def inner(c_blk, s_blk, o_blk, acc_ref):
        i = pl.program_id(2)

        @pl.when(i == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        valid = i < num_blocks if num_blocks is not None else None

        def accumulate():
            cm = c_blk[0]                       # (B, bm)
            acc_ref[:] += jax.lax.dot_general(
                cm, s_blk[0].astype(cm.dtype),
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        if valid is None:
            accumulate()
        else:
            pl.when(valid)(accumulate)

        @pl.when(i == t_max - 1)
        def _():
            o_blk[:] = acc_ref[:].astype(o_blk.dtype)

    def run(acc_ref):
        pipeline = pltpu.emit_pipeline(
            functools.partial(inner, acc_ref=acc_ref),
            grid=(pl.cdiv(mc, bm), pl.cdiv(n, bn), t_max),
            in_specs=[
                pl.BlockSpec((1, block, bm), lambda mi, j, i: (i, 0, mi)),
                pl.BlockSpec((1, block, bn), lambda mi, j, i: (i, 0, j)),
            ],
            out_specs=[
                pl.BlockSpec((bm, bn), lambda mi, j, i: (mi, j)),
            ],
        )
        pipeline(cmatb_ref, stage_ref, o_ref)

    pl.run_scoped(run, acc_ref=pltpu.VMEM((bm, bn), jnp.float32))


# ---------------------------------------------------------------------------
# Dropless packed expert GEMMs (`moe_utils.pack_by_expert` layout)
# ---------------------------------------------------------------------------


def _expert_tile(n: int, most: int = 512) -> int:
    """Widest lane-aligned column tile of ``n`` at most ``most``."""
    for tn in range(min(most, n) // 128 * 128, 0, -128):
        if n % tn == 0:
            return tn
    return n


def _packed_call(kernel, name, operands, weight_specs, plan_tables, *,
                 rows, block, k, n, tn, out_dtype, row_operands=(),
                 interpret=None):
    """The grid both packed expert GEMMs share: (column tiles, row
    blocks), row blocks innermost, so one (expert, column tile) of the
    weights is fetched once for the consecutive blocks of that expert
    and the blocks past ``n_blocks`` — mapped to the last block in use
    — fetch and store nothing."""
    block_expert, n_blocks = plan_tables
    t_max = rows // block

    def row_map(j, t, bexp, nblk):
        return (jnp.minimum(t, nblk[0] - 1), 0)

    def out_map(j, t, bexp, nblk):
        return (jnp.minimum(t, nblk[0] - 1), j)

    in_specs = [pl.BlockSpec((block, k), row_map,
                             memory_space=pltpu.VMEM)]
    in_specs += weight_specs
    in_specs += [pl.BlockSpec((block, 1), row_map,
                              memory_space=pltpu.VMEM)
                 for _ in row_operands]
    w_itemsize = operands[1].dtype.itemsize
    return pl.pallas_call(
        kernel,
        name=name,
        out_shape=jax.ShapeDtypeStruct((rows, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, t_max),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block, tn), out_map,
                                   memory_space=pltpu.VMEM),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=SCOPED_VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n * (len(operands) - 1),
            # every expert once (the worst case), the rows once a tile
            bytes_accessed=(operands[1].shape[0] * k * n * w_itemsize
                            * (len(operands) - 1)
                            + rows * k * (n // tn) * 2 + rows * n * 2),
            transcendentals=0,
        ),
        interpret=default_interpret(interpret),
    )(block_expert.astype(jnp.int32),
      jnp.reshape(n_blocks, (1,)).astype(jnp.int32),
      *operands, *row_operands)


def _gate_up_kernel(bexp_ref, nblk_ref, x_ref, g_ref, u_ref, o_ref):
    @pl.when(pl.program_id(1) < nblk_ref[0])
    def _():
        x = x_ref[...]
        dims = (((1,), (0,)), ((), ()))
        g = jax.lax.dot_general(x, g_ref[0], dims,
                                preferred_element_type=jnp.float32)
        u = jax.lax.dot_general(x, u_ref[0], dims,
                                preferred_element_type=jnp.float32)
        o_ref[...] = (g * jax.nn.sigmoid(g) * u).astype(o_ref.dtype)


def _relu_gate_up_kernel(bexp_ref, nblk_ref, x_ref, g_ref, u_ref, o_ref):
    @pl.when(pl.program_id(1) < nblk_ref[0])
    def _():
        x = x_ref[...]
        dims = (((1,), (0,)), ((), ()))
        g = jax.lax.dot_general(x, g_ref[0], dims,
                                preferred_element_type=jnp.float32)
        u = jax.lax.dot_general(x, u_ref[0], dims,
                                preferred_element_type=jnp.float32)
        o_ref[...] = (jnp.maximum(g, 0.0) * u).astype(o_ref.dtype)


#: The gated up-projection's kernel by the gate's activation.
_GATED = {"silu": _gate_up_kernel, "relu": _relu_gate_up_kernel}


def packed_expert_gate_up(x_rows, w_gate, w_up, block_expert, n_blocks,
                          *, block: int, name: str = "moe_gate_up",
                          act: str = "silu",
                          interpret: Optional[bool] = None):
    """``act(x W_gate[e]) * (x W_up[e])`` for rows packed by expert;
    ``act``: the gate's activation, "silu" or "relu" (a kernel each:
    the default is the program it was).

    x_rows: (T * block, h) — `PackedPlan` row order, padding rows
    zero; w_gate / w_up: (E, h, f); block_expert: (T,); n_blocks: ().
    Returns (T * block, f) in x's dtype; rows of blocks past
    ``n_blocks`` are not written.  Only the experts named by the
    blocks in use are read.  ``name`` is the kernel's name in a device
    trace (the expert layer names its decode and prefill calls
    apart)."""
    rows, h = x_rows.shape
    e, h2, f = w_gate.shape
    assert h == h2 and w_up.shape == w_gate.shape and rows % block == 0
    tn = _expert_tile(f)
    wspec = pl.BlockSpec((1, h, tn),
                         lambda j, t, bexp, nblk: (bexp[t], 0, j),
                         memory_space=pltpu.VMEM)
    return _packed_call(
        _GATED[act], name, [x_rows, w_gate, w_up],
        [wspec, wspec], (block_expert, n_blocks), rows=rows,
        block=block, k=h, n=f, tn=tn, out_dtype=x_rows.dtype,
        interpret=interpret)


def _relu2_up_kernel(bexp_ref, nblk_ref, x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(1) < nblk_ref[0])
    def _():
        u = jnp.maximum(jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32), 0.0)
        o_ref[...] = (u * u).astype(o_ref.dtype)


def packed_expert_relu2_up(x_rows, w_up, block_expert, n_blocks, *,
                           block: int, name: str = "moe_relu2_up",
                           interpret: Optional[bool] = None):
    """``relu(x W_up[e]) ** 2`` for rows packed by expert: the
    up-projection of an expert of TWO matrices (no gate matrix).

    x_rows: (T * block, k); w_up: (E, k, f); the rest as
    `packed_expert_gate_up`.  Returns (T * block, f) in x's dtype."""
    rows, k = x_rows.shape
    e, k2, f = w_up.shape
    assert k == k2 and rows % block == 0
    tn = _expert_tile(f)
    wspec = pl.BlockSpec((1, k, tn),
                         lambda j, t, bexp, nblk: (bexp[t], 0, j),
                         memory_space=pltpu.VMEM)
    return _packed_call(
        _relu2_up_kernel, name, [x_rows, w_up], [wspec],
        (block_expert, n_blocks), rows=rows, block=block, k=k, n=f,
        tn=tn, out_dtype=x_rows.dtype, interpret=interpret)


def _down_kernel(bexp_ref, nblk_ref, a_ref, w_ref, s_ref, o_ref):
    @pl.when(pl.program_id(1) < nblk_ref[0])
    def _():
        y = jax.lax.dot_general(a_ref[...], w_ref[0],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        o_ref[...] = (y * s_ref[...]).astype(o_ref.dtype)


def packed_expert_down(act_rows, w_down, row_weight, block_expert,
                       n_blocks, *, block: int, name: str = "moe_down",
                       interpret: Optional[bool] = None):
    """``(act W_down[e]) * row_weight`` for rows packed by expert: the
    combine weight is applied to the float32 accumulator, so the
    caller's combine is a plain sum of each token's rows.

    act_rows: (T * block, f); w_down: (E, f, h); row_weight:
    (T * block,) f32.  Returns (T * block, h) in act's dtype."""
    rows, f = act_rows.shape
    e, f2, h = w_down.shape
    assert f == f2 and rows % block == 0
    tn = _expert_tile(h)
    wspec = pl.BlockSpec((1, f, tn),
                         lambda j, t, bexp, nblk: (bexp[t], 0, j),
                         memory_space=pltpu.VMEM)
    return _packed_call(
        _down_kernel, name, [act_rows, w_down], [wspec],
        (block_expert, n_blocks), rows=rows, block=block, k=f, n=h,
        tn=tn, out_dtype=act_rows.dtype,
        row_operands=[row_weight.astype(jnp.float32)[:, None]],
        interpret=interpret)


# ---------------------------------------------------------------------------
# Resource-sanitizer registration (analysis.resources).
# ---------------------------------------------------------------------------


@resources.register_resource_kernel("grouped_gemm.grouped")
def _resource_grouped():
    a = jnp.zeros((4, 256, 512), jnp.bfloat16)
    b = jnp.zeros((4, 512, 256), jnp.bfloat16)
    with resources.capture_pallas_calls() as records:
        grouped_matmul(a, b, interpret=False)
    return records


@resources.register_resource_kernel("grouped_gemm.w8a8")
def _resource_grouped_w8a8():
    a = jnp.zeros((4, 256, 512), jnp.int8)
    b = jnp.zeros((4, 512, 256), jnp.int8)
    sa = jnp.ones((4, 256), jnp.float32)
    sb = jnp.ones((4, 256), jnp.float32)
    with resources.capture_pallas_calls() as records:
        grouped_matmul_w8a8(a, b, sa, sb, interpret=False)
    return records


@resources.register_resource_kernel("grouped_gemm.packed_experts")
def _resource_packed_experts():
    e, h, f, block, t = 8, 256, 384, 16, 12
    bexp = jnp.zeros((t,), jnp.int32)
    x = jnp.zeros((t * block, h), jnp.bfloat16)
    with resources.capture_pallas_calls() as records:
        act = packed_expert_gate_up(
            x, jnp.zeros((e, h, f), jnp.bfloat16),
            jnp.zeros((e, h, f), jnp.bfloat16), bexp, jnp.int32(t),
            block=block, interpret=False)
        packed_expert_down(
            act, jnp.zeros((e, f, h), jnp.bfloat16),
            jnp.zeros((t * block,), jnp.float32), bexp, jnp.int32(t),
            block=block, interpret=False)
        packed_expert_relu2_up(
            x, jnp.zeros((e, h, f), jnp.bfloat16), bexp, jnp.int32(t),
            block=block, interpret=False)
    return records
