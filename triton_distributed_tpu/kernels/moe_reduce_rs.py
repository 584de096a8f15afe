"""Grouped GEMM + topk-weighted combine + ReduceScatter — the MoE TP
epilogue.

Reference: `python/triton_dist/kernels/nvidia/moe_reduce_rs.py` (1432
LoC): a grouped-GEMM producer scatters tiles while a consumer does the
topk weighted reduce and a 2D reduce-scatter (`MoEReduceRSContext:245`,
producer `:380`, topk-RS consumer `:486`, rowise `:816` / colwise
`:1357` variants).

Two implementations:

- :func:`moe_reduce_rs` — staged: grouped GEMM (Pallas/MXU), topk
  combine (XLA gather+weighted-sum), reduce-scatter (Pallas ring).
  Golden reference for the fused kernel.
- :func:`moe_reduce_rs_fused` — the reference's actual pipeline as ONE
  Pallas kernel, chunk-major over the RAGGED-PACKED block schedule of
  `moe_utils.plan_chunks`: for each destination rank's chunk (in
  rank+1 swizzled order, the gemm_rs schedule) run the packed grouped
  GEMM for that chunk's occupied expert row-blocks with the
  topk-weighted combine folded into the epilogue
  (`emit_packed_combine` — each tile is scaled-and-accumulated into
  the chunk output as it leaves the MXU; the reference's topk-RS
  consumer, `moe_reduce_rs.py:486`), and put the combined chunk to
  its owner over ICI while the next chunk computes; a final pipelined
  VPU reduction sums the `world` received partials.  Both the bf16
  and the w8a8 producer run this single-phase form; when the
  (mc, n) VMEM accumulator cannot fit the scoped-VMEM ceiling the
  kernel falls back to a packed two-phase shape that stages only the
  OCCUPIED blocks through HBM (`emit_packed_matmul` +
  `emit_packed_combine_matmul`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu import collective_ids as cids

from triton_distributed_tpu.kernels import moe_utils
from triton_distributed_tpu.kernels.grouped_gemm import (
    SCALE_LANES,
    emit_packed_combine,
    emit_packed_combine_matmul,
    emit_packed_matmul,
    grouped_matmul,
)
from triton_distributed_tpu.kernels.matmul import (
    MatmulConfig,
    pad_contraction_lanes,
)
from triton_distributed_tpu.kernels.reduce_scatter import (
    ReduceScatterContext,
    ReduceScatterMethod,
    _emit_reduce_sum,
    reduce_scatter,
)
from triton_distributed_tpu.language import core as dl
from triton_distributed_tpu.utils.platform import (
    COMM_VMEM_LIMIT,
    comm_compiler_params,
    default_interpret,
)


@dataclasses.dataclass
class MoEReduceRSContext:
    """Reference analogue: `MoEReduceRSContext` (`moe_reduce_rs.py:245`)."""
    axis: str
    world_size: int
    num_experts: int
    topk: int
    gemm: MatmulConfig = dataclasses.field(default_factory=MatmulConfig)
    #: Block config for the w8a8 path (None → Int8MatmulConfig
    #: defaults).
    gemm_int8: Optional[object] = None
    rs_method: ReduceScatterMethod = ReduceScatterMethod.AUTO
    collective_id: int = cids.MOE_REDUCE_RS
    interpret: Optional[bool] = None


def create_moe_rs_context(axis: str, world_size: int, num_experts: int,
                          topk: int, **kw):
    return MoEReduceRSContext(axis=axis, world_size=world_size,
                              num_experts=num_experts, topk=topk, **kw)


def moe_reduce_rs(buckets, expert_weights, expert_ids, slot_of_pair,
                  topk_weights, ctx: MoEReduceRSContext):
    """Per-rank partial MoE output → reduced+scattered tokens.

    Call inside shard_map over `ctx.axis`.

    buckets:        (E, cap, k_loc) — routed tokens (intermediate
                    activations), this rank's TP K-shard.
    expert_weights: (E, k_loc, n) — down-projection K-shard.
    expert_ids / slot_of_pair / topk_weights: (n_tokens, topk) routing
                    (from moe_utils.route_capacity on the full token
                    set; identical on every rank).
    Returns (n_tokens / world, n): this rank's reduced row chunk.
    """
    expert_out = grouped_matmul(buckets, expert_weights, config=ctx.gemm,
                                interpret=ctx.interpret)
    combined = moe_utils.combine_tokens(expert_out, expert_ids,
                                        slot_of_pair, topk_weights)
    rs_ctx = ReduceScatterContext(axis=ctx.axis, world_size=ctx.world_size,
                                  method=ctx.rs_method,
                                  collective_id=ctx.collective_id,
                                  interpret=ctx.interpret)
    return reduce_scatter(combined, rs_ctx)


def _chunk_tables(bexp_ref, bslot_ref, nblk_ref, chunk):
    """Index-table accessors for one chunk's packed schedule (the
    scalar-prefetch idiom: SMEM reads steer the pipeline's BlockSpec
    index maps onto the dense bucket tensor)."""
    return (lambda i, c=chunk: bexp_ref[c, i],
            lambda i, c=chunk: bslot_ref[c, i],
            nblk_ref[chunk])


def _moe_rs_fused_kernel(ctx: MoEReduceRSContext, t_max, block, mc, n,
                         k, quantized, *refs):
    """Single-phase path (bf16/f32 AND w8a8): per chunk, ONE
    producer-consumer pipeline (`emit_packed_combine`) folds each
    occupied expert row-block's down-GEMM tile into a VMEM (mc, n)
    f32 accumulator as it leaves the MXU — the per-expert partials
    never exist, the combine's MXU work hides under the weight
    streaming that bounds the grouped GEMM at decode shapes, and the
    packed schedule skips at B-row granularity (a small expert costs
    one block, not its capacity)."""
    if quantized:
        (buckets_ref, w_ref, sa_ref, sw_ref, cmatb_ref,
         bexp_ref, bslot_ref, nblk_ref,
         out_ref, rbuf_ref, acc_scr, obf_scr,
         send_sems, recv_sems) = refs
    else:
        (buckets_ref, w_ref, cmatb_ref,
         bexp_ref, bslot_ref, nblk_ref,
         out_ref, rbuf_ref, acc_scr, obf_scr,
         send_sems, recv_sems) = refs
        sa_ref = sw_ref = None
    world = ctx.world_size
    cfg = ctx.gemm_int8 if quantized else ctx.gemm
    my = jax.lax.axis_index(ctx.axis)
    dl.entry_barrier(ctx.axis, world)  # every peer puts into rbuf_ref

    pending = []
    for s in range(world):
        # gemm_rs swizzle: remote chunks first (comm starts after the
        # first chunk), own chunk last (needs no transfer).
        chunk = jax.lax.rem(my + 1 + s, world)
        bexp, bslot, nblk = _chunk_tables(bexp_ref, bslot_ref,
                                          nblk_ref, chunk)
        emit_packed_combine(
            buckets_ref.at[chunk], w_ref, cmatb_ref.at[chunk], acc_scr,
            block_expert=bexp, block_slot=bslot, num_blocks=nblk,
            t_max=t_max, block=block, mc=mc, n=n, k=k, config=cfg,
            sa_ref=None if sa_ref is None else sa_ref.at[chunk],
            sb_ref=sw_ref)
        slot = s % 2
        if len(pending) >= 2:
            # Free the obf slot we are about to overwrite.
            pending.pop(0).wait_send()
        obf_scr[slot] = acc_scr[:].astype(obf_scr.dtype)
        if s == world - 1:
            # Own chunk: copy straight into our receive slot.
            local = pltpu.make_async_copy(
                obf_scr.at[slot], rbuf_ref.at[my], send_sems.at[slot])
            local.start()
            local.wait()
        else:
            rdma = pltpu.make_async_remote_copy(
                src_ref=obf_scr.at[slot],
                dst_ref=rbuf_ref.at[my],
                send_sem=send_sems.at[slot],
                recv_sem=recv_sems.at[my],
                device_id=dl.peer_id(ctx.axis, chunk),
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            rdma.start()
            pending.append(rdma)

    for rdma in pending:
        rdma.wait_send()
    for i in range(1, world):
        peer = jax.lax.rem(my + i, world)
        dl.wait_recv(rbuf_ref.at[peer], recv_sems.at[peer])

    _emit_reduce_sum(rbuf_ref, out_ref, world=world, m=mc, n=n)


def _moe_rs_fused_kernel_2p(ctx: MoEReduceRSContext, t_max, block, mc,
                            n, k, quantized, *refs):
    """Packed two-phase fallback: when the single-phase (mc, n) f32
    accumulator + double-buffered send staging would not fit
    `COMM_VMEM_LIMIT` (the guard computes via the SHARED estimator
    `analysis.resources.scratch_footprint_bytes`), stage the packed
    grouped GEMM through HBM (`pstage`, T·B rows — only the occupied
    blocks, not the dense E·cap) and run the packed combine matmul
    into the cstage/recv slots.  Same chunk choreography as the
    single-phase kernel; the combine still consumes the packed plan,
    so no dense one-hot exists on this path either."""
    if quantized:
        (buckets_ref, w_ref, sa_ref, sw_ref, cmatb_ref,
         bexp_ref, bslot_ref, nblk_ref,
         out_ref, rbuf_ref, pstage_ref, cstage_ref,
         send_sems, recv_sems) = refs
    else:
        (buckets_ref, w_ref, cmatb_ref,
         bexp_ref, bslot_ref, nblk_ref,
         out_ref, rbuf_ref, pstage_ref, cstage_ref,
         send_sems, recv_sems) = refs
        sa_ref = sw_ref = None
    world = ctx.world_size
    cfg = ctx.gemm_int8 if quantized else ctx.gemm
    my = jax.lax.axis_index(ctx.axis)
    dl.entry_barrier(ctx.axis, world)  # every peer puts into rbuf_ref

    pending = []
    for s in range(world):
        chunk = jax.lax.rem(my + 1 + s, world)
        bexp, bslot, nblk = _chunk_tables(bexp_ref, bslot_ref,
                                          nblk_ref, chunk)
        emit_packed_matmul(
            buckets_ref.at[chunk], w_ref, pstage_ref,
            block_expert=bexp, block_slot=bslot, num_blocks=nblk,
            t_max=t_max, block=block, n=n, k=k, config=cfg,
            sa_ref=None if sa_ref is None else sa_ref.at[chunk],
            sb_ref=sw_ref)
        combine = functools.partial(
            emit_packed_combine_matmul, cmatb_ref.at[chunk],
            pstage_ref, num_blocks=nblk, t_max=t_max, block=block,
            mc=mc, n=n)
        if s == world - 1:
            # Own chunk: combine straight into our receive slot.
            combine(rbuf_ref.at[my])
        else:
            slot = s % 2
            if len(pending) >= 2:
                # Free the cstage slot we are about to overwrite.
                pending.pop(0).wait_send()
            combine(cstage_ref.at[slot])
            rdma = pltpu.make_async_remote_copy(
                src_ref=cstage_ref.at[slot],
                dst_ref=rbuf_ref.at[my],
                send_sem=send_sems.at[slot],
                recv_sem=recv_sems.at[my],
                device_id=dl.peer_id(ctx.axis, chunk),
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            rdma.start()
            pending.append(rdma)

    for rdma in pending:
        rdma.wait_send()
    for i in range(1, world):
        peer = jax.lax.rem(my + i, world)
        dl.wait_recv(rbuf_ref.at[peer], recv_sems.at[peer])

    _emit_reduce_sum(rbuf_ref, out_ref, world=world, m=mc, n=n)


def moe_reduce_rs_fused(buckets, expert_weights,
                        plan: moe_utils.ChunkPlan,
                        ctx: MoEReduceRSContext, weight_scales=None):
    """Single-kernel fused MoE epilogue (reference
    `moe_reduce_rs.py:380-486`: grouped-GEMM producer + topk-RS
    consumer).  Call inside shard_map over `ctx.axis`.

    buckets:        (world, E, cap, k_loc) — per-destination-chunk
                    expert buckets of intermediate activations (e.g.
                    the activated output of `ag_group_gemm`, whose
                    leading dim is already the source-rank chunk).
    expert_weights: (E, k_loc, n) — down-projection TP K-shard.
                    With int8 weights (+ ``weight_scales`` (E, n) f32)
                    the buckets are quantized per-token on the fly and
                    the producer runs the int8 grouped GEMM — half the
                    weight-streaming bytes, 2× the MXU ceiling.
    plan:           `moe_utils.ChunkPlan` (replicated): the ragged
                    packed block schedule (`block_expert` /
                    `block_slot` / `n_blocks`) plus the per-block
                    combine weights (`combine_blocks`) — the dense
                    (mc, E·cap) one-hot of the old API is gone.
    Returns (mc, n): this rank's reduced output chunk.
    """
    world, e, cap, k = buckets.shape
    e2, k2, n = expert_weights.shape
    assert world == ctx.world_size and e == e2 == ctx.num_experts
    assert k == k2, (buckets.shape, expert_weights.shape)
    w2, t_max, block, mc = plan.combine_blocks.shape
    assert w2 == world, (plan.combine_blocks.shape, world)
    assert cap % block == 0, (cap, block)
    quantized = expert_weights.dtype == jnp.int8
    assert quantized == (weight_scales is not None), (
        "int8 expert_weights require weight_scales (and float weights "
        "must not pass them)")
    if quantized:
        assert block % 32 == 0, (
            f"int8 packed blocks need 32-row alignment, got {block}")

    out_dtype = buckets.dtype
    # The combine is an MXU matmul over one-hot-weighted coefficients:
    # run it at the activation dtype (ADVICE r5 — an f32 cmat forces
    # the whole combine to the f32 MXU rate; accumulation stays f32
    # inside the kernels either way).
    combine_blocks = plan.combine_blocks.astype(out_dtype)
    if quantized:
        from triton_distributed_tpu.kernels.quantized import quantize_sym

        buckets, sa = quantize_sym(buckets, axis=-1)  # i8, (w,E,cap)
    # Lane-align the grouped GEMM's contraction dim (see
    # `matmul.pad_contraction_lanes`).
    buckets, expert_weights, k = pad_contraction_lanes(
        buckets, expert_weights, axis_b=1)

    operands = [buckets, expert_weights]
    if quantized:
        operands += [jnp.broadcast_to(sa[..., None],
                                      (world, e, cap, SCALE_LANES)),
                     weight_scales.astype(jnp.float32).reshape(e, 1, n)]
    operands.append(combine_blocks)
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)] * len(operands)
    # Packed schedule tables ride SMEM: the pipeline's BlockSpec index
    # maps read them to place each packed block onto the dense bucket
    # tensor (the `flash_decode_paged` page-table idiom).
    operands += [plan.block_expert.astype(jnp.int32),
                 plan.block_slot.astype(jnp.int32),
                 plan.n_blocks.astype(jnp.int32)]
    in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM)] * 3

    # Single-phase scratch: f32 (mc, n) accumulator + double-buffered
    # (2, mc, n) send staging.  When that footprint cannot fit the
    # scoped-VMEM ceiling (large mc·n chunks), fall back to the packed
    # two-phase kernel that stages through HBM instead of silently
    # failing to compile.  The footprint comes from the SHARED
    # estimator (`analysis.resources`) — the same arithmetic the
    # resource sanitizer sweeps, so guard and analyzer cannot drift.
    from triton_distributed_tpu.analysis.resources import (
        scratch_footprint_bytes)
    scratch_bytes = scratch_footprint_bytes(
        [((mc, n), jnp.float32), ((2, mc, n), out_dtype)])
    two_phase = scratch_bytes > COMM_VMEM_LIMIT
    if two_phase:
        kern = functools.partial(_moe_rs_fused_kernel_2p, ctx, t_max,
                                 block, mc, n, k, quantized)
        out_shape = (
            jax.ShapeDtypeStruct((mc, n), out_dtype),
            jax.ShapeDtypeStruct((world, mc, n), out_dtype),   # rbuf
            jax.ShapeDtypeStruct((t_max, block, n), out_dtype),  # pstage
            jax.ShapeDtypeStruct((2, mc, n), out_dtype),       # cstage
        )
        scratch = []
    else:
        kern = functools.partial(_moe_rs_fused_kernel, ctx, t_max,
                                 block, mc, n, k, quantized)
        out_shape = (
            jax.ShapeDtypeStruct((mc, n), out_dtype),
            jax.ShapeDtypeStruct((world, mc, n), out_dtype),   # rbuf
        )
        scratch = [
            pltpu.VMEM((mc, n), jnp.float32),        # acc
            pltpu.VMEM((2, mc, n), out_dtype),       # obf
        ]

    # Launch-metadata event (fires once per traced specialization).
    from triton_distributed_tpu.observability import (
        emit_kernel_event, estimate_compute_us, observability_enabled)
    if observability_enabled():
        rows = t_max * block                     # packed row budget
        flops = (2 * world * rows * n * k
                 + 2 * world * mc * rows * n)
        comm_bytes = ((world - 1) * mc * n * out_dtype.itemsize
                      if world > 1 else 0)
        emit_kernel_event(
            "moe_reduce_rs_fused", kind="fused_gemm",
            method=(("w8a8_" if quantized else "")
                    + ("two_phase" if two_phase else "fused")),
            axis=ctx.axis, world=world,
            shape=(world, t_max, block, k, n),
            dtype=out_dtype, bytes_moved=comm_bytes, flops=flops,
            estimate_us=estimate_compute_us(
                flops, jnp.int8 if quantized else out_dtype),
            config=ctx.gemm,
            # Link attribution: the RS epilogue ships each reduced
            # chunk straight to its owner rank (one-sided puts).
            hops="all_pairs" if world > 1 else "none")

    rows = t_max * block
    res = pl.pallas_call(
        kern,
        name="moe_reduce_rs_fused",
        out_shape=out_shape,
        in_specs=in_specs,
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),) * len(out_shape),
        scratch_shapes=scratch + [
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((world,)),
        ],
        compiler_params=comm_compiler_params(ctx.collective_id, world),
        cost_estimate=pl.CostEstimate(
            flops=2 * world * rows * n * k + 2 * world * mc * rows * n,
            bytes_accessed=(world * rows * k + e * k * n
                            + world * mc * n) * buckets.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=default_interpret(ctx.interpret),
    )(*operands)
    return res[0]


# ---------------------------------------------------------------------------
# Comm-sanitizer registration (analysis.registry; docs/analysis.md).
# ---------------------------------------------------------------------------

from triton_distributed_tpu.analysis.registry import (  # noqa: E402
    KernelSpec,
    RefSpec,
    SemSpec,
    register_comm_kernel,
    single_axis,
)


def _moe_rs_common(axis_sizes, quantized=False):
    import numpy as np

    axis, world = single_axis(axis_sizes)
    # cap and pack block sized for the strictest sublane rule (int8:
    # 32 rows); bf16 variants share the geometry so the sweep
    # exercises one packed schedule shape for all four kernels.
    e, cap, mc, n, k = 4, 32, 8, 128, 128
    block = moe_utils.pack_block(cap)           # 32
    t_max = moe_utils.packed_block_bound(mc * 2, e, cap, block)
    ctx = MoEReduceRSContext(axis=axis, world_size=world,
                             num_experts=e, topk=2)
    # Concrete schedule tables (the steering scalars of the replay):
    # every chunk fully occupied, one block per expert.
    bexp = np.tile(np.arange(e, dtype=np.int32) % e, (world, 1))[:, :t_max]
    bslot = np.zeros((world, t_max), np.int32)
    nblk = np.full((world,), min(e, t_max), np.int32)
    tables = [RefSpec("bexp", (world, t_max), np.int32, value=bexp),
              RefSpec("bslot", (world, t_max), np.int32, value=bslot),
              RefSpec("nblk", (world,), np.int32, value=nblk)]
    return ctx, world, e, cap, mc, n, k, block, t_max, tables


@register_comm_kernel("moe_reduce_rs.fused", meshes=({"ep": 2}, {"ep": 4}))
def _analysis_moe_fused(axis_sizes):
    (ctx, world, e, cap, mc, n, k, block, t_max,
     tables) = _moe_rs_common(axis_sizes)
    return KernelSpec(
        name="moe_reduce_rs.fused",
        body=functools.partial(_moe_rs_fused_kernel, ctx, t_max, block,
                               mc, n, k, False),
        axis_sizes=axis_sizes,
        refs=[RefSpec("buckets", (world, e, cap, k), jnp.bfloat16),
              RefSpec("w", (e, k, n), jnp.bfloat16),
              RefSpec("cmatb", (world, t_max, block, mc), jnp.bfloat16),
              *tables,
              RefSpec("out", (mc, n), jnp.bfloat16),
              RefSpec("rbuf", (world, mc, n), jnp.bfloat16),
              RefSpec("acc", (mc, n), jnp.float32),
              RefSpec("obf", (2, mc, n), jnp.bfloat16)],
        sems=[SemSpec("send", (2,)), SemSpec("recv", (world,))],
    )


@register_comm_kernel("moe_reduce_rs.two_phase", meshes=({"ep": 4},))
def _analysis_moe_2p(axis_sizes):
    (ctx, world, e, cap, mc, n, k, block, t_max,
     tables) = _moe_rs_common(axis_sizes)
    return KernelSpec(
        name="moe_reduce_rs.two_phase",
        body=functools.partial(_moe_rs_fused_kernel_2p, ctx, t_max,
                               block, mc, n, k, False),
        axis_sizes=axis_sizes,
        refs=[RefSpec("buckets", (world, e, cap, k), jnp.bfloat16),
              RefSpec("w", (e, k, n), jnp.bfloat16),
              RefSpec("cmatb", (world, t_max, block, mc), jnp.bfloat16),
              *tables,
              RefSpec("out", (mc, n), jnp.bfloat16),
              RefSpec("rbuf", (world, mc, n), jnp.bfloat16),
              RefSpec("pstage", (t_max, block, n), jnp.bfloat16),
              RefSpec("cstage", (2, mc, n), jnp.bfloat16)],
        sems=[SemSpec("send", (2,)), SemSpec("recv", (world,))],
    )


@register_comm_kernel("moe_reduce_rs.w8a8", meshes=({"ep": 4},))
def _analysis_moe_q(axis_sizes):
    (ctx, world, e, cap, mc, n, k, block, t_max,
     tables) = _moe_rs_common(axis_sizes)
    return KernelSpec(
        name="moe_reduce_rs.w8a8",
        body=functools.partial(_moe_rs_fused_kernel, ctx, t_max, block,
                               mc, n, k, True),
        axis_sizes=axis_sizes,
        refs=[RefSpec("buckets", (world, e, cap, k), jnp.int8),
              RefSpec("w", (e, k, n), jnp.int8),
              RefSpec("sa", (world, e, cap, SCALE_LANES), jnp.float32),
              RefSpec("sw", (e, 1, n), jnp.float32),
              RefSpec("cmatb", (world, t_max, block, mc), jnp.bfloat16),
              *tables,
              RefSpec("out", (mc, n), jnp.bfloat16),
              RefSpec("rbuf", (world, mc, n), jnp.bfloat16),
              RefSpec("acc", (mc, n), jnp.float32),
              RefSpec("obf", (2, mc, n), jnp.bfloat16)],
        sems=[SemSpec("send", (2,)), SemSpec("recv", (world,))],
    )


@register_comm_kernel("moe_reduce_rs.w8a8_two_phase", meshes=({"ep": 4},))
def _analysis_moe_q_2p(axis_sizes):
    (ctx, world, e, cap, mc, n, k, block, t_max,
     tables) = _moe_rs_common(axis_sizes)
    return KernelSpec(
        name="moe_reduce_rs.w8a8_two_phase",
        body=functools.partial(_moe_rs_fused_kernel_2p, ctx, t_max,
                               block, mc, n, k, True),
        axis_sizes=axis_sizes,
        refs=[RefSpec("buckets", (world, e, cap, k), jnp.int8),
              RefSpec("w", (e, k, n), jnp.int8),
              RefSpec("sa", (world, e, cap, SCALE_LANES), jnp.float32),
              RefSpec("sw", (e, 1, n), jnp.float32),
              RefSpec("cmatb", (world, t_max, block, mc), jnp.bfloat16),
              *tables,
              RefSpec("out", (mc, n), jnp.bfloat16),
              RefSpec("rbuf", (world, mc, n), jnp.bfloat16),
              RefSpec("pstage", (t_max, block, n), jnp.bfloat16),
              RefSpec("cstage", (2, mc, n), jnp.bfloat16)],
        sems=[SemSpec("send", (2,)), SemSpec("recv", (world,))],
    )
