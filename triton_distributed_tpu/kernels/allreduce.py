"""AllReduce kernels over ICI.

Reference: `python/triton_dist/kernels/nvidia/allreduce.py` (1102 LoC) —
8 methods (one-shot/two-shot push, double-tree, TMA one-shot, NVLS
multimem one/two-shot, two-shot multimem-ST) with size-based
auto-selection (`get_auto_allreduce_method:1039`) and straggler fault
injection (`_run_straggler:146`).

TPU methods (no NVLS/multimem on ICI — multicast is replaced by
explicit fan-out; SURVEY.md §5):

- ``ONE_SHOT``: every device pushes its whole buffer to every peer;
  each reduces world copies locally.  One network hop — decode-latency
  optimal.
- ``TWO_SHOT``: scatter partials to chunk owners, owners reduce, then
  broadcast reduced chunks (one-shot allgather).  world× less traffic
  than one-shot for the reduce half; the TPU stand-in for the
  reference's two-shot and tree methods.
- ``RING``: bandwidth-optimal reduce-scatter ring + all-gather ring for
  large tensors.
- ``XLA``: `jax.lax.psum` golden/fallback.

Straggler injection for overlap robustness testing (reference
`_run_straggler`) is provided by `straggler_cycles`: the chosen rank
spins `pl.delay` before communicating.
"""

from __future__ import annotations

import dataclasses
import enum
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu import collective_ids as cids

from triton_distributed_tpu.kernels.reduce_scatter import _emit_reduce_sum
from triton_distributed_tpu.kernels.matmul import pad_lanes, unpad_lanes
from triton_distributed_tpu.language import core as dl
from triton_distributed_tpu.utils.platform import (
    comm_compiler_params,
    default_interpret,
)


class AllReduceMethod(enum.Enum):
    AUTO = "auto"
    ONE_SHOT = "one_shot"
    TWO_SHOT = "two_shot"
    RING = "ring"
    CHAIN = "chain"
    XLA = "xla"


def get_auto_allreduce_method(nbytes: int, world_size: int,
                              closed_ring: bool = None) -> AllReduceMethod:
    """Perf-model-driven selection (reference
    `get_auto_allreduce_method`, `allreduce.py:1039`): compare the
    predicted cost of each method on this chip generation's ICI —
    tiny payloads are latency-bound → one-shot (1 hop), medium →
    two-shot (scatter + broadcast), large → bandwidth-optimal ring.
    On OPEN topologies (no wraparound — `rings_closed()` False) the
    ring's wrap hop routes through every link (~2× busiest-link load);
    the CHAIN method needs no wrap, filling the slot the reference's
    double-tree fills (`allreduce.py:418`)."""
    from triton_distributed_tpu.kernels.comm_perf_model import (
        estimate_all_reduce_time_us, estimate_chain_allreduce_time_us,
        estimate_one_shot_time_us, estimate_two_shot_time_us,
        rings_closed)
    w = world_size
    closed = rings_closed() if closed_ring is None else closed_ring
    t_one = estimate_one_shot_time_us(nbytes, w, closed_ring=closed)
    t_two = estimate_two_shot_time_us(nbytes, w)
    t_ring = estimate_all_reduce_time_us(nbytes, w, closed_ring=closed)
    candidates = [(t_one, AllReduceMethod.ONE_SHOT),
                  (t_two, AllReduceMethod.TWO_SHOT),
                  (t_ring, AllReduceMethod.RING)]
    if not closed:
        # Wrap-free chain fills the open-topology slot the reference's
        # double-tree fills; on closed rings the hardware-validated
        # ring stays the bandwidth choice.
        candidates.append((estimate_chain_allreduce_time_us(nbytes, w),
                           AllReduceMethod.CHAIN))
    return min(candidates, key=lambda p: p[0])[1]


@dataclasses.dataclass
class AllReduceContext:
    """Reference analogue: `AllReduceContext` (`allreduce.py:76`)."""
    axis: str
    world_size: int
    method: AllReduceMethod = AllReduceMethod.AUTO
    collective_id: int = cids.ALLREDUCE
    # Fault-injection: (rank, cycles) — that rank delays before comms.
    straggler: Optional[tuple] = None
    interpret: Optional[bool] = None


def create_allreduce_context(axis: str, world_size: int, **kw):
    return AllReduceContext(axis=axis, world_size=world_size, **kw)


def _one_shot_kernel(ctx, m, n, x_ref, o_ref, rbuf_ref, local_sem,
                     send_sem, recv_sems):
    world = ctx.world_size
    my = jax.lax.axis_index(ctx.axis)
    dl.maybe_straggle(ctx.axis, ctx.straggler)
    dl.entry_barrier(ctx.axis, world)  # every peer puts into rbuf_ref

    dl.local_copy(x_ref, rbuf_ref.at[my], local_sem)
    for i in range(1, world):
        peer = jax.lax.rem(my + i, world)
        pltpu.make_async_remote_copy(
            src_ref=x_ref,
            dst_ref=rbuf_ref.at[my],
            send_sem=send_sem,
            recv_sem=recv_sems.at[my],
            device_id=dl.peer_id(ctx.axis, peer),
            device_id_type=pltpu.DeviceIdType.MESH,
        ).start()
    for i in range(1, world):
        peer = jax.lax.rem(my + i, world)
        dl.wait_recv(rbuf_ref.at[peer], recv_sems.at[peer])
    for _ in range(1, world):
        dl.wait_send(x_ref, send_sem)
    _emit_reduce_sum(rbuf_ref, o_ref, world=world, m=m, n=n)


def _two_shot_kernel(ctx, mc, n, x_ref, o_ref, rbuf_ref, local_sem,
                     send_sem, bcast_send_sem, recv_sems, bcast_sems):
    """Phase 1: scatter partial chunk c to owner c + local reduce of own
    chunk (into o_ref[my]); phase 2: broadcast reduced chunk to all."""
    world = ctx.world_size
    my = jax.lax.axis_index(ctx.axis)
    dl.maybe_straggle(ctx.axis, ctx.straggler)
    dl.entry_barrier(ctx.axis, world)  # peers put into rbuf/o_ref

    # -- scatter partials --
    dl.local_copy(x_ref.at[my], rbuf_ref.at[my], local_sem)
    for i in range(1, world):
        peer = jax.lax.rem(my + i, world)
        pltpu.make_async_remote_copy(
            src_ref=x_ref.at[peer],
            dst_ref=rbuf_ref.at[my],
            send_sem=send_sem,
            recv_sem=recv_sems.at[my],
            device_id=dl.peer_id(ctx.axis, peer),
            device_id_type=pltpu.DeviceIdType.MESH,
        ).start()
    for i in range(1, world):
        peer = jax.lax.rem(my + i, world)
        dl.wait_recv(rbuf_ref.at[peer], recv_sems.at[peer])
    for _ in range(1, world):
        dl.wait_send(x_ref.at[0], send_sem)

    # -- reduce own chunk into o_ref[my] --
    _emit_reduce_sum(rbuf_ref, o_ref.at[my], world=world, m=mc, n=n)

    # -- broadcast reduced chunk --
    for i in range(1, world):
        peer = jax.lax.rem(my + i, world)
        pltpu.make_async_remote_copy(
            src_ref=o_ref.at[my],
            dst_ref=o_ref.at[my],
            send_sem=bcast_send_sem,
            recv_sem=bcast_sems.at[my],
            device_id=dl.peer_id(ctx.axis, peer),
            device_id_type=pltpu.DeviceIdType.MESH,
        ).start()
    for i in range(1, world):
        peer = jax.lax.rem(my + i, world)
        dl.wait_recv(o_ref.at[peer], bcast_sems.at[peer])
    for _ in range(1, world):
        dl.wait_send(o_ref.at[my], bcast_send_sem)


def _chain_kernel(ctx, P, mc, n, x_ref, o_ref, staging_ref,
                  send_sem, red_sems, bcast_sems):
    """Pipelined line AllReduce (no wrap hop — the open-topology
    method; reference slot: double-tree, `allreduce.py:418`).

    Reduce: running partial sums stream chunk-by-chunk toward rank 0
    on the leftward links; broadcast: the reduced chunks stream back
    on the rightward links.  The two phases ride OPPOSITE link
    directions, so once the pipe fills they overlap fully; per
    directed link ~nbytes total, independent of world size.
    """
    world = ctx.world_size
    my = jax.lax.axis_index(ctx.axis)
    dl.maybe_straggle(ctx.axis, ctx.straggler)
    # Neighbors DMA into our staging (right) and o_ref (left).
    dl.entry_barrier(ctx.axis, world, neighbors_only=True)

    def add_into(dst, a_ref, b_ref):
        from triton_distributed_tpu.kernels.reduce_scatter import (
            emit_add_into)
        emit_add_into(dst, a_ref, b_ref, (mc, n))

    left = dl.peer_id(ctx.axis, jax.lax.max(my - 1, 0))
    right = dl.peer_id(ctx.axis,
                       jax.lax.min(my + 1, world - 1))

    # ---- reduce phase: partials flow left --------------------------
    for c in range(P):
        @pl.when(my == world - 1)
        def _(c=c):
            dl.put(x_ref.at[c], staging_ref.at[c], send_sem,
                   red_sems.at[c], left)

        @pl.when(jnp.logical_and(my > 0, my < world - 1))
        def _(c=c):
            dl.wait_recv(staging_ref.at[c], red_sems.at[c])
            add_into(staging_ref.at[c], staging_ref.at[c], x_ref.at[c])
            dl.put(staging_ref.at[c], staging_ref.at[c], send_sem,
                   red_sems.at[c], left)

        @pl.when(my == 0)
        def _(c=c):
            dl.wait_recv(staging_ref.at[c], red_sems.at[c])
            add_into(o_ref.at[c], staging_ref.at[c], x_ref.at[c])
            # Broadcast starts immediately — rides the rightward links
            # while later chunks are still reducing leftward.
            dl.put(o_ref.at[c], o_ref.at[c], send_sem,
                   bcast_sems.at[c], right)

    # ---- broadcast phase: reduced chunks flow right ----------------
    for c in range(P):
        @pl.when(jnp.logical_and(my > 0, my < world - 1))
        def _(c=c):
            dl.wait_recv(o_ref.at[c], bcast_sems.at[c])
            dl.put(o_ref.at[c], o_ref.at[c], send_sem,
                   bcast_sems.at[c], right)

        @pl.when(my == world - 1)
        def _(c=c):
            dl.wait_recv(o_ref.at[c], bcast_sems.at[c])


def _chain_chunks(m: int) -> int:
    """Pipeline depth: more chunks = earlier pipe fill, but each chunk
    must still be a reasonable DMA."""
    for p in (8, 4, 2):
        if m % p == 0:
            return p
    return 1


def all_reduce(x, ctx: AllReduceContext):
    """Sum `x` across `ctx.axis`; returns the full reduced array on
    every device.  Call inside shard_map.  x: (m, n)."""
    world = ctx.world_size
    m, n = x.shape
    method = ctx.method
    if method == AllReduceMethod.AUTO:
        method = get_auto_allreduce_method(x.size * x.dtype.itemsize, world)

    def _record(final_method):
        # Launch-metadata event (once per traced specialization).
        # Emitted only for methods that run their own kernel/collective
        # here — the RING compose delegates to reduce_scatter +
        # all_gather, which emit their own events (no double counting).
        # The hop pattern link attribution needs derives from the
        # method (instrument.hops_for_method): one/two-shot DMA chunks
        # straight to every peer; the chain reduces up the line and
        # broadcasts back down it.
        from triton_distributed_tpu.observability import (
            record_collective)
        record_collective("all_reduce", axis=ctx.axis, world=world,
                          method=final_method, shape=x.shape,
                          dtype=x.dtype,
                          payload_bytes=x.size * x.dtype.itemsize)

    if method == AllReduceMethod.XLA:
        _record(method)
        return jax.lax.psum(x, ctx.axis)

    if method == AllReduceMethod.RING:
        # Compose the flow-controlled ring RS with the ring AG.
        from triton_distributed_tpu.kernels.allgather import (
            AllGatherContext, AllGatherMethod, all_gather)
        from triton_distributed_tpu.kernels.reduce_scatter import (
            ReduceScatterContext, ReduceScatterMethod, reduce_scatter)
        if m % world != 0:
            # Rows don't tile across ranks: fall back to one-shot.
            # (Padding m up to a multiple of world would keep RING
            # usable for large non-divisible tensors; the pad/unpad
            # copies cost about what one-shot loses, so keep simple.)
            method = AllReduceMethod.ONE_SHOT
        else:
            rs_ctx = ReduceScatterContext(
                axis=ctx.axis, world_size=world,
                method=ReduceScatterMethod.RING,
                collective_id=ctx.collective_id,
                interpret=ctx.interpret)
            # Distinct id for the second kernel: the RS and AG phases
            # are sequential, but a custom ctx.collective_id must not
            # collide with another op's registered id (cids audit).
            ag_ctx = AllGatherContext(
                axis=ctx.axis, world_size=world,
                method=AllGatherMethod.RING,
                collective_id=(cids.ALLREDUCE_RING_AG
                               if ctx.collective_id == cids.ALLREDUCE
                               else ctx.collective_id),
                interpret=ctx.interpret)
            chunk = reduce_scatter(x, rs_ctx)
            return all_gather(chunk, ag_ctx)

    _record(method)
    interpret = default_interpret(ctx.interpret)
    cparams = comm_compiler_params(ctx.collective_id, world)

    # Lane-align the payload columns (Mosaic memref_slice rule — see
    # `matmul.pad_lanes`); sliced back on exit.  The RING compose
    # above delegates to hosts that pad themselves.
    x, n_orig = pad_lanes(x)
    m, n = x.shape

    if method == AllReduceMethod.CHAIN:
        if world <= 1:
            # rank 0 would wait on a put that never comes; return the
            # UNPADDED input (x was lane-padded above).
            return unpad_lanes(x, n_orig)
        P = _chain_chunks(m)
        mc = m // P
        out, _ = pl.pallas_call(
            functools.partial(_chain_kernel, ctx, P, mc, n),
            name="all_reduce_chain",
            out_shape=(
                jax.ShapeDtypeStruct((P, mc, n), x.dtype),
                jax.ShapeDtypeStruct((P, mc, n), x.dtype),  # staging
            ),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),) * 2,
            scratch_shapes=[
                pltpu.SemaphoreType.DMA(()),      # send
                pltpu.SemaphoreType.DMA((P,)),    # reduce arrivals
                pltpu.SemaphoreType.DMA((P,)),    # broadcast arrivals
            ],
            compiler_params=cparams,
            interpret=interpret,
        )(x.reshape(P, mc, n))
        return unpad_lanes(out.reshape(m, n), n_orig)

    # NOTE: HBM communication buffers are extra *outputs* (discarded),
    # not scratch — Mosaic only allows vmem/smem/semaphore scratch.
    if method == AllReduceMethod.TWO_SHOT and m % world == 0:
        mc = m // world
        out, _ = pl.pallas_call(
            functools.partial(_two_shot_kernel, ctx, mc, n),
            name="all_reduce_two_shot",
            out_shape=(
                jax.ShapeDtypeStruct((world, mc, n), x.dtype),
                jax.ShapeDtypeStruct((world, mc, n), x.dtype),
            ),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),) * 2,
            scratch_shapes=[
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA((world,)),
                pltpu.SemaphoreType.DMA((world,)),
            ],
            compiler_params=cparams,
            interpret=interpret,
        )(x.reshape(world, mc, n))
        return unpad_lanes(out.reshape(m, n), n_orig)

    # ONE_SHOT (also the fallback when shapes don't tile)
    out, _ = pl.pallas_call(
        functools.partial(_one_shot_kernel, ctx, m, n),
        name="all_reduce_one_shot",
        out_shape=(
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((world, m, n), x.dtype),
        ),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),) * 2,
        scratch_shapes=[
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((world,)),
        ],
        compiler_params=cparams,
        interpret=interpret,
    )(x)
    return unpad_lanes(out, n_orig)


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


@register_comm_kernel("allreduce.one_shot", meshes=({"tp": 2}, {"tp": 4}))
def _analysis_one_shot(axis_sizes):
    axis, world = single_axis(axis_sizes)
    m, n = 8, 128
    ctx = AllReduceContext(axis=axis, world_size=world)
    return KernelSpec(
        name="allreduce.one_shot",
        body=functools.partial(_one_shot_kernel, ctx, m, n),
        axis_sizes=axis_sizes,
        refs=[RefSpec("x", (m, n), jnp.float32),
              RefSpec("o", (m, n), jnp.float32),
              RefSpec("rbuf", (world, m, n), jnp.float32)],
        sems=[SemSpec("local"), SemSpec("send"), SemSpec("recv", (world,))],
    )


@register_comm_kernel("allreduce.two_shot", meshes=({"tp": 2}, {"tp": 4}))
def _analysis_two_shot(axis_sizes):
    axis, world = single_axis(axis_sizes)
    mc, n = 8, 128
    ctx = AllReduceContext(axis=axis, world_size=world)
    return KernelSpec(
        name="allreduce.two_shot",
        body=functools.partial(_two_shot_kernel, ctx, mc, n),
        axis_sizes=axis_sizes,
        refs=[RefSpec("x", (world, mc, n), jnp.float32),
              RefSpec("o", (world, mc, n), jnp.float32),
              RefSpec("rbuf", (world, mc, n), jnp.float32)],
        sems=[SemSpec("local"), SemSpec("send"), SemSpec("bcast_send"),
              SemSpec("recv", (world,)), SemSpec("bcast", (world,))],
    )


@register_comm_kernel("allreduce.chain", meshes=({"tp": 2}, {"tp": 4}))
def _analysis_chain(axis_sizes):
    axis, world = single_axis(axis_sizes)
    if world < 2:
        raise ValueError("chain needs world >= 2")
    m, n = 8, 128
    P = _chain_chunks(m)
    mc = m // P
    ctx = AllReduceContext(axis=axis, world_size=world)
    return KernelSpec(
        name="allreduce.chain",
        body=functools.partial(_chain_kernel, ctx, P, mc, n),
        axis_sizes=axis_sizes,
        refs=[RefSpec("x", (P, mc, n), jnp.float32),
              RefSpec("o", (P, mc, n), jnp.float32),
              RefSpec("staging", (P, mc, n), jnp.float32)],
        sems=[SemSpec("send"), SemSpec("red", (P,)), SemSpec("bcast", (P,))],
    )
