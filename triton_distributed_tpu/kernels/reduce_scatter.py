"""ReduceScatter kernels over ICI.

Reference: `python/triton_dist/kernels/nvidia/reduce_scatter.py` (882
LoC): intra-node scatter into per-rank symmetric buffers + ring/TMA
reduce (`intra_node_scatter:597`, `kernel_ring_reduce_tma:716`), 2D
intra+inter decomposition, `reduce_scatter_2d_op:873`.

TPU methods:

- ``SCATTER_REDUCE`` (one-shot): every device puts its partial chunk c
  directly to chunk-owner c; owners then sum world contributions with a
  pipelined VPU reduction.  Maps to the reference's scatter-then-reduce
  decomposition; latency-optimal, and on an ICI torus the direct puts
  ride disjoint links.
- ``RING``: bandwidth-optimal ring with running partial sums and
  credit-based flow control (acks) so a fast left neighbor cannot
  overrun the 2-slot staging buffer.
- ``XLA``: `jax.lax.psum_scatter` golden/fallback.

All inputs are per-device partials of the *full* array: (world*m, n);
output is this device's reduced chunk (m, n).
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

from triton_distributed_tpu.kernels.matmul import pad_lanes, unpad_lanes
from triton_distributed_tpu.language import core as dl
from triton_distributed_tpu.utils.platform import (
    comm_compiler_params,
    default_interpret,
)


class ReduceScatterMethod(enum.Enum):
    AUTO = "auto"
    SCATTER_REDUCE = "scatter_reduce"
    RING = "ring"
    XLA = "xla"


@dataclasses.dataclass
class ReduceScatterContext:
    """Reference analogue: `ReduceScatter2DContext`
    (`reduce_scatter.py:46-146`)."""
    axis: str
    world_size: int
    method: ReduceScatterMethod = ReduceScatterMethod.AUTO
    collective_id: int = cids.REDUCE_SCATTER
    interpret: Optional[bool] = None
    #: Fault injection (reference `_run_straggler`,
    #: `stress_test_ag_gemm.py:119-121`): (rank, cycles) delays that
    #: rank at kernel entry; `for_correctness` staggers every rank.
    straggler: Optional[tuple] = None
    for_correctness: bool = False

    def resolve_method(self, nbytes_per_chunk: int,
                       bus=None) -> ReduceScatterMethod:
        if self.method != ReduceScatterMethod.AUTO:
            return self.method
        # Perf-model-driven: one-shot wins until chunks are large
        # enough that world-1 parallel long-haul puts congest the
        # torus links (see estimate_one_shot_time_us).  ``bus``:
        # optional feedback bus whose live link heat shifts the
        # crossover; absent/empty/stale ⇒ the static choice.
        from triton_distributed_tpu.kernels.comm_perf_model import (
            one_shot_beats_ring)
        if one_shot_beats_ring(nbytes_per_chunk, self.world_size,
                               axis=self.axis, bus=bus,
                               op="reduce_scatter"):
            return ReduceScatterMethod.SCATTER_REDUCE
        return ReduceScatterMethod.RING


def create_reduce_scatter_context(axis: str, world_size: int, **kw):
    return ReduceScatterContext(axis=axis, world_size=world_size, **kw)


# ---------------------------------------------------------------------------
# Pipelined sum over the `world` leading dim of an HBM buffer.
# ---------------------------------------------------------------------------

def _emit_reduce_sum(src_ref, out_ref, *, world, m, n, block_m=256,
                     accum_dtype=jnp.float32):
    """out[m,n] = sum over w of src[w,m,n], pipelined through VMEM.

    The VPU analogue of the reference's `kernel_ring_reduce_*`
    (`reduce_scatter.py:689-744`)."""
    bm = min(block_m, m)

    def inner(*refs):
        out_blk = refs[-1]
        acc = refs[0][:].astype(accum_dtype)
        for w in range(1, world):
            acc = acc + refs[w][:].astype(accum_dtype)
        out_blk[:] = acc.astype(out_blk.dtype)

    # One in_spec per world-slot (not a single (world, bm, n) block):
    # keeps each DMA a plain 2D tile.
    pipeline = pltpu.emit_pipeline(
        inner,
        grid=(pl.cdiv(m, bm),),
        in_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0))] * world,
        out_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0))],
    )
    pipeline(*[src_ref.at[w] for w in range(world)], out_ref)


def emit_add_into(dst, a_ref, b_ref, shape):
    """dst = a + b (f32 accumulate), pipelined through VMEM; handles
    2D (rows, n) chunk refs and any number of leading slab dims —
    (w, rows, n), (wa, wb, rows, n) for the 3-axis torus.  Shared by
    the ring/chain/torus reduce kernels — one place owns the blocking
    and the cast dance.  ``dst`` may alias ``a_ref``."""
    def inner(a_blk, b_blk, o_blk):
        o_blk[:] = (a_blk[:].astype(jnp.float32)
                    + b_blk[:].astype(jnp.float32)).astype(o_blk.dtype)

    lead, (rows, n) = tuple(shape[:-2]), shape[-2:]
    bm = min(256, rows)
    grid = lead + (pl.cdiv(rows, bm),)
    spec = pl.BlockSpec((1,) * len(lead) + (bm, n),
                        lambda *ids: ids[:-1] + (ids[-1], 0))
    pltpu.emit_pipeline(
        inner, grid=grid, in_specs=[spec] * 2, out_specs=[spec],
    )(a_ref, b_ref, dst)


# ---------------------------------------------------------------------------
# One-shot scatter + local reduce
# ---------------------------------------------------------------------------

def emit_scatter_reduce(axis, world, src_ref, out_ref, rbuf_ref,
                        local_sem, send_sem, recv_sems, *, m, n,
                        barrier: bool = True):
    """One-shot scatter-reduce usable from inside larger kernels:
    chunk c of ``src_ref`` (world, m, n) is put to owner c (1 hop, all
    peers concurrent; slot = sender's rank on the receiver), then the
    ``world`` received partials are summed into ``out_ref`` (m, n).
    Shared by the standalone SCATTER_REDUCE collective and the fused
    low-latency overlap kernels."""
    my = jax.lax.axis_index(axis)
    if barrier:
        dl.entry_barrier(axis, world)  # every peer puts into rbuf_ref

    # Our own partial for our own chunk.
    dl.local_copy(src_ref.at[my], rbuf_ref.at[my], local_sem)

    # Push partial chunk c to owner c; slot = my rank on the receiver.
    for i in range(1, world):
        peer = jax.lax.rem(my + i, world)
        pltpu.make_async_remote_copy(
            src_ref=src_ref.at[peer],
            dst_ref=rbuf_ref.at[my],
            send_sem=send_sem,
            recv_sem=recv_sems.at[my],
            device_id=dl.peer_id(axis, peer),
            device_id_type=pltpu.DeviceIdType.MESH,
        ).start()

    # Wait for the other world-1 partials of *our* chunk to land.
    for i in range(1, world):
        peer = jax.lax.rem(my + i, world)
        dl.wait_recv(rbuf_ref.at[peer], recv_sems.at[peer])

    # Drain sends.
    for _ in range(1, world):
        dl.wait_send(rbuf_ref.at[my], send_sem)

    _emit_reduce_sum(rbuf_ref, out_ref, world=world, m=m, n=n)


def _scatter_reduce_kernel(ctx, m, n, x_ref, out_ref, rbuf_ref,
                           local_sem, send_sem, recv_sems):
    dl.maybe_straggle(ctx.axis, ctx.straggler)
    dl.correctness_delay(ctx.axis, ctx.for_correctness)
    emit_scatter_reduce(ctx.axis, ctx.world_size, x_ref, out_ref,
                        rbuf_ref, local_sem, send_sem, recv_sems,
                        m=m, n=n)


# ---------------------------------------------------------------------------
# Ring with running sums + ack-based flow control
# ---------------------------------------------------------------------------

def _ring_rs_kernel(ctx, m, n, x_ref, out_ref, staging_ref, accum_ref,
                    local_sem, send_sem, recv_sems, ack_sem):
    world = ctx.world_size
    my = jax.lax.axis_index(ctx.axis)
    right = jax.lax.rem(my + 1, world)
    left = jax.lax.rem(my - 1 + world, world)
    dl.maybe_straggle(ctx.axis, ctx.straggler)
    dl.correctness_delay(ctx.axis, ctx.for_correctness)
    dl.entry_barrier(ctx.axis, world, neighbors_only=True)

    def add_into(dst, a_ref, b_ref):
        # dst = a + b, pipelined (dst may alias a_ref).
        def inner(a_blk, b_blk, o_blk):
            o_blk[:] = (a_blk[:].astype(jnp.float32)
                        + b_blk[:].astype(jnp.float32)).astype(o_blk.dtype)
        pltpu.emit_pipeline(
            inner,
            grid=(pl.cdiv(m, 256),),
            in_specs=[pl.BlockSpec((min(256, m), n), lambda i: (i, 0))] * 2,
            out_specs=[pl.BlockSpec((min(256, m), n), lambda i: (i, 0))],
        )(a_ref, b_ref, dst)

    for s in range(world - 1):
        slot = s % 2
        send_chunk = jax.lax.rem(my - 1 - s + 2 * world, world)
        # Flow control: from step 2 on, the slot we are about to send
        # into on the right neighbor must have been consumed there.
        if s >= 2:
            pltpu.semaphore_wait(ack_sem, 1)
        src = x_ref.at[send_chunk] if s == 0 else accum_ref.at[slot]
        rdma = pltpu.make_async_remote_copy(
            src_ref=src,
            dst_ref=staging_ref.at[slot],
            send_sem=send_sem,
            recv_sem=recv_sems.at[slot],
            device_id=dl.peer_id(ctx.axis, right),
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        rdma.start()

        recv_chunk = jax.lax.rem(my - 2 - s + 2 * world, world)
        dl.wait_recv(staging_ref.at[slot], recv_sems.at[slot])
        # accum[next_slot] = staging[slot] + local partial(recv_chunk)
        nslot = (s + 1) % 2
        if s < world - 2:
            add_into(accum_ref.at[nslot], staging_ref.at[slot],
                     x_ref.at[recv_chunk])
        else:
            add_into(out_ref, staging_ref.at[slot], x_ref.at[recv_chunk])
        # Tell the left neighbor the slot is free again.
        pltpu.semaphore_signal(ack_sem, inc=1, device_id=dl.peer_id(ctx.axis, left),
                               device_id_type=pltpu.DeviceIdType.MESH)
        rdma.wait_send()

    # Drain leftover acks (the last two signals are never waited on).
    n_leftover = min(2, world - 1)
    pltpu.semaphore_wait(ack_sem, n_leftover)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def reduce_scatter(x, ctx: ReduceScatterContext):
    """x: per-device partials (world*m, n) → this device's reduced
    chunk (m, n).  Call inside shard_map."""
    world = ctx.world_size
    mt = x.shape[0]
    assert mt % world == 0, (x.shape, world)
    m = mt // world
    method = ctx.resolve_method(m * x.shape[1] * x.dtype.itemsize)

    # Launch-metadata event (fires once per traced specialization).
    from triton_distributed_tpu.observability import record_collective
    # The hop pattern link attribution needs derives from the method
    # (instrument.hops_for_method): the ring circulates chunks over +1
    # neighbor links; scatter_reduce pushes one chunk straight to each
    # peer (dimension-ordered over the torus).
    record_collective("reduce_scatter", axis=ctx.axis, world=world,
                      method=method, shape=x.shape, dtype=x.dtype,
                      payload_bytes=m * x.shape[1] * x.dtype.itemsize)

    if method == ReduceScatterMethod.XLA:
        return jax.lax.psum_scatter(
            x.reshape(world, m, x.shape[1]), ctx.axis,
            scatter_dimension=0, tiled=False)

    # Lane-align the payload columns (see `matmul.pad_lanes`).
    x, n_orig = pad_lanes(x)
    n = x.shape[1]

    interpret = default_interpret(ctx.interpret)
    cparams = comm_compiler_params(ctx.collective_id, world)
    xr = x.reshape(world, m, n)

    # NOTE: HBM communication buffers are extra *outputs* (discarded),
    # not scratch — Mosaic only allows vmem/smem/semaphore scratch.
    if method == ReduceScatterMethod.SCATTER_REDUCE:
        out, _ = pl.pallas_call(
            functools.partial(_scatter_reduce_kernel, ctx, m, n),
            name="reduce_scatter_scatter_reduce",
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
        )(xr)
        return unpad_lanes(out, n_orig)

    # RING
    out, _, _ = pl.pallas_call(
        functools.partial(_ring_rs_kernel, ctx, m, n),
        name="reduce_scatter_ring",
        out_shape=(
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((2, m, n), x.dtype),   # staging (recv)
            jax.ShapeDtypeStruct((2, m, n), x.dtype),   # accum (send)
        ),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),) * 3,
        scratch_shapes=[
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ],
        compiler_params=cparams,
        interpret=interpret,
    )(xr)
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


@register_comm_kernel("reduce_scatter.scatter_reduce",
                      meshes=({"tp": 2}, {"tp": 4}))
def _analysis_scatter_reduce(axis_sizes):
    axis, world = single_axis(axis_sizes)
    m, n = 8, 128
    ctx = ReduceScatterContext(axis=axis, world_size=world)
    return KernelSpec(
        name="reduce_scatter.scatter_reduce",
        body=functools.partial(_scatter_reduce_kernel, ctx, m, n),
        axis_sizes=axis_sizes,
        refs=[RefSpec("x", (world, m, n), jnp.float32),
              RefSpec("out", (m, n), jnp.float32),
              RefSpec("rbuf", (world, m, n), jnp.float32)],
        sems=[SemSpec("local"), SemSpec("send"), SemSpec("recv", (world,))],
    )


@register_comm_kernel("reduce_scatter.ring", meshes=({"tp": 2}, {"tp": 4}))
def _analysis_ring_rs(axis_sizes):
    axis, world = single_axis(axis_sizes)
    if world < 2:
        raise ValueError("ring needs world >= 2")
    m, n = 8, 128
    ctx = ReduceScatterContext(axis=axis, world_size=world)
    return KernelSpec(
        name="reduce_scatter.ring",
        body=functools.partial(_ring_rs_kernel, ctx, m, n),
        axis_sizes=axis_sizes,
        refs=[RefSpec("x", (world, m, n), jnp.float32),
              RefSpec("out", (m, n), jnp.float32),
              RefSpec("staging", (2, m, n), jnp.float32),
              RefSpec("accum", (2, m, n), jnp.float32)],
        sems=[SemSpec("local"), SemSpec("send"), SemSpec("recv", (2,)),
              SemSpec("ack")],
    )
