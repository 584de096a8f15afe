"""AllGather kernels over ICI.

Reference: `python/triton_dist/kernels/nvidia/allgather.py` (593 LoC) —
copy-engine push/pull full-mesh, 1D/2D rings, NUMA-aware variants, with
topology-driven method auto-selection (`AllGatherMethod`, `:46-72`).

TPU re-design: the copy engine is the ICI DMA engine driven from inside
a Pallas kernel.  Methods:

- ``RING``: bandwidth-optimal ring — each step forwards the
  most-recently-received chunk to the right neighbor while exposing
  per-chunk recv semaphores (the "readiness flags" consumers overlap
  against; reference's per-rank barrier array).
- ``PUSH_ALL``: one-shot push of the local chunk to every peer
  (latency-optimal, maps to the reference's full-mesh push
  `cp_engine_producer_all_gather_full_mesh_push:81` and the
  low-latency allgather family).
- ``BIDIR_RING``: two half-chunks around opposite ring directions,
  doubling link utilisation (reference's 2D/ring variants exploit
  NVLink duplex the same way).
- ``XLA``: `jax.lax.all_gather` — golden reference and DCN fallback.

All entry points run *inside* shard_map over the target mesh axis.
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


class AllGatherMethod(enum.Enum):
    AUTO = "auto"
    RING = "ring"
    BIDIR_RING = "bidir_ring"
    PUSH_ALL = "push_all"
    XLA = "xla"


@dataclasses.dataclass
class AllGatherContext:
    """Per-op config (reference: ctx dataclasses like
    `AllGatherGEMMTensorParallelContext`).

    `axis`: mesh axis to gather over; `world_size` its static size.
    """
    axis: str
    world_size: int
    method: AllGatherMethod = AllGatherMethod.AUTO
    collective_id: int = cids.ALLGATHER
    interpret: Optional[bool] = None
    #: Fault injection (reference `_run_straggler`,
    #: `stress_test_ag_gemm.py:119-121`): (rank, cycles) delays that
    #: rank at kernel entry; `for_correctness` staggers every rank.
    straggler: Optional[tuple] = None
    for_correctness: bool = False

    def resolve_method(self, nbytes_per_shard: int,
                       bus=None) -> AllGatherMethod:
        """Auto-select like `get_auto_all_gather_method`
        (`allgather.py:57-72`), driven by the analytic ICI perf model
        rather than a fixed byte cutoff: one-shot push wins while
        latency-bound, the ring wins once its single-hop transfers
        beat the push's multi-hop link contention.  ``bus``: optional
        feedback bus (`observability.feedback`) whose live link heat
        shifts the crossover; absent/empty/stale ⇒ the static choice,
        bit-identically."""
        if self.method != AllGatherMethod.AUTO:
            return self.method
        from triton_distributed_tpu.kernels.comm_perf_model import (
            one_shot_beats_ring)
        if one_shot_beats_ring(nbytes_per_shard, self.world_size,
                               axis=self.axis, bus=bus,
                               op="all_gather"):
            return AllGatherMethod.PUSH_ALL
        return AllGatherMethod.RING


def create_allgather_context(axis: str, world_size: int,
                             method: AllGatherMethod = AllGatherMethod.AUTO,
                             **kw) -> AllGatherContext:
    return AllGatherContext(axis=axis, world_size=world_size, method=method,
                            **kw)


# ---------------------------------------------------------------------------
# Ring all-gather (bandwidth optimal)
# ---------------------------------------------------------------------------

def _ring_ag_kernel(axis, world, straggler, fc, x_ref, o_ref, local_sem,
                    send_sem, recv_sems):
    my = jax.lax.axis_index(axis)
    right = jax.lax.rem(my + 1, world)

    dl.maybe_straggle(axis, straggler)
    dl.correctness_delay(axis, fc)
    # Entry barrier: the left neighbor must not put into our o_ref
    # while we are still in the previous program (ADVICE r1).
    dl.entry_barrier(axis, world, neighbors_only=True)

    # Place the local shard into slot `my` of the output.
    dl.local_copy(x_ref, o_ref.at[my], local_sem)

    def step(s, _):
        # Forward the chunk that originated at (my - s): at s=0 that is
        # our own shard; afterwards it is the chunk whose arrival we
        # awaited in the previous iteration.
        src_chunk = jax.lax.rem(my - s + 2 * world, world)
        rdma = pltpu.make_async_remote_copy(
            src_ref=o_ref.at[src_chunk],
            dst_ref=o_ref.at[src_chunk],
            send_sem=send_sem,
            recv_sem=recv_sems.at[src_chunk],
            device_id=dl.peer_id(axis, right),
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        rdma.start()
        # Our left neighbor concurrently sends us the chunk that
        # originated at (my - 1 - s); wait on its *own-slot* semaphore so
        # out-of-order arrivals cannot alias (each chunk has a dedicated
        # readiness flag — the reference's per-rank barrier_ptrs).
        exp_chunk = jax.lax.rem(my - 1 - s + 2 * world, world)
        dl.wait_recv(o_ref.at[exp_chunk], recv_sems.at[exp_chunk])
        rdma.wait_send()
        return 0

    jax.lax.fori_loop(0, world - 1, step, 0, unroll=True)


# ---------------------------------------------------------------------------
# One-shot push all-gather (latency optimal)
# ---------------------------------------------------------------------------

def emit_push_allgather(axis, world, x_ref, o_ref, local_sem, send_sem,
                        recv_sems, *, barrier: bool = True):
    """One-shot push AG usable from inside larger kernels: the local
    shard ``x_ref`` lands in ``o_ref[my]`` and is pushed to every
    peer's same slot (1 hop, all peers concurrent).  ``recv_sems``
    must have shape (world,).  Shared by the standalone PUSH_ALL
    collective and the fused low-latency overlap kernels."""
    my = jax.lax.axis_index(axis)
    if barrier:
        dl.entry_barrier(axis, world)  # every peer puts into our o_ref
    dl.local_copy(x_ref, o_ref.at[my], local_sem)

    def send(i, _):
        peer = jax.lax.rem(my + i, world)
        pltpu.make_async_remote_copy(
            src_ref=o_ref.at[my],
            dst_ref=o_ref.at[my],
            send_sem=send_sem,
            recv_sem=recv_sems.at[my],
            device_id=dl.peer_id(axis, peer),
            device_id_type=pltpu.DeviceIdType.MESH,
        ).start()
        return 0

    jax.lax.fori_loop(1, world, send, 0, unroll=True)

    # Wait for every peer's shard to land, then drain our send sem.
    def recv(i, _):
        peer = jax.lax.rem(my + i, world)
        dl.wait_recv(o_ref.at[peer], recv_sems.at[peer])
        return 0

    jax.lax.fori_loop(1, world, recv, 0, unroll=True)
    # world-1 sends of x_ref bytes each.
    def drain(i, _):
        dl.wait_send(o_ref.at[my], send_sem)
        return 0
    jax.lax.fori_loop(1, world, drain, 0, unroll=True)


def _push_all_ag_kernel(axis, world, straggler, fc, x_ref, o_ref,
                        local_sem, send_sem, recv_sems):
    dl.maybe_straggle(axis, straggler)
    dl.correctness_delay(axis, fc)
    emit_push_allgather(axis, world, x_ref, o_ref, local_sem, send_sem,
                        recv_sems)


# ---------------------------------------------------------------------------
# Bidirectional ring (two half-width rings in opposite directions)
# ---------------------------------------------------------------------------

def _bidir_ring_ag_kernel(axis, world, straggler, fc, x_ref, o_ref,
                          local_sem, send_sems, recv_sems):
    # o_ref shape: (world, 2, half_rows, cols); halves travel opposite
    # directions. recv_sems shape (world, 2).
    my = jax.lax.axis_index(axis)
    right = jax.lax.rem(my + 1, world)
    left = jax.lax.rem(my - 1 + world, world)

    dl.maybe_straggle(axis, straggler)
    dl.correctness_delay(axis, fc)
    dl.entry_barrier(axis, world, neighbors_only=True)
    dl.local_copy(x_ref, o_ref.at[my], local_sem)

    def step(s, _):
        fwd_chunk = jax.lax.rem(my - s + 2 * world, world)
        bwd_chunk = jax.lax.rem(my + s, world)
        r0 = pltpu.make_async_remote_copy(
            src_ref=o_ref.at[fwd_chunk, 0],
            dst_ref=o_ref.at[fwd_chunk, 0],
            send_sem=send_sems.at[0],
            recv_sem=recv_sems.at[fwd_chunk, 0],
            device_id=dl.peer_id(axis, right),
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        r1 = pltpu.make_async_remote_copy(
            src_ref=o_ref.at[bwd_chunk, 1],
            dst_ref=o_ref.at[bwd_chunk, 1],
            send_sem=send_sems.at[1],
            recv_sem=recv_sems.at[bwd_chunk, 1],
            device_id=dl.peer_id(axis, left),
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        r0.start()
        r1.start()
        exp_fwd = jax.lax.rem(my - 1 - s + 2 * world, world)
        exp_bwd = jax.lax.rem(my + 1 + s, world)
        dl.wait_recv(o_ref.at[exp_fwd, 0], recv_sems.at[exp_fwd, 0])
        dl.wait_recv(o_ref.at[exp_bwd, 1], recv_sems.at[exp_bwd, 1])
        r0.wait_send()
        r1.wait_send()
        return 0

    jax.lax.fori_loop(0, world - 1, step, 0, unroll=True)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def all_gather(x, ctx: AllGatherContext):
    """Gather shards along axis 0 across `ctx.axis`.

    Input: per-device shard of shape (m, n) (inside shard_map).
    Output: (world * m, n).
    """
    world = ctx.world_size
    method = ctx.resolve_method(x.size * x.dtype.itemsize)

    # Launch-metadata event (fires once per traced specialization).
    # The method name IS the ICI schedule, so the hop-pattern
    # annotation link attribution needs derives from it
    # (instrument.hops_for_method): ring/bidir_ring push to the ±1
    # neighbors, push_all DMAs a chunk straight to each peer.
    from triton_distributed_tpu.observability import record_collective
    record_collective("all_gather", axis=ctx.axis, world=world,
                      method=method, shape=x.shape, dtype=x.dtype,
                      payload_bytes=x.size * x.dtype.itemsize)

    if method == AllGatherMethod.XLA:
        return jax.lax.all_gather(x, ctx.axis, tiled=True)

    # Lane-align the payload columns (Mosaic memref_slice rule — see
    # `matmul.pad_lanes`); sliced back on exit.
    x, n_orig = pad_lanes(x)
    m, n = x.shape

    interpret = default_interpret(ctx.interpret)
    cparams = comm_compiler_params(ctx.collective_id, world)

    if method == AllGatherMethod.BIDIR_RING and m % 2 == 0 and world > 2:
        xr = x.reshape(2, m // 2, n)
        out = pl.pallas_call(
            functools.partial(_bidir_ring_ag_kernel, ctx.axis, world,
                              ctx.straggler, ctx.for_correctness),
            name="all_gather_bidir_ring",
            out_shape=jax.ShapeDtypeStruct((world, 2, m // 2, n), x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((world, 2)),
            ],
            compiler_params=cparams,
            interpret=interpret,
        )(xr)
        return unpad_lanes(out.reshape(world * m, n), n_orig)

    kernel = (_push_all_ag_kernel if method == AllGatherMethod.PUSH_ALL
              else _ring_ag_kernel)
    out = pl.pallas_call(
        functools.partial(kernel, ctx.axis, world, ctx.straggler,
                          ctx.for_correctness),
        name=("all_gather_push_all"
              if method == AllGatherMethod.PUSH_ALL
              else "all_gather_ring"),
        out_shape=jax.ShapeDtypeStruct((world, m, n), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((world,)),
        ],
        compiler_params=cparams,
        interpret=interpret,
    )(x)
    return unpad_lanes(out.reshape(world * m, n), n_orig)


# ---------------------------------------------------------------------------
# Comm-sanitizer registration (analysis.registry; docs/analysis.md).
# Specs mirror the pallas_call sites above — a drifted spec fails the
# `python -m triton_distributed_tpu.analysis` sweep loudly.
# ---------------------------------------------------------------------------

from triton_distributed_tpu.analysis.registry import (  # noqa: E402
    KernelSpec,
    RefSpec,
    SemSpec,
    register_comm_kernel,
    single_axis,
)


@register_comm_kernel("allgather.ring", meshes=({"tp": 2}, {"tp": 4}))
def _analysis_ring(axis_sizes):
    axis, world = single_axis(axis_sizes)
    m, n = 8, 128
    return KernelSpec(
        name="allgather.ring",
        body=functools.partial(_ring_ag_kernel, axis, world, None, False),
        axis_sizes=axis_sizes,
        refs=[RefSpec("x", (m, n), jnp.float32),
              RefSpec("o", (world, m, n), jnp.float32)],
        sems=[SemSpec("local"), SemSpec("send"), SemSpec("recv", (world,))],
    )


@register_comm_kernel("allgather.push_all", meshes=({"tp": 2}, {"tp": 4}))
def _analysis_push_all(axis_sizes):
    axis, world = single_axis(axis_sizes)
    m, n = 8, 128
    return KernelSpec(
        name="allgather.push_all",
        body=functools.partial(_push_all_ag_kernel, axis, world, None,
                               False),
        axis_sizes=axis_sizes,
        refs=[RefSpec("x", (m, n), jnp.float32),
              RefSpec("o", (world, m, n), jnp.float32)],
        sems=[SemSpec("local"), SemSpec("send"), SemSpec("recv", (world,))],
    )


@register_comm_kernel("allgather.bidir_ring", meshes=({"tp": 4},))
def _analysis_bidir(axis_sizes):
    axis, world = single_axis(axis_sizes)
    if world <= 2:
        raise ValueError("bidir ring needs world > 2")
    m, n = 8, 128
    return KernelSpec(
        name="allgather.bidir_ring",
        body=functools.partial(_bidir_ring_ag_kernel, axis, world, None,
                               False),
        axis_sizes=axis_sizes,
        refs=[RefSpec("x", (2, m // 2, n), jnp.float32),
              RefSpec("o", (world, 2, m // 2, n), jnp.float32)],
        sems=[SemSpec("local"), SemSpec("send", (2,)),
              SemSpec("recv", (world, 2))],
    )
