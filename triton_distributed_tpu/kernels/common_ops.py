"""Barrier and signal building blocks.

Reference: `python/triton_dist/kernels/nvidia/common_ops.py` (441 LoC) —
grid/node-scope barriers (`barrier_on_this_grid:58`,
`barrier_all_intra_node_atomic_cas_block:135`), host-side
`set_signal`/`wait_eq` stream ops (`:242-279`).

On TPU, host-side stream-ordered signals don't exist (XLA owns the
stream); ordering between kernels is expressed by data dependencies.
What remains meaningful — and is provided here — are device barriers
across a mesh axis, used standalone (a pallas_call) or via
`language.barrier_all` inside larger kernels.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu import collective_ids as cids

from triton_distributed_tpu.language import core as dl
from triton_distributed_tpu.utils.platform import default_interpret


def _barrier_kernel(axis, x_ref, o_ref, sem):
    dl.barrier_all(axis)
    cp = pltpu.make_async_copy(x_ref, o_ref, sem)
    cp.start()
    cp.wait()


def barrier_all_on_axis(x, axis: str, *, collective_id: int = cids.BARRIER,
                        interpret: Optional[bool] = None):
    """Block every device on `axis` until all have arrived; returns `x`
    unchanged (the data dependency orders subsequent ops after the
    barrier).  Call inside shard_map.

    Reference: `barrier_all_on_stream` (`common_ops.py:209-240`).
    """
    # Launch-metadata event: semaphore-only (no payload bytes), but
    # doctor/flight views need to see a rank was in a barrier.
    from triton_distributed_tpu.observability import emit_kernel_event
    emit_kernel_event("barrier_all", kind="collective", axis=axis,
                      world=jax.lax.axis_size(axis), shape=x.shape,
                      dtype=x.dtype, hops="none")
    return pl.pallas_call(
        functools.partial(_barrier_kernel, axis),
        name="barrier_all_on_axis",
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id),
        interpret=default_interpret(interpret),
    )(x)


def _broadcast_kernel(axis, world, x_ref, root_ref, o_ref,
                      local_sem, send_sem, recv_sem):
    dl.entry_barrier(axis, world)
    dl.emit_broadcast(axis, world, root_ref[0], x_ref, o_ref,
                      local_sem, send_sem, recv_sem)


def broadcast(x, root, axis: str, world_size: int, *,
              collective_id: int = cids.BROADCAST,
              interpret: Optional[bool] = None):
    """Broadcast `x` from rank `root` to every device on `axis`
    (reference: `libshmem_device.broadcast`; docs/device_language.md).
    Call inside shard_map; `root` may be traced."""
    if world_size <= 1:
        return x
    # Launch-metadata event.  Only the root actually sends (world-1
    # pushes, routed over the ICI torus — hence all_pairs, not the
    # DCN-fabric pairs_direct); rank-symmetric trace-time emission
    # can't know the traced root, so root_only scales the bytes to
    # the expected per-rank share.
    from triton_distributed_tpu.observability import emit_kernel_event
    emit_kernel_event(
        "broadcast", kind="collective", axis=axis, world=world_size,
        shape=x.shape, dtype=x.dtype,
        bytes_moved=(world_size - 1) * x.size * x.dtype.itemsize,
        hops="all_pairs", root_only=True)
    root_arr = jnp.asarray(root, jnp.int32).reshape(1)
    return pl.pallas_call(
        functools.partial(_broadcast_kernel, axis, world_size),
        name="broadcast",
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id),
        interpret=default_interpret(interpret),
    )(x, root_arr)


# ---------------------------------------------------------------------------
# Comm-sanitizer registration (analysis.registry; docs/analysis.md).
# ---------------------------------------------------------------------------

import numpy as _np  # noqa: E402

from triton_distributed_tpu.analysis.registry import (  # noqa: E402
    KernelSpec,
    RefSpec,
    SemSpec,
    register_comm_kernel,
    single_axis,
)


@register_comm_kernel("common_ops.barrier", meshes=({"tp": 2}, {"tp": 4}))
def _analysis_barrier(axis_sizes):
    axis, _ = single_axis(axis_sizes)
    m, n = 8, 128
    return KernelSpec(
        name="common_ops.barrier",
        body=functools.partial(_barrier_kernel, axis),
        axis_sizes=axis_sizes,
        refs=[RefSpec("x", (m, n), jnp.float32),
              RefSpec("o", (m, n), jnp.float32)],
        sems=[SemSpec("sem")],
    )


@register_comm_kernel("common_ops.broadcast", meshes=({"tp": 2}, {"tp": 4}))
def _analysis_broadcast(axis_sizes):
    axis, world = single_axis(axis_sizes)
    m, n = 8, 128
    return KernelSpec(
        name="common_ops.broadcast",
        body=functools.partial(_broadcast_kernel, axis, world),
        axis_sizes=axis_sizes,
        refs=[RefSpec("x", (m, n), jnp.float32),
              # The broadcast root steers the comm pattern: analyze
              # with a concrete root (0) in the SMEM scalar.
              RefSpec("root", (1,), _np.int32, value=_np.zeros(1, _np.int32)),
              RefSpec("o", (m, n), jnp.float32)],
        sems=[SemSpec("local"), SemSpec("send"), SemSpec("recv")],
    )
