"""Low-latency AllToAll for EP MoE dispatch/combine.

Reference: `python/triton_dist/kernels/nvidia/low_latency_all_to_all.py`
(279 LoC) — the DeepEP-equivalent single kernel (`all_to_all_kernel:36`):
per-peer `putmem_nbi_block` of tokens + splits, `fence`, `signal_op`,
`signal_wait_until`, double-buffered by `call_count` parity to avoid
resets between calls.  Headline number: 137 µs dispatch @ 32 ranks,
128 tok/rank (BASELINE.md).

TPU re-design: one Pallas kernel; each device pushes its per-peer
token block and split counts with two one-sided DMAs per peer.  The
recv-DMA semaphore *is* the arrival signal (every TPU remote copy is a
put-with-signal), so no separate fence/signal round is needed — one
network traversal total, and no phase/parity bookkeeping: Pallas DMA
semaphores are allocated per call, so calls cannot alias (the hazard
the reference's `call_count % 2` double-buffering guards against).

Tokens are exchanged at fixed capacity (static shapes for XLA); true
counts ride along and downstream consumers mask.  `split_send` must be
grouped by destination rank (host-side preprocess, as in the
reference's layer: `ep_a2a_layer.py:118-138`).
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

from triton_distributed_tpu.language import core as dl
from triton_distributed_tpu.utils.platform import (
    comm_compiler_params,
    default_interpret,
)


@dataclasses.dataclass
class AllToAllContext:
    """Reference analogue: `AllToAllContext`
    (`low_latency_all_to_all.py:125`): world size, token capacity,
    hidden size, dtypes (fp8 scale support via the optional second
    payload)."""

    axis: str
    world_size: int
    max_tokens_per_rank: int
    hidden: int
    collective_id: int = cids.ALL_TO_ALL
    #: "auto" (the Pallas one-sided-put kernel) or "xla"
    #: (`jax.lax.all_to_all` — golden reference, and the only method
    #: that can cross PROCESS boundaries, e.g. the DCN-stage of a
    #: multi-host launch or interpret-mode cross-process tests).
    method: str = "auto"
    # Fault injection — see AllGatherGEMMContext.
    straggler: Optional[tuple] = None
    for_correctness: bool = False
    interpret: Optional[bool] = None


def create_all_to_all_context(axis: str, world_size: int,
                              max_tokens_per_rank: int, hidden: int, **kw):
    return AllToAllContext(axis=axis, world_size=world_size,
                           max_tokens_per_rank=max_tokens_per_rank,
                           hidden=hidden, **kw)


def _a2a_kernel(ctx: AllToAllContext, has_scale,
                send_ref, counts_ref, scale_ref,
                recv_ref, rcounts_ref, rscale_ref,
                local_sem, send_sem, tok_sems, cnt_sems, scl_sems):
    world = ctx.world_size
    my = jax.lax.axis_index(ctx.axis)
    dl.maybe_straggle(ctx.axis, ctx.straggler)
    dl.entry_barrier(ctx.axis, world)  # every peer puts into recv bufs
    dl.correctness_delay(ctx.axis, ctx.for_correctness)

    # Local slice: my tokens destined to myself.
    dl.local_copy(send_ref.at[my], recv_ref.at[my], local_sem)
    dl.local_copy(counts_ref.at[my], rcounts_ref.at[my], local_sem)
    if has_scale:
        dl.local_copy(scale_ref.at[my], rscale_ref.at[my], local_sem)

    # One put per (peer, payload): tokens, counts[, scales].
    for i in range(1, world):
        peer = jax.lax.rem(my + i, world)
        pltpu.make_async_remote_copy(
            src_ref=send_ref.at[peer], dst_ref=recv_ref.at[my],
            send_sem=send_sem, recv_sem=tok_sems.at[my],
            device_id=dl.peer_id(ctx.axis, peer),
            device_id_type=pltpu.DeviceIdType.MESH).start()
        pltpu.make_async_remote_copy(
            src_ref=counts_ref.at[peer], dst_ref=rcounts_ref.at[my],
            send_sem=send_sem, recv_sem=cnt_sems.at[my],
            device_id=dl.peer_id(ctx.axis, peer),
            device_id_type=pltpu.DeviceIdType.MESH).start()
        if has_scale:
            pltpu.make_async_remote_copy(
                src_ref=scale_ref.at[peer], dst_ref=rscale_ref.at[my],
                send_sem=send_sem, recv_sem=scl_sems.at[my],
                device_id=dl.peer_id(ctx.axis, peer),
                device_id_type=pltpu.DeviceIdType.MESH).start()

    # Arrival waits (the reference's signal_wait_until on per-src flags).
    for i in range(1, world):
        peer = jax.lax.rem(my + i, world)
        dl.wait_recv(recv_ref.at[peer], tok_sems.at[peer])
        dl.wait_recv(rcounts_ref.at[peer], cnt_sems.at[peer])
        if has_scale:
            dl.wait_recv(rscale_ref.at[peer], scl_sems.at[peer])

    # Drain send side.
    for i in range(1, world):
        peer = jax.lax.rem(my + i, world)
        dl.wait_send(send_ref.at[peer], send_sem)
        dl.wait_send(counts_ref.at[peer], send_sem)
        if has_scale:
            dl.wait_send(scale_ref.at[peer], send_sem)


def fast_all_to_all(send_tokens, send_counts, ctx: AllToAllContext,
                    send_scales=None):
    """Exchange capacity-padded token blocks between all EP ranks.

    Call inside shard_map over `ctx.axis`.

    send_tokens: (world, cap, hidden) — block p holds the tokens this
      rank routes to rank p (padded to cap).
    send_counts: (world, 1) int32 — true token count per block (2D for
      TPU layout).
    send_scales: optional (world, cap, n_scales) — fp8 per-token scales
      (reference's `putmem_signal_nbi_block` scale payload).

    Returns (recv_tokens, recv_counts[, recv_scales]): block p of
    recv_tokens holds what rank p sent here.
    """
    world = ctx.world_size
    cap, hidden = send_tokens.shape[1], send_tokens.shape[2]
    has_scale = send_scales is not None

    # Launch-metadata event: one capacity-padded block DMAed straight
    # to each peer (dimension-ordered over the torus).
    from triton_distributed_tpu.observability import record_collective
    record_collective(
        "all_to_all", axis=ctx.axis, world=world, method=ctx.method,
        shape=tuple(send_tokens.shape), dtype=send_tokens.dtype,
        payload_bytes=cap * hidden * send_tokens.dtype.itemsize,
        hops="all_pairs", scaled=has_scale)

    if ctx.method == "xla":
        a2a = functools.partial(jax.lax.all_to_all, axis_name=ctx.axis,
                                split_axis=0, concat_axis=0,
                                tiled=False)
        rt = a2a(send_tokens)
        rc = a2a(send_counts.astype(jnp.int32))
        if has_scale:
            return rt, rc, a2a(send_scales)
        return rt, rc

    # Mosaic DMA slices need lane-dim (last-dim) alignment to 128;
    # narrow payloads (counts (world, 1), scale slots) are padded here
    # and sliced back below — interpret mode doesn't care, hardware
    # does.
    cnt_w = 128
    send_counts = jnp.pad(send_counts.astype(jnp.int32),
                          ((0, 0), (0, cnt_w - send_counts.shape[1])))
    ns = ns_pad = 0
    if has_scale:
        ns = send_scales.shape[-1]
        ns_pad = -ns % 128
        if ns_pad:
            send_scales = jnp.pad(send_scales,
                                  ((0, 0), (0, 0), (0, ns_pad)))

    out_shapes = [
        jax.ShapeDtypeStruct((world, cap, hidden), send_tokens.dtype),
        jax.ShapeDtypeStruct((world, cnt_w), jnp.int32),
    ]
    scratch = [
        pltpu.SemaphoreType.DMA(()),
        pltpu.SemaphoreType.DMA(()),
        pltpu.SemaphoreType.DMA((world,)),
        pltpu.SemaphoreType.DMA((world,)),
        pltpu.SemaphoreType.DMA((world,)),
    ]
    operands = [send_tokens, send_counts]
    if has_scale:
        out_shapes.append(jax.ShapeDtypeStruct(send_scales.shape,
                                               send_scales.dtype))
        operands.append(send_scales)

    kernel = functools.partial(_a2a_kernel, ctx, has_scale)

    def body(send_ref, counts_ref, *rest):
        if has_scale:
            scale_ref = rest[0]
            outs = rest[1:4]
            sems = rest[4:]
        else:
            scale_ref = None
            outs = rest[0:2] + (None,)
            sems = rest[2:]
        kernel(send_ref, counts_ref, scale_ref, *outs, *sems)

    result = pl.pallas_call(
        body,
        name="fast_all_to_all",
        out_shape=tuple(out_shapes),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(operands),
        out_specs=tuple(pl.BlockSpec(memory_space=pl.ANY)
                        for _ in out_shapes),
        scratch_shapes=scratch,
        compiler_params=comm_compiler_params(ctx.collective_id, world),
        interpret=default_interpret(ctx.interpret),
    )(*operands)

    rcounts = result[1][:, :1]
    if has_scale:
        rscales = result[2][..., :ns] if ns_pad else result[2]
        return result[0], rcounts, rscales
    return result[0], rcounts


def all_to_all_post_process(recv_tokens, recv_counts, cap: int):
    """Compact received blocks into a dense prefix (reference
    `all_to_all_post_process:260`).  Static output size world*cap;
    rows beyond the true total are zero.  Returns (tokens, total)."""
    world = recv_tokens.shape[0]
    hidden = recv_tokens.shape[2]
    counts = recv_counts.reshape(world)
    flat = recv_tokens.reshape(world * cap, hidden)
    block = jax.lax.broadcasted_iota(jnp.int32, (world, cap), 0)
    within = jax.lax.broadcasted_iota(jnp.int32, (world, cap), 1)
    valid = (within < counts[:, None]).reshape(-1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(counts)[:-1]])
    dest = (offsets[block] + within).reshape(-1)
    # Scatter valid rows to their dense position; invalid rows get an
    # out-of-bounds index and are dropped.
    out = jnp.zeros_like(flat).at[
        jnp.where(valid, dest, world * cap)
    ].set(flat, mode="drop")
    return out, counts.sum()


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


def _a2a_spec(axis_sizes, has_scale: bool):
    axis, world = single_axis(axis_sizes)
    cap, hidden, ns = 8, 128, 128
    ctx = AllToAllContext(axis=axis, world_size=world,
                          max_tokens_per_rank=cap, hidden=hidden)
    refs = [RefSpec("send", (world, cap, hidden), jnp.bfloat16),
            RefSpec("counts", (world, 128), jnp.int32)]
    if has_scale:
        refs.append(RefSpec("scale", (world, cap, ns), jnp.float32))
    refs += [RefSpec("recv", (world, cap, hidden), jnp.bfloat16),
             RefSpec("rcounts", (world, 128), jnp.int32)]
    if has_scale:
        refs.append(RefSpec("rscale", (world, cap, ns), jnp.float32))

    if has_scale:
        def body(send, counts, scale, recv, rcounts, rscale, *sems):
            _a2a_kernel(ctx, True, send, counts, scale, recv, rcounts,
                        rscale, *sems)
    else:
        def body(send, counts, recv, rcounts, *sems):
            _a2a_kernel(ctx, False, send, counts, None, recv, rcounts,
                        None, *sems)

    return KernelSpec(
        name=f"all_to_all.{'scaled' if has_scale else 'plain'}",
        body=body,
        axis_sizes=axis_sizes,
        refs=refs,
        sems=[SemSpec("local"), SemSpec("send"), SemSpec("tok", (world,)),
              SemSpec("cnt", (world,)), SemSpec("scl", (world,))],
    )


@register_comm_kernel("all_to_all.plain", meshes=({"ep": 2}, {"ep": 4}))
def _analysis_a2a(axis_sizes):
    return _a2a_spec(axis_sizes, has_scale=False)


@register_comm_kernel("all_to_all.scaled", meshes=({"ep": 4},))
def _analysis_a2a_scaled(axis_sizes):
    return _a2a_spec(axis_sizes, has_scale=True)
