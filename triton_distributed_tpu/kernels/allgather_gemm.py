"""Fused AllGather-GEMM — the flagship TP overlap op.

Reference: `python/triton_dist/kernels/nvidia/allgather_gemm.py` (744
LoC): a copy-engine/NVSHMEM producer streams remote A-shards into a
symmetric workspace while a persistent GEMM consumer `dl.wait`s
per-rank readiness flags and consumes tiles in rank-swizzled order
(`kernel_consumer_gemm_persistent:146`, swizzle `:211-216`, wait
`:223-224`).

TPU re-design (one Pallas kernel per device, no producer/consumer
split): the ICI DMA engine *is* the copy engine, so a single kernel

  1. forwards the freshest A-chunk to the right neighbor (ring), and
  2. feeds the chunk it already owns into a software-pipelined MXU
     matmul (`emit_matmul`),

so step s computes chunk (rank - s) while chunk (rank - s - 1) is in
flight — the same "consume in arrival order, start from own rank"
swizzle as the reference, expressed as loop order instead of
threadblock remapping.  Per-chunk DMA semaphores are the readiness
flags (`dl.wait(barrier_ptr + rank)` ↔ `wait_recv(recv_sems[chunk])`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu import collective_ids as cids

from triton_distributed_tpu.kernels.matmul import (
    MatmulConfig,
    emit_chunked_matmul,
    emit_matmul,
    pad_contraction_lanes,
    round_up_rows,
)
from triton_distributed_tpu.language import core as dl
from triton_distributed_tpu.utils.platform import (
    comm_compiler_params,
    default_interpret,
)


@dataclasses.dataclass(frozen=True)
class AllGatherGEMMContext:
    """Reference analogue: `AllGatherGEMMTensorParallelContext`
    (`allgather_gemm.py:404-487`) minus the symmetric-buffer plumbing
    (Pallas buffers are allocated per call by XLA; reuse across calls
    comes from jit caching, the role CUDA graphs play in the
    reference).

    ``method``: "auto" | "fused" | "ll" | "xla" — the reference's
    method auto-select (`get_auto_all_gather_method`).  "auto" picks
    "xla" when there is no communication to overlap (world_size == 1 —
    the XLA matmul already runs at ~96% MFU, there is nothing to win),
    the low-latency one-shot path ("ll") in the decode regime (few
    gathered rows: latency-bound, B-streaming-dominated — the
    reference's `low_latency_allgather.py` family), and the fused
    ring kernel otherwise."""

    axis: str
    world_size: int
    gemm: MatmulConfig = dataclasses.field(default_factory=MatmulConfig)
    method: str = "auto"
    collective_id: int = cids.AG_GEMM
    # Fault injection (stress suite): (rank, cycles) delays that rank
    # at kernel entry; for_correctness staggers every rank's comm
    # phase to widen race windows (reference
    # `allgather_gemm.py:506-508`, `stress_test_ag_gemm.py:119-121`).
    straggler: Optional[Tuple[int, int]] = None
    for_correctness: bool = False
    interpret: Optional[bool] = None
    #: Collective id for the training dual (`ag_gemm_diff`'s backward
    #: gemm_rs).  None → the registry default; programs with several
    #: CONCURRENT fused-training instances must give each its own
    #: (same invariant as collective_id itself).
    bwd_collective_id: Optional[int] = None

    #: Shape-only fallback for "auto" when K/N are unknown: one-shot
    #: ll below this many (padded) gathered rows — the decode regime.
    LL_MAX_GATHERED_ROWS = 256

    def resolve_method(self, m: int, dtype, k: Optional[int] = None,
                       n: Optional[int] = None, bus=None) -> str:
        """Pick xla / ll / fused.  With K and N known, the choice is
        model-driven with hysteresis (`choose_ll_or_fused`); otherwise
        the shape-only decode threshold decides.  ``bus``: optional
        feedback bus (`observability.feedback`) whose live link heat
        shifts the crossover — under contention from a concurrent
        collective on the axis the overlap-friendly schedule wins
        earlier; absent/empty/stale ⇒ the static choice."""
        assert self.method in ("auto", "fused", "ll", "xla"), self.method
        if self.method != "auto":
            return self.method
        world = self.world_size
        if world <= 1:
            return "xla"
        mp = round_up_rows(m, dtype)
        if k is None or n is None:
            return ("ll" if world * mp <= self.LL_MAX_GATHERED_ROWS
                    else "fused")
        from triton_distributed_tpu.kernels.comm_perf_model import (
            choose_ll_or_fused)
        return choose_ll_or_fused(mp * k * jnp.dtype(dtype).itemsize,
                                  mp, n, k, world, dtype,
                                  axis=self.axis, bus=bus,
                                  op="ag_gemm")


def create_ag_gemm_context(axis: str, world_size: int, **kw) -> AllGatherGEMMContext:
    return AllGatherGEMMContext(axis=axis, world_size=world_size, **kw)


def _emit_ag_ring(ctx: AllGatherGEMMContext, emit_chunk,
                  x_ref, gathered_ref, local_sem, send_sem, recv_sems):
    """The fused-AG ring schedule, shared by every consumer variant
    (bf16 matmul, int8 W8A8): forward the freshest chunk to the right
    neighbor while ``emit_chunk(chunk)`` does this step's MXU work on
    the chunk already held."""
    world = ctx.world_size
    my = jax.lax.axis_index(ctx.axis)
    right = jax.lax.rem(my + 1, world)

    dl.maybe_straggle(ctx.axis, ctx.straggler)
    # Entry barrier with ring neighbors before they put into
    # gathered_ref (ADVICE r1: reused output buffers may alias the
    # previous program's live memory on a slow device).
    dl.entry_barrier(ctx.axis, world, neighbors_only=True)
    dl.correctness_delay(ctx.axis, ctx.for_correctness)
    dl.local_copy(x_ref, gathered_ref.at[my], local_sem)

    # Python loop: `world` is static, so each step is unrolled and the
    # Mosaic scheduler can overlap the RDMA of step s with the matmul
    # pipeline of step s.
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
        # MXU work for the chunk we already hold overlaps the DMA.
        emit_chunk(chunk)
        if rdma is not None:
            exp = jax.lax.rem(my - s - 1 + 2 * world, world)
            dl.wait_recv(gathered_ref.at[exp], recv_sems.at[exp])
            rdma.wait_send()


def _ag_gemm_fused_kernel(ctx: AllGatherGEMMContext, m, n, k,
                          x_ref, b_ref, gathered_ref, out_ref,
                          local_sem, send_sem, recv_sems):
    def emit_chunk(chunk):
        emit_matmul(gathered_ref.at[chunk], b_ref, out_ref.at[chunk],
                    m=m, n=n, k=k, config=ctx.gemm)

    _emit_ag_ring(ctx, emit_chunk, x_ref, gathered_ref, local_sem,
                  send_sem, recv_sems)


def _ag_gemm_ll_kernel(ctx: AllGatherGEMMContext, mp, n, k,
                       x_ref, b_ref, gathered_ref, out_ref,
                       local_sem, send_sem, recv_sems):
    """Low-latency variant: one-shot push AG (1 hop, all peers
    concurrent — reference `low_latency_allgather.py:48-217`) and a
    single chunked matmul that streams B exactly once.

    Schedule (``weights_ahead_of_gather`` in the launch event): every B
    block needs every peer's rows, so the matmul cannot start before
    the gather — but its weight stream can.  The entry barrier's
    SIGNAL (`dl.barrier_all_signal`) goes out first, the first
    B blocks are put in flight, and only then does the kernel talk to
    its peers (local copy, barrier WAIT, push, arrivals): the gather's
    few microseconds pass while HBM is already streaming, and the
    sends are drained after the matmul, not before it.  At decode
    shapes a call is 10-60 us of weight streaming, so a serial gather
    in front of an idle stream cost a fifth of it (PERF.md section 5,
    PR 34)."""
    world = ctx.world_size
    my = jax.lax.axis_index(ctx.axis)
    dl.maybe_straggle(ctx.axis, ctx.straggler)
    dl.barrier_all_signal(ctx.axis)

    def gather():
        dl.correctness_delay(ctx.axis, ctx.for_correctness)
        own = pltpu.make_async_copy(x_ref, gathered_ref.at[my], local_sem)
        own.start()
        dl.barrier_all_wait(ctx.axis)  # peers' gathered_ref
        for i in range(1, world):
            pltpu.make_async_remote_copy(
                src_ref=x_ref,
                dst_ref=gathered_ref.at[my],
                send_sem=send_sem,
                recv_sem=recv_sems.at[my],
                device_id=dl.peer_id(ctx.axis, jax.lax.rem(my + i, world)),
                device_id_type=pltpu.DeviceIdType.MESH,
            ).start()
        own.wait()
        for i in range(1, world):
            peer = jax.lax.rem(my + i, world)
            dl.wait_recv(gathered_ref.at[peer], recv_sems.at[peer])

    emit_chunked_matmul(gathered_ref, b_ref, out_ref, chunks=world,
                        mc=mp, n=n, k=k, config=ctx.gemm,
                        while_prefetching=gather)
    for _ in range(1, world):
        dl.wait_send(x_ref, send_sem)


def _ag_gemm_2d(a_shard, b, hctx, return_gathered: bool):
    """Two-level (dcn × ici) fused AG-GEMM: DCN slice-chunks are
    pipelined through the fused ICI ring kernel.

    Reference: the internode AG-GEMM path — rank-swizzled tile order
    for nnodes > 1 (`allgather_gemm.py:211-216`), a dedicated
    internode AG stream feeding the persistent GEMM
    (`allgather_gemm.py:430,471-481`,
    `cp_engine_producer_all_gather_inter_node`, `allgather.py:293-472`).

    TPU re-design: Pallas cannot issue one-sided DMA across DCN, so
    the DCN stage is a host-composed ring of `lax.ppermute` steps —
    XLA's latency-hiding scheduler runs the (slow) DCN transfer of
    slice-chunk s+1 concurrently with the Pallas kernel (ICI ring +
    MXU) consuming slice-chunk s.  Each DCN hop carries only this
    device's (m, k) rows, the per-slice minimum, and the ICI ring
    starts on the *local* slice's rows at step 0 — no wait on any DCN
    traffic to begin computing, the same "start from own rank" swizzle
    as the single-axis ring, lifted one level up.
    """
    dcn = hctx.dcn_size
    ici_ctx = hctx._ag_gemm_ctx()
    if dcn <= 1:
        return ag_gemm(a_shard, b, ici_ctx, return_gathered)

    m, k = a_shard.shape
    n = b.shape[1]
    mi = hctx.ici_size * m          # rows per slice after the ICI AG
    my_d = jax.lax.axis_index(hctx.dcn_axis)
    perm = [(i, (i + 1) % dcn) for i in range(dcn)]

    cur = a_shard
    blocks = []
    for s in range(dcn):
        # Start the DCN hop BEFORE the Pallas call so the scheduler
        # can overlap the collective-permute with the fused kernel.
        nxt = (jax.lax.ppermute(cur, hctx.dcn_axis, perm)
               if s < dcn - 1 else None)
        blocks.append(ag_gemm(cur, b, ici_ctx, return_gathered))
        cur = nxt

    # Step s held slice (my_d - s): place each block at its global
    # slot (global rank g = dcn_index * ici_size + ici_index).
    out_full = jnp.zeros((dcn, mi, n), blocks[0][0].dtype
                         if return_gathered else blocks[0].dtype)
    g_full = jnp.zeros((dcn, mi, k), a_shard.dtype) if return_gathered \
        else None
    for s, res in enumerate(blocks):
        src = jax.lax.rem(my_d - s + dcn, dcn)
        o, g = res if return_gathered else (res, None)
        out_full = jax.lax.dynamic_update_slice(
            out_full, o[None], (src, 0, 0))
        if return_gathered:
            g_full = jax.lax.dynamic_update_slice(
                g_full, g[None], (src, 0, 0))
    out = out_full.reshape(dcn * mi, n)
    if return_gathered:
        return out, g_full.reshape(dcn * mi, k)
    return out


def ag_gemm(a_shard, b, ctx, return_gathered: bool = False):
    """C = all_gather(a, axis) @ b, overlapped.  Call inside shard_map.

    a_shard: (m_local, k) — row shard of A over `ctx.axis`.
    b:       (k, n_local) — this rank's column shard of B (weights).
    Returns (world*m_local, n_local), and optionally gathered A
    (the reference's `copy_to_local` path, `allgather_gemm.py:573`).

    Any m is supported on the fused paths: rows are padded to the
    Mosaic sublane multiple inside the op and sliced back out — decode
    shapes (m = 1..8) run the Pallas "ll" path, not an XLA fallback.

    ``ctx`` may be an `AllGatherGEMMContext` (single axis), a
    `HierarchicalContext` (two-level dcn × ici — the reference's
    internode AG-GEMM, `allgather_gemm.py:430-481`), or a
    `TorusContext` (both ICI torus axes at once, `kernels/torus.py`).
    """
    from triton_distributed_tpu.kernels.hierarchical import (
        HierarchicalContext)
    from triton_distributed_tpu.kernels.torus import (
        TorusContext, ag_gemm_torus)
    if isinstance(ctx, HierarchicalContext):
        return _ag_gemm_2d(a_shard, b, ctx, return_gathered)
    if isinstance(ctx, TorusContext):
        return ag_gemm_torus(a_shard, b, ctx, return_gathered)

    world = ctx.world_size
    m, k = a_shard.shape
    k2, n = b.shape
    assert k == k2, (a_shard.shape, b.shape)

    method = ctx.resolve_method(m, a_shard.dtype, k=k, n=n)

    # Launch-metadata event (fires once per traced specialization).
    # The hop pattern link attribution needs derives from the method
    # (instrument.hops_for_method): the fused ring circulates A-chunks
    # over +1 neighbor links (overlapped with the GEMM); the ll method
    # one-shot-pushes the shard to every peer up front.
    from triton_distributed_tpu.observability import record_overlap_gemm
    record_overlap_gemm("ag_gemm", axis=ctx.axis, world=world,
                        method=method, m=m, n=n, k=k,
                        dtype=a_shard.dtype, config=ctx.gemm)

    def xla_dot(a_full):
        return jnp.dot(a_full, b, preferred_element_type=jnp.float32
                       ).astype(a_shard.dtype)

    if method == "xla" and world > 1:
        a_full = jax.lax.all_gather(a_shard, ctx.axis, tiled=True)
        out = xla_dot(a_full)
        return (out, a_full) if return_gathered else out

    if world <= 1:
        # Single device: no comm.  `method` is "xla" here unless a
        # fused path was requested explicitly (e.g. by the autotuner
        # with a tuned config) — the XLA dot needs no tuning to be
        # fast.
        if method in ("fused", "ll"):
            from triton_distributed_tpu.kernels.matmul import matmul
            out = matmul(a_shard, b, config=ctx.gemm,
                         interpret=ctx.interpret)
        else:
            out = xla_dot(a_shard)
        return (out, a_shard) if return_gathered else out

    # Pad rows to the Mosaic sublane multiple (sliced back below).
    mp = round_up_rows(m, a_shard.dtype)
    a_p = (a_shard if mp == m
           else jnp.pad(a_shard, ((0, mp - m), (0, 0))))
    # Lane-align K (see `matmul.pad_contraction_lanes`); gathered A
    # is sliced back below.
    k_orig = k
    a_p, b, k = pad_contraction_lanes(a_p, b)
    kp_pad = k != k_orig

    kernel = (_ag_gemm_ll_kernel if method == "ll"
              else _ag_gemm_fused_kernel)
    gathered, out = pl.pallas_call(
        functools.partial(kernel, ctx, mp, n, k),
        name="ag_gemm_ll" if method == "ll" else "ag_gemm_ring",
        out_shape=(
            jax.ShapeDtypeStruct((world, mp, k), a_shard.dtype),
            jax.ShapeDtypeStruct((world, mp, n), a_shard.dtype),
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
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
            flops=2 * world * mp * n * k,
            bytes_accessed=(world * mp * k + k * n) * a_shard.dtype.itemsize
            + world * mp * n * a_shard.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=default_interpret(ctx.interpret),
    )(a_p, b)

    if mp != m:
        out = out[:, :m]
    out = out.reshape(world * m, n)
    if return_gathered:
        g = gathered[:, :m] if mp != m else gathered
        if kp_pad:
            g = g[:, :, :k_orig]
        return out, g.reshape(world * m, k_orig)
    return out


def _ag_gemm_w8a8_kernel(ctx: AllGatherGEMMContext, cfg, m, n, k,
                         x_ref, b_ref, sa_ref, sb_ref,
                         gathered_ref, out_ref,
                         local_sem, send_sem, recv_sems):
    """Fused ring AG-GEMM over int8 activations: the same ring
    schedule (`_emit_ag_ring`), but each forwarded chunk is int8 —
    HALF the ICI bytes of the bf16 ring — and each held chunk feeds
    the MXU's int8 path (2× bf16 peak) with a rank-1 dequant epilogue.
    Per-row activation scales ride outside the kernel (one tiny XLA
    all_gather); per-channel weight scales are resident."""
    from triton_distributed_tpu.kernels.quantized import emit_matmul_w8a8

    def emit_chunk(chunk):
        emit_matmul_w8a8(gathered_ref.at[chunk], b_ref,
                         sa_ref.at[chunk], sb_ref,
                         out_ref.at[chunk], m=m, n=n, k=k, config=cfg)

    _emit_ag_ring(ctx, emit_chunk, x_ref, gathered_ref, local_sem,
                  send_sem, recv_sems)


def ag_gemm_w8a8(a_shard, b_q, scale_b, ctx: AllGatherGEMMContext,
                 config=None):
    """Quantized fused AG-GEMM: C ≈ all_gather(a) @ (b_q·scale_b).

    a_shard: (m_local, k) float — quantized per-row on the fly;
    b_q: (k, n_local) int8 weights (quantize once ahead of time with
    `quantize_sym(w, axis=0)`); scale_b: (n_local,) f32.
    Returns (world*m_local, n_local) in a_shard's dtype.

    Beyond-parity: the reference's AG-GEMM family is half-precision
    only.  Int8 both halves the ring's ICI traffic and doubles the
    MXU ceiling, so the overlap balance point shifts — comm shrinks
    2× while compute speeds up ~1.7×.
    """
    from triton_distributed_tpu.kernels.quantized import (
        Int8MatmulConfig, matmul_w8a8, quantize_sym)

    world = ctx.world_size
    m, k = a_shard.shape
    k2, n = b_q.shape
    assert k == k2, (a_shard.shape, b_q.shape)
    assert b_q.dtype == jnp.int8
    # No xla/ll variants for the quantized path (yet): refuse a ctx
    # that asks for one rather than silently running the fused ring.
    assert ctx.method in ("auto", "fused"), (
        f"ag_gemm_w8a8 implements the fused ring only, got method="
        f"{ctx.method!r}")

    from triton_distributed_tpu.observability import record_overlap_gemm
    record_overlap_gemm("ag_gemm_w8a8", axis=ctx.axis, world=world,
                        method="fused", m=m, n=n, k=k, dtype=jnp.int8,
                        config=config)

    a_q, sa = quantize_sym(a_shard, axis=1)          # (m, k) i8, (m,)

    if world <= 1:
        return matmul_w8a8(a_q, b_q, sa, scale_b, config=config,
                           out_dtype=a_shard.dtype,
                           interpret=ctx.interpret)

    mp = round_up_rows(m, jnp.int8)
    if mp != m:
        a_q = jnp.pad(a_q, ((0, mp - m), (0, 0)))
        sa = jnp.pad(sa, (0, mp - m))

    # Scales are tiny (world*mp f32): one XLA all_gather, not worth a
    # ring slot.
    sa_all = jax.lax.all_gather(sa, ctx.axis)        # (world, mp)
    cfg = (config or Int8MatmulConfig()).resolve(mp, n, k)

    gathered, out = pl.pallas_call(
        functools.partial(_ag_gemm_w8a8_kernel, ctx, cfg, mp, n, k),
        name="ag_gemm_w8a8",
        out_shape=(
            jax.ShapeDtypeStruct((world, mp, k), jnp.int8),
            jax.ShapeDtypeStruct((world, mp, n), a_shard.dtype),
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
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
            flops=2 * world * mp * n * k,
            bytes_accessed=world * mp * k + k * n
            + world * mp * n * a_shard.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=default_interpret(ctx.interpret),
    )(a_q, b_q, sa_all.reshape(world, mp, 1),
      scale_b.astype(jnp.float32).reshape(1, n))

    if mp != m:
        out = out[:, :m]
    return out.reshape(world * m, n)


def _dual_context(ctx, target_cls, default_bwd_id):
    """Build the backward dual's context from the forward's — ONE
    place owns the field mirroring (method downgrade, fault injection,
    bwd collective id), so fwd and bwd can't silently diverge when a
    knob is added.

    The duality is TOPOLOGY-INDEPENDENT (da of AG-GEMM is a GEMM-RS
    over the same global row ordering, whatever carried the gather),
    and `ag_gemm`/`gemm_rs` both dispatch on Hierarchical/Torus
    contexts — so for those the dual ctx is the SAME ctx with the
    backward's collective id.
    """
    from triton_distributed_tpu.kernels.hierarchical import (
        HierarchicalContext)
    from triton_distributed_tpu.kernels.torus import TorusContext

    bwd_id = (ctx.bwd_collective_id
              if ctx.bwd_collective_id is not None else default_bwd_id)
    if isinstance(ctx, HierarchicalContext):
        # Mirror the flat branch's method downgrade: a forward-forced
        # GEMM method (tuned for the forward's shapes) must not leak
        # into the differently-shaped backward.
        return dataclasses.replace(
            ctx, collective_id=bwd_id,
            gemm_method=(ctx.gemm_method if ctx.gemm_method == "xla"
                         else "auto"))
    if isinstance(ctx, TorusContext):
        # TorusContext.method picks the TOPOLOGY schedule (torus vs
        # xla), not a shape-tuned GEMM method: a forced choice stays
        # valid for the backward's shapes, so preserve it — a
        # downgrade here would silently drop the fused torus backward
        # whenever the perf model ruled against the small shapes.
        return dataclasses.replace(ctx, collective_id=bwd_id)
    return target_cls(
        axis=ctx.axis, world_size=ctx.world_size, gemm=ctx.gemm,
        method=ctx.method if ctx.method == "xla" else "auto",
        collective_id=bwd_id,
        straggler=ctx.straggler,
        for_correctness=ctx.for_correctness,
        interpret=ctx.interpret)


def ag_gemm_diff(a_shard, b, ctx):
    """DIFFERENTIABLE fused AG-GEMM — training with comm-compute
    overlap in BOTH directions (beyond reference parity: the
    reference's overlap ops are inference-only).

    The backward is the dual op: with C = AG(a) @ b,

        da = reduce_scatter(dC @ bᵀ)   →  the fused `gemm_rs` kernel
        db = AG(a)ᵀ @ dC               →  a local matmul (reuses the
                                          gathered A saved in fwd)

    so the backward's communication overlaps its GEMM exactly like
    the forward's.  Residual memory: the gathered A (world × the
    shard) — the standard activation-recompute tradeoff applies; pass
    through `jax.checkpoint` to trade it back for a re-gather.
    """
    from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
        GEMMReduceScatterContext, gemm_rs)

    @jax.custom_vjp
    def core(a, w):
        return ag_gemm(a, w, ctx)

    def fwd(a, w):
        out, gathered = ag_gemm(a, w, ctx, return_gathered=True)
        return out, (gathered, w)

    def bwd(res, dc):
        gathered, w = res
        rs_ctx = _dual_context(ctx, GEMMReduceScatterContext,
                               cids.AG_GEMM_BWD)
        da = gemm_rs(dc, jnp.swapaxes(w, 0, 1), rs_ctx)
        db = jnp.dot(jnp.swapaxes(gathered, 0, 1), dc,
                     preferred_element_type=jnp.float32).astype(w.dtype)
        return da, db

    core.defvjp(fwd, bwd)
    return core(a_shard, b)


def ag_gemm_nonoverlap(a_shard, b, axis: str):
    """Golden / baseline: XLA collective then matmul (the reference's
    torch fwd mode, `layers/nvidia/tp_mlp.py` "torch" path)."""
    a_full = jax.lax.all_gather(a_shard, axis, tiled=True)
    return jnp.dot(a_full, b, preferred_element_type=jnp.float32).astype(
        a_shard.dtype)


def ag_gemm_ppermute(a_shard, b, axis: str):
    """XLA-level overlap: ring of `lax.ppermute`s with the dot of the
    previously-received chunk in between; XLA's latency-hiding
    scheduler runs the collective-permute DMA concurrently with the
    MXU.  Idiomatic-XLA middle ground between `ag_gemm_nonoverlap`
    and the fused Pallas kernel."""
    world = jax.lax.axis_size(axis)
    my = jax.lax.axis_index(axis)
    m, _ = a_shard.shape
    n = b.shape[1]
    perm = [(i, (i + 1) % world) for i in range(world)]

    out0 = jnp.dot(a_shard, b, preferred_element_type=jnp.float32)
    outs = [(my, out0)]
    cur = a_shard
    for s in range(world - 1):
        cur = jax.lax.ppermute(cur, axis, perm)
        src = jax.lax.rem(my - s - 1 + 2 * world, world)
        outs.append((src, jnp.dot(cur, b, preferred_element_type=jnp.float32)))

    full = jnp.zeros((world * m, n), dtype=jnp.float32)
    for src, val in outs:
        full = jax.lax.dynamic_update_slice(full, val, (src * m, 0))
    return full.astype(a_shard.dtype)


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


def _ag_gemm_spec(axis_sizes, method: str):
    axis, world = single_axis(axis_sizes)
    m, n, k = 8, 128, 128
    ctx = AllGatherGEMMContext(axis=axis, world_size=world)
    kernel = (_ag_gemm_ll_kernel if method == "ll"
              else _ag_gemm_fused_kernel)
    return KernelSpec(
        name=f"ag_gemm.{method}",
        body=functools.partial(kernel, ctx, m, n, k),
        axis_sizes=axis_sizes,
        refs=[RefSpec("x", (m, k), jnp.bfloat16),
              RefSpec("b", (k, n), jnp.bfloat16),
              RefSpec("gathered", (world, m, k), jnp.bfloat16),
              RefSpec("out", (world, m, n), jnp.bfloat16)],
        sems=[SemSpec("local"), SemSpec("send"), SemSpec("recv", (world,))],
    )


@register_comm_kernel("ag_gemm.fused", meshes=({"tp": 2}, {"tp": 4}))
def _analysis_ag_gemm_fused(axis_sizes):
    return _ag_gemm_spec(axis_sizes, "fused")


@register_comm_kernel("ag_gemm.ll", meshes=({"tp": 2}, {"tp": 4}))
def _analysis_ag_gemm_ll(axis_sizes):
    return _ag_gemm_spec(axis_sizes, "ll")


@register_comm_kernel("ag_gemm.w8a8", meshes=({"tp": 4},))
def _analysis_ag_gemm_w8a8(axis_sizes):
    from triton_distributed_tpu.kernels.quantized import Int8MatmulConfig

    axis, world = single_axis(axis_sizes)
    m, n, k = 8, 128, 128
    ctx = AllGatherGEMMContext(axis=axis, world_size=world)
    cfg = Int8MatmulConfig().resolve(m, n, k)
    return KernelSpec(
        name="ag_gemm.w8a8",
        body=functools.partial(_ag_gemm_w8a8_kernel, ctx, cfg, m, n, k),
        axis_sizes=axis_sizes,
        refs=[RefSpec("x", (m, k), jnp.int8),
              RefSpec("b", (k, n), jnp.int8),
              RefSpec("sa", (world, m, 1), jnp.float32),
              RefSpec("sb", (1, n), jnp.float32),
              RefSpec("gathered", (world, m, k), jnp.int8),
              RefSpec("out", (world, m, n), jnp.bfloat16)],
        sems=[SemSpec("local"), SemSpec("send"), SemSpec("recv", (world,))],
    )
