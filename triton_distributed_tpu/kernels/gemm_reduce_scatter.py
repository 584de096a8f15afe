"""Fused GEMM-ReduceScatter — the reverse TP overlap op.

Reference: `python/triton_dist/kernels/nvidia/gemm_reduce_scatter.py`
(590 LoC): a persistent GEMM producer computes C tiles in rank-swizzled
order (`gemm_rs_threadblock_swizzle.py`), stores each tile straight into
the owner rank's symmetric scatter buffer and sets a barrier; an RS
consumer on another stream reduces arrived tiles
(`kernel_gemm_rs_producer_persistent:131`, `gemm_rs_op:515`).

TPU re-design (single Pallas kernel): iterate output row-chunks in the
order (rank+1, rank+2, …, rank) — the same swizzle, so communication
starts after the first chunk and the *own* chunk (which needs no
transfer) is computed last.  Each remote chunk is matmul'ed into a
double-buffered staging area and immediately put to the owner's
receive buffer over ICI while the MXU moves on to the next chunk; a
final pipelined VPU reduction sums the ``world`` received partials.
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

from triton_distributed_tpu.kernels.matmul import (
    MatmulConfig,
    emit_chunked_matmul,
    emit_matmul,
    pad_contraction_lanes,
    round_up_rows,
)
from triton_distributed_tpu.kernels.reduce_scatter import _emit_reduce_sum
from triton_distributed_tpu.language import core as dl
from triton_distributed_tpu.utils.platform import (
    comm_compiler_params,
    default_interpret,
)


@dataclasses.dataclass
class GEMMReduceScatterContext:
    """Reference analogue: `GEMMReduceScatterTensorParallelContext`
    (`gemm_reduce_scatter.py:42`)."""

    axis: str
    world_size: int
    gemm: MatmulConfig = dataclasses.field(default_factory=MatmulConfig)
    method: str = "auto"          # auto | fused | ll | xla
    collective_id: int = cids.GEMM_RS
    # Fault injection — see AllGatherGEMMContext.
    straggler: Optional[tuple] = None
    for_correctness: bool = False
    interpret: Optional[bool] = None
    #: Collective id for the training dual (`gemm_rs_diff`'s backward
    #: ag_gemm); None → registry default.  See AllGatherGEMMContext.
    bwd_collective_id: Optional[int] = None

    #: Shape-only fallback for "auto" when K/N are unknown.
    LL_MAX_ROWS = 256

    def resolve_method(self, mc: int, dtype, k: Optional[int] = None,
                       n: Optional[int] = None, bus=None) -> str:
        """Model-driven fused/ll choice when K/N are known (shared
        `choose_ll_or_fused` with hysteresis); shape-only decode
        threshold otherwise.  ``bus``: optional feedback bus whose
        live link heat shifts the crossover; absent/empty/stale ⇒
        the static choice."""
        assert self.method in ("auto", "fused", "ll", "xla"), self.method
        if self.method != "auto":
            return self.method
        world = self.world_size
        if world <= 1:
            return "xla"
        mcp = round_up_rows(mc, dtype)
        if k is None or n is None:
            return "ll" if world * mcp <= self.LL_MAX_ROWS else "fused"
        from triton_distributed_tpu.kernels.comm_perf_model import (
            choose_ll_or_fused)
        return choose_ll_or_fused(mcp * n * jnp.dtype(dtype).itemsize,
                                  mcp, n, k, world, dtype,
                                  axis=self.axis, bus=bus,
                                  op="gemm_rs")


def create_gemm_rs_context(axis: str, world_size: int, **kw):
    return GEMMReduceScatterContext(axis=axis, world_size=world_size, **kw)


def _gemm_rs_fused_kernel(ctx: GEMMReduceScatterContext, mc, n, k,
                          a_ref, b_ref, out_ref, rbuf_ref, stage_ref,
                          send_sems, recv_sems):
    world = ctx.world_size
    my = jax.lax.axis_index(ctx.axis)
    dl.maybe_straggle(ctx.axis, ctx.straggler)
    dl.entry_barrier(ctx.axis, world)  # every peer puts into rbuf_ref
    dl.correctness_delay(ctx.axis, ctx.for_correctness)

    # Per-slot send semaphores: a shared counter would let wait_send be
    # satisfied by the *other* in-flight transfer and free a staging
    # slot that is still being read.
    pending = []
    for s in range(world):
        chunk = jax.lax.rem(my + 1 + s, world)
        if s == world - 1:
            # Own chunk: compute straight into our receive buffer.
            emit_matmul(a_ref.at[chunk], b_ref, rbuf_ref.at[my],
                        m=mc, n=n, k=k, config=ctx.gemm)
        else:
            slot = s % 2
            if len(pending) >= 2:
                # Free the staging slot we are about to overwrite.
                pending.pop(0).wait_send()
            emit_matmul(a_ref.at[chunk], b_ref, stage_ref.at[slot],
                        m=mc, n=n, k=k, config=ctx.gemm)
            rdma = pltpu.make_async_remote_copy(
                src_ref=stage_ref.at[slot],
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

    # Wait for the other ranks' partials of our chunk.
    for i in range(1, world):
        peer = jax.lax.rem(my + i, world)
        dl.wait_recv(rbuf_ref.at[peer], recv_sems.at[peer])

    _emit_reduce_sum(rbuf_ref, out_ref, world=world, m=mc, n=n)


def _gemm_rs_ll_kernel(ctx: GEMMReduceScatterContext, mcp, n, k,
                       a_ref, b_ref, out_ref, rbuf, osum, recv_sems):
    """Low-latency variant: one chunked matmul (streams B once) whose
    N blocks are scattered to their owners as they finish (1 hop, all
    peers concurrent), then the local reduction.  The decode-regime
    `gemm_rs` — reference analogue: the low-latency RS composition
    rather than the persistent tile-scatter producer.

    Schedule (``scatter_behind_stream`` in the launch event): the entry
    barrier protects the peers' receive buffer, and no peer is
    written before this chip's first N
    block is done — so the barrier's SIGNAL goes out at entry and its
    WAIT stands immediately before the first put, behind the weight
    stream.  Block j of every chunk travels straight out of the
    stream's VMEM block into the owner's VMEM (``rbuf``; the own chunk
    by a local copy) while block j+1's weights stream; only the last
    block's put, the sum over the ``world`` partials (in VMEM, no
    trip through HBM) and one write of the result stay exposed.  Same
    bytes on the wire, same slot per sender, same order of the sum.
    At decode shapes a call is 10-35 us of weight streaming, and the
    serial form (barrier, whole matmul, scatter, a pipelined reduce
    out of HBM) cost 9-17 us on top (PERF.md section 5, PR 34)."""
    world = ctx.world_size
    my = jax.lax.axis_index(ctx.axis)
    dl.maybe_straggle(ctx.axis, ctx.straggler)
    dl.barrier_all_signal(ctx.axis)
    dl.correctness_delay(ctx.axis, ctx.for_correctness)

    def scatter_block(j, blk, cols, sem):
        @pl.when(j == 0)
        def _():
            dl.barrier_all_wait(ctx.axis)  # peers' rbuf

        # Partial chunk c goes to owner c; slot = my rank on the
        # receiver.  Our own partial for our own chunk stays here.
        pltpu.make_async_copy(blk.at[my], rbuf.at[my, :, cols], sem).start()
        for i in range(1, world):
            peer = jax.lax.rem(my + i, world)
            pltpu.make_async_remote_copy(
                src_ref=blk.at[peer],
                dst_ref=rbuf.at[my, :, cols],
                send_sem=sem,
                recv_sem=recv_sems.at[my],
                device_id=dl.peer_id(ctx.axis, peer),
                device_id_type=pltpu.DeviceIdType.MESH,
            ).start()

    emit_chunked_matmul(a_ref, b_ref, chunks=world, mc=mcp, n=n, k=k,
                        config=ctx.gemm, write_block=scatter_block,
                        resident=[(rbuf.shape, rbuf.dtype),
                                  (osum.shape, osum.dtype)])

    # Wait for the other world-1 partials of *our* chunk to land.
    for i in range(1, world):
        peer = jax.lax.rem(my + i, world)
        dl.wait_recv(rbuf.at[peer], recv_sems.at[peer])
    acc = rbuf[0].astype(jnp.float32)
    for w in range(1, world):
        acc = acc + rbuf[w].astype(jnp.float32)
    osum[...] = acc.astype(osum.dtype)
    # Our own slot of recv_sems is otherwise unused: nobody puts to it.
    dl.local_copy(osum, out_ref, recv_sems.at[my])


def _gemm_rs_2d(a, b, hctx):
    """Two-level (dcn × ici) fused GEMM-RS: a DCN ring of partial sums
    wrapped around the fused ICI kernel.

    Reference: the 2D GEMM-RS composition — persistent GEMM feeding
    the 2D reduce-scatter (`gemm_reduce_scatter.py:515-576` →
    `reduce_scatter.py:844-873`, inter-node p2p at `:518`).

    TPU re-design: at DCN step s each device runs the fused ICI
    GEMM-RS (compute + intra-slice reduce-scatter, one Pallas kernel)
    on the rows destined for slice (my_d + dcn - 1 - s), and adds the
    result into an accumulator travelling a DCN ring — after dcn-1
    hops each accumulated chunk lands on its owner slice.  The DCN
    hops carry only the already-slice-reduced (M/world, n) chunk (the
    scarce-resource minimum, like the reference's 1/LOCAL_WORLD_SIZE
    IB traffic), and XLA overlaps each hop with the next step's Pallas
    kernel.  Cross-slice accumulation rides in f32 — dcn-1 sequential
    adds of bf16 partials would otherwise lose the golden's precision.
    """
    dcn = hctx.dcn_size
    ici_ctx = hctx._gemm_rs_ctx()
    if dcn <= 1:
        return gemm_rs(a, b, ici_ctx)

    mt, k = a.shape
    world = dcn * hctx.ici_size
    assert mt % world == 0, (a.shape, world)
    mi = mt // dcn                   # rows destined per slice
    ar = a.reshape(dcn, mi, k)
    my_d = jax.lax.axis_index(hctx.dcn_axis)
    perm = [(i, (i + 1) % dcn) for i in range(dcn)]

    def part(c):
        """Slice-level partial for destination slice ``c``: fused ICI
        GEMM-RS over this slice's K-shards → (M/world, n)."""
        rows = jax.lax.dynamic_index_in_dim(ar, c, axis=0,
                                            keepdims=False)
        return gemm_rs(rows, b, ici_ctx).astype(jnp.float32)

    # Same ring walk as `gemm_rs_ppermute`, lifted to the DCN level:
    # step s computes the chunk owned by slice (my_d + dcn - 1 - s);
    # the travelling accumulator reaches its owner at the last step.
    acc = part(jax.lax.rem(my_d + dcn - 1, dcn))
    for s in range(1, dcn):
        acc = jax.lax.ppermute(acc, hctx.dcn_axis, perm)
        acc = acc + part(jax.lax.rem(my_d + 2 * dcn - 1 - s, dcn))
    return acc.astype(a.dtype)


def gemm_rs(a, b, ctx):
    """reduce_scatter(a @ b) over `ctx.axis`, overlapped.
    Call inside shard_map.

    a: (M, k_local) — this rank's K-shard of the activation.
    b: (k_local, n) — this rank's K-shard of the (row-parallel) weight.
    Returns this rank's reduced output rows: (M / world, n).

    Any chunk size is supported on the fused paths: chunks are padded
    to the Mosaic sublane multiple inside the op and sliced back —
    decode shapes run the Pallas "ll" path, not an XLA fallback.

    ``ctx`` may be a `GEMMReduceScatterContext` (single axis), a
    `HierarchicalContext` (two-level dcn × ici — the reference's 2D
    GEMM-RS, `gemm_reduce_scatter.py:515-576`), or a `TorusContext`
    (both ICI torus axes at once, `kernels/torus.py`).
    """
    from triton_distributed_tpu.kernels.hierarchical import (
        HierarchicalContext)
    from triton_distributed_tpu.kernels.torus import (
        TorusContext, gemm_rs_torus)
    if isinstance(ctx, HierarchicalContext):
        return _gemm_rs_2d(a, b, ctx)
    if isinstance(ctx, TorusContext):
        return gemm_rs_torus(a, b, ctx)

    world = ctx.world_size
    mt, k = a.shape
    k2, n = b.shape
    assert k == k2 and mt % world == 0, (a.shape, b.shape, world)
    mc = mt // world

    method = ctx.resolve_method(mc, a.dtype, k=k, n=n)

    # Launch-metadata event (fires once per traced specialization).
    # The hop pattern link attribution needs derives from the method
    # (instrument.hops_for_method): the fused ring forwards partial
    # chunks over +1 neighbor links; ll pushes each reduced chunk
    # straight to its owner.
    from triton_distributed_tpu.observability import record_overlap_gemm
    record_overlap_gemm("gemm_rs", axis=ctx.axis, world=world,
                        method=method, m=mc, n=n, k=k, dtype=a.dtype,
                        config=ctx.gemm)

    if method == "xla" or world <= 1:
        return gemm_rs_nonoverlap(a, b, ctx.axis)

    # Pad each chunk's rows to the sublane multiple (sliced back
    # below; padded partial rows are computed but discarded).
    mcp = round_up_rows(mc, a.dtype)
    a3 = a.reshape(world, mc, k)
    if mcp != mc:
        a3 = jnp.pad(a3, ((0, 0), (0, mcp - mc), (0, 0)))
    # Lane-align K (see `matmul.pad_contraction_lanes`; topology-
    # compile catch at k_local=64 — interpret mode accepts anything).
    a3, b, k = pad_contraction_lanes(a3, b)

    out_shape = [jax.ShapeDtypeStruct((mcp, n), a.dtype)]
    if method == "ll":
        kernel = _gemm_rs_ll_kernel
        # The partials leave straight from the stream's VMEM blocks and
        # are received, and summed, in VMEM.
        scratch = [pltpu.VMEM((world, mcp, n), a.dtype),
                   pltpu.VMEM((mcp, n), a.dtype),
                   pltpu.SemaphoreType.DMA((world,))]
    else:
        kernel = _gemm_rs_fused_kernel
        # HBM receive buffer and double-buffered send staging
        # (per-chunk matmul + put) are extra outputs (discarded) —
        # Mosaic only allows vmem/smem/semaphore scratch.
        out_shape += [jax.ShapeDtypeStruct((world, mcp, n), a.dtype),
                      jax.ShapeDtypeStruct((2, mcp, n), a.dtype)]
        scratch = [
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((world,)),
        ]

    out = pl.pallas_call(
        functools.partial(kernel, ctx, mcp, n, k),
        name="gemm_rs_ll" if method == "ll" else "gemm_rs_fused",
        out_shape=tuple(out_shape),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),) * len(out_shape),
        scratch_shapes=scratch,
        compiler_params=comm_compiler_params(ctx.collective_id, world),
        cost_estimate=pl.CostEstimate(
            flops=2 * world * mcp * n * k,
            bytes_accessed=(world * mcp * k + k * n + world * mcp * n)
            * a.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=default_interpret(ctx.interpret),
    )(a3, b)[0]
    return out[:mc] if mcp != mc else out


def gemm_rs_diff(a, b, ctx):
    """DIFFERENTIABLE fused GEMM-RS (see `ag_gemm_diff` — this is its
    dual).  With o = RS(a @ b) over rows,

        dA = AG(do) @ bᵀ    →  the fused `ag_gemm` kernel (which also
                               hands back AG(do) = the full dC)
        db = aᵀ @ dC        →  a local matmul on that gathered dC

    so the backward's all-gather overlaps its GEMM.
    """
    from triton_distributed_tpu.kernels.allgather_gemm import (
        AllGatherGEMMContext, ag_gemm)

    @jax.custom_vjp
    def core(a, w):
        return gemm_rs(a, w, ctx)

    def fwd(a, w):
        return gemm_rs(a, w, ctx), (a, w)

    def bwd(res, do):
        a, w = res
        from triton_distributed_tpu.kernels.allgather_gemm import (
            _dual_context)
        ag_ctx = _dual_context(ctx, AllGatherGEMMContext,
                               cids.GEMM_RS_BWD)
        da, dc_full = ag_gemm(do, jnp.swapaxes(w, 0, 1), ag_ctx,
                              return_gathered=True)
        db = jnp.dot(jnp.swapaxes(a, 0, 1), dc_full,
                     preferred_element_type=jnp.float32).astype(w.dtype)
        return da, db

    core.defvjp(fwd, bwd)
    return core(a, b)


def gemm_rs_nonoverlap(a, b, axis: str):
    """Golden / baseline: matmul then XLA reduce-scatter."""
    world = jax.lax.axis_size(axis)
    mt = a.shape[0]
    partial = jnp.dot(a, b, preferred_element_type=jnp.float32)
    out = jax.lax.psum_scatter(
        partial.reshape(world, mt // world, -1), axis,
        scatter_dimension=0, tiled=False)
    return out.astype(a.dtype)


def gemm_rs_ppermute(a, b, axis: str):
    """XLA-level overlap: compute the chunk destined for rank
    (my+1+s) each step and pass partial sums around a ring; XLA's
    scheduler overlaps the collective-permutes with the dots."""
    world = jax.lax.axis_size(axis)
    my = jax.lax.axis_index(axis)
    mt, _ = a.shape
    mc = mt // world
    ar = a.reshape(world, mc, -1)
    perm = [(i, (i + 1) % world) for i in range(world)]

    # Walk the ring so that after world-1 hops the running sum lands on
    # its owner: start with the chunk for rank my+1 (send direction +1
    # means data moves toward its owner one hop per step... owner is
    # my+world-1 hops away for chunk my+1? Use the standard RS walk:
    # at step s compute/add the chunk owned by rank (my - s) and pass.
    def chunk_of(r):
        return jnp.take(ar, r, axis=0)

    acc = jnp.dot(chunk_of(jax.lax.rem(my + world - 1, world)), b,
                  preferred_element_type=jnp.float32)
    for s in range(1, world):
        acc = jax.lax.ppermute(acc, axis, perm)
        c = jax.lax.rem(my + world - 1 - s, world)
        acc = acc + jnp.dot(chunk_of(c), b,
                            preferred_element_type=jnp.float32)
    return acc.astype(a.dtype)


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


@register_comm_kernel("gemm_rs.fused", meshes=({"tp": 2}, {"tp": 4}))
def _analysis_gemm_rs_fused(axis_sizes):
    axis, world = single_axis(axis_sizes)
    mc, n, k = 8, 128, 128
    ctx = GEMMReduceScatterContext(axis=axis, world_size=world)
    return KernelSpec(
        name="gemm_rs.fused",
        body=functools.partial(_gemm_rs_fused_kernel, ctx, mc, n, k),
        axis_sizes=axis_sizes,
        refs=[RefSpec("a", (world, mc, k), jnp.bfloat16),
              RefSpec("b", (k, n), jnp.bfloat16),
              RefSpec("out", (mc, n), jnp.bfloat16),
              RefSpec("rbuf", (world, mc, n), jnp.bfloat16),
              RefSpec("stage", (2, mc, n), jnp.bfloat16)],
        sems=[SemSpec("send", (2,)), SemSpec("recv", (world,))],
    )


@register_comm_kernel("gemm_rs.ll", meshes=({"tp": 2}, {"tp": 4}))
def _analysis_gemm_rs_ll(axis_sizes):
    axis, world = single_axis(axis_sizes)
    mc, n, k = 8, 128, 128
    ctx = GEMMReduceScatterContext(axis=axis, world_size=world)
    return KernelSpec(
        name="gemm_rs.ll",
        body=functools.partial(_gemm_rs_ll_kernel, ctx, mc, n, k),
        axis_sizes=axis_sizes,
        refs=[RefSpec("a", (world, mc, k), jnp.bfloat16),
              RefSpec("b", (k, n), jnp.bfloat16),
              RefSpec("out", (mc, n), jnp.bfloat16),
              RefSpec("rbuf", (world, mc, n), jnp.bfloat16),
              RefSpec("osum", (mc, n), jnp.bfloat16)],
        sems=[SemSpec("recv", (world,))],
    )
