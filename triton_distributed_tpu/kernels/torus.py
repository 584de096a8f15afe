"""Multi-axis torus collectives: drive EVERY torus dimension at once.

Reference: the NUMA-aware / multi-dimensional intra-node variants —
2D ring AllGather (`python/triton_dist/kernels/nvidia/allgather.py:
196-293`), low-latency push-2d AND push-3d
(`low_latency_allgather.py:345-400` — the reference escalates its
topology exploitation from 2 to 3 levels; this module does the same
for the ICI torus).  Those exploit NVLink topology hierarchy; the TPU
analogue exploits the ICI torus: a v5e chip has 4 ICI links (x±, y±),
a v4/v5p chip has 6 (x±, y±, z±) — but a single-axis ring only ever
drives one axis, at most 2 of the 4-6 links.

Design — the 2·nd-lane bucket schedule (nd = number of torus axes):
split the local shard into 2·nd pieces and run 2·nd CONCURRENT
nd-phase rings, one per (cyclic axis rotation, direction):

  2 axes (4 quarters):            3 axes (6 sextants):
    q0: +x then +y                  q0: +x, +y, +z
    q1: +y then +x                  q1: +y, +z, +x
    q2: -x then -y                  q2: +z, +x, +y
    q3: -y then -x                  q3: -x, -y, -z
                                    q4: -y, -z, -x
                                    q5: -z, -x, -y

At phase p, lane (rotation r, sign s) rides axis (r + p) mod nd in
direction s — across lanes every directed link (axis, dir) is busy at
EVERY phase, so the torus runs at ~nd× the bandwidth of a
bidirectional single-axis ring and ~2·nd× a unidirectional one.
Phase 0 rings gather each piece within its first axis (per-chunk
sends); phase p>0 rings forward whole slabs (the block gathered over
the lane's first p axes) along axis p.  Per-(lane, position) recv
semaphores are the readiness flags, exactly like the 1D kernels in
`allgather.py`.

ReduceScatter reverses the schedule: stage t ring-reduces the slabs
of AG phase nd-1-t (running partial sums with ack flow control, like
`reduce_scatter._ring_rs_kernel`), so the heavy big-slab traffic again
spreads over all 2·nd links.

Layout: global rank g is row-major over the mesh axes in ctx order
(x-major for 2 axes), matching ``Mesh(devs.reshape(*sizes), axes)``
with ``P(axes)``.  The gathered output (*sizes, L, ms, n) reshapes
straight to (world * m, n) with each device block being its L pieces
in order — no transpose, no extra HBM pass.

Fault injection (reference `stress_test_ag_gemm.py:119-121`,
`allgather_gemm.py:606-607`): ``TorusContext.straggler`` /
``for_correctness`` thread `dl.maybe_straggle` / `correctness_delay`
into every torus kernel at entry, keyed by flat rank over the torus
axes.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu import collective_ids as cids

from triton_distributed_tpu.kernels.matmul import (
    MatmulConfig,
    emit_matmul,
    pad_contraction_lanes,
    pad_lanes,
    round_up_rows,
    unpad_lanes,
)
from triton_distributed_tpu.kernels.reduce_scatter import (
    emit_add_into as _add_into,
)
from triton_distributed_tpu.language import core as dl
from triton_distributed_tpu.utils.platform import (
    comm_compiler_params,
    default_interpret,
)


@dataclasses.dataclass
class TorusContext:
    """Two or three concurrent mesh axes of one ICI torus (all
    Pallas-DMA addressable — unlike `HierarchicalContext`, where the
    outer axis is DCN and only XLA collectives can cross it)."""

    axes: Tuple[str, ...]          # (x_axis, y_axis[, z_axis])
    sizes: Tuple[int, ...]         # (wx, wy[, wz])
    method: str = "auto"           # auto | torus | xla
    collective_id: int = cids.ALLGATHER
    interpret: Optional[bool] = None
    #: MXU config for the fused torus GEMM ops (`ag_gemm` / `gemm_rs`
    #: accept a TorusContext and consume pieces in arrival order).
    gemm: MatmulConfig = dataclasses.field(default_factory=MatmulConfig)
    #: Collective id for the training duals; None → registry default
    #: (see HierarchicalContext.bwd_collective_id).
    bwd_collective_id: Optional[int] = None
    #: Fault injection (reference `_run_straggler`): (flat_rank,
    #: cycles) delays that rank at kernel entry; `for_correctness`
    #: staggers every rank's entry to widen race windows.
    straggler: Optional[Tuple[int, int]] = None
    for_correctness: bool = False

    @property
    def world_size(self) -> int:
        w = 1
        for s in self.sizes:
            w *= s
        return w

    def active(self) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
        """Axes/sizes with the degenerate (size-1) dimensions dropped:
        a (1, 8) "torus" is really a single ring, a (2, 2, 1) one a
        2-axis torus.  Row-major rank order is preserved."""
        pairs = [(a, s) for a, s in zip(self.axes, self.sizes) if s > 1]
        return (tuple(a for a, _ in pairs), tuple(s for _, s in pairs))

    def resolve_method(self, nbytes_per_shard: int, bus=None) -> str:
        """Perf-model crossover: the multi-lane torus schedule wins on
        bandwidth (~nd× a bidir single-axis ring) but pays nd
        serialized ring phases of latency; below the crossover fall
        back to the XLA collective over all axes.  ``bus``: optional
        feedback bus — live contention on one axis favors the lane
        schedule that spreads over the others; absent/empty/stale ⇒
        the static choice."""
        if self.method != "auto":
            return self.method
        axes, sizes = self.active()
        if len(sizes) <= 1:
            return "torus"   # degenerates to the single-axis auto path
        from triton_distributed_tpu.kernels.comm_perf_model import (
            torus_beats_single_axis)
        return ("torus" if torus_beats_single_axis(
            nbytes_per_shard, sizes, axes=axes, bus=bus) else "xla")


def create_torus_context(axes, sizes, **kw) -> TorusContext:
    return TorusContext(axes=tuple(axes), sizes=tuple(sizes), **kw)


#: Stable per-RS-id allocation of the AllReduce AG-stage id (ADVICE
#: r3): the default maps to the registry constant; any other id gets
#: ONE registry-allocated partner, cached so repeated traces reuse it.
#: Growth is bounded by the number of DISTINCT user-supplied RS ids
#: (user ids come from `cids.allocate()`, so programs allocate a
#: handful, not unbounded); the lock makes check-then-allocate atomic
#: under concurrent tracing (ADVICE r4).
_PAIRED_AG_IDS: dict = {}
_PAIRED_AG_IDS_LOCK = threading.Lock()


def _paired_ag_id(rs_id: int) -> int:
    if rs_id == cids.ALLGATHER:
        return cids.ALLREDUCE_RING_AG
    with _PAIRED_AG_IDS_LOCK:
        if rs_id not in _PAIRED_AG_IDS:
            _PAIRED_AG_IDS[rs_id] = cids.allocate()
        return _PAIRED_AG_IDS[rs_id]


def lane_schedules(nd: int):
    """The 2·nd lane schedules: lane (sign s, rotation r) rides axis
    (r + p) mod nd in direction s at phase p.  Each schedule is a
    tuple of (axis_idx, direction) per phase; across lanes every
    directed link is in use at every phase (the generalization of the
    round-3 4-quarter `_QUARTERS` table, per VERDICT r3 next #2)."""
    return tuple(
        tuple(((r + p) % nd, s) for p in range(nd))
        for s in (+1, -1) for r in range(nd))


def _neighbor(axes, sizes, axis_idx: int, direction: int):
    """peer_id of the ring neighbor `direction` along axes[axis_idx],
    holding the other axes fixed."""
    ax = axes[axis_idx]
    w = sizes[axis_idx]
    p = jax.lax.axis_index(ax)
    tgt = jax.lax.rem(p + direction + w, w)
    return dl.peer_id(ax, tgt)


def _slab_ref(ref, sched, p: int, c, pos, q: int):
    """Phase-``p`` slab of lane ``q``: the block gathered over the
    lane's first ``p`` axes, ring position ``c`` along axis
    ``sched[p][0]``, own position on every remaining axis.  ``ref`` is
    (*sizes, L, ms, n); index order follows MESH axis order."""
    gathered = {sched[j][0] for j in range(p)}
    ring_ax = sched[p][0]
    idx = []
    for ax in range(len(sched)):
        if ax == ring_ax:
            idx.append(c)
        elif ax in gathered:
            idx.append(slice(None))
        else:
            idx.append(pos[ax])
    return ref.at[tuple(idx) + (q,)]


def _inject_faults(ctx: TorusContext):
    """Straggler / race-widening delays at kernel entry (before the
    entry barriers, so the skew is visible to every sync point)."""
    axes, _ = ctx.active()
    dl.maybe_straggle(axes, ctx.straggler)
    dl.correctness_delay(axes, ctx.for_correctness)


# ---------------------------------------------------------------------------
# AllGather over a 2- or 3-axis torus
# ---------------------------------------------------------------------------

def _emit_torus_ag(ctx: TorusContext, axes, sizes, x_ref, o_ref,
                   local_sems, send_sems, phase_sems,
                   consume_local=None, consume_piece=None):
    """The 2·nd-lane nd-phase torus AG schedule, with optional
    arrival-order consumption hooks (the torus analogue of
    `allgather_gemm._emit_ag_ring`'s consume-while-the-next-chunk-
    flies pattern):

    - ``consume_local()`` fires once the L local pieces are placed
      (and step-0 sends started), overlapping the first chunk flights;
    - ``consume_piece(q, p, c)`` fires when lane ``q``'s phase-``p``
      slab at ring position ``c`` has landed and the NEXT step's sends
      are in flight.

    Every gathered row is announced to exactly one hook.
    """
    nd = len(sizes)
    scheds = lane_schedules(nd)
    L = len(scheds)
    pos = tuple(jax.lax.axis_index(a) for a in axes)
    w = sizes

    _inject_faults(ctx)

    # Every axis neighborhood puts into our o_ref: barrier with each.
    for i, a in enumerate(axes):
        dl.entry_barrier(a, w[i], neighbors_only=True)

    # Place the L local pieces.
    for q in range(L):
        dl.local_copy(x_ref.at[q], o_ref.at[pos + (q,)],
                      local_sems.at[q])

    pending = []      # (q, p, c) slabs landed but not yet consumed

    def flush_pending():
        if consume_piece is not None:
            for item in pending:
                consume_piece(*item)
        pending.clear()

    first = True
    for p in range(nd):
        steps = max(w[sched[p][0]] for sched in scheds) - 1
        for s in range(steps):
            started = []
            for q, sched in enumerate(scheds):
                ax, d = sched[p]
                if s >= w[ax] - 1:
                    continue
                pcur = pos[ax]
                src = jax.lax.rem(pcur - s * d + 2 * s * w[ax] + w[ax],
                                  w[ax])
                slab = _slab_ref(o_ref, sched, p, src, pos, q)
                pltpu.make_async_remote_copy(
                    src_ref=slab,
                    dst_ref=slab,
                    send_sem=send_sems.at[q],
                    recv_sem=phase_sems.at[p, q, src],
                    device_id=_neighbor(axes, sizes, ax, d),
                    device_id_type=pltpu.DeviceIdType.MESH,
                ).start()
                exp = jax.lax.rem(pcur - (s + 1) * d
                                  + 2 * (s + 1) * w[ax] + w[ax], w[ax])
                started.append((q, p, exp))
            # MXU work on data already held overlaps in-flight DMAs.
            if first:
                if consume_local is not None:
                    consume_local()
                first = False
            else:
                flush_pending()
            for q, pp, exp in started:
                dl.wait_recv(_slab_ref(o_ref, scheds[q], pp, exp, pos, q),
                             phase_sems.at[pp, q, exp])
                dl.wait_send(_slab_ref(o_ref, scheds[q], pp, exp, pos, q),
                             send_sems.at[q])
            pending.extend(started)
    flush_pending()


def _torus_ag_kernel(ctx, axes, sizes, x_ref, o_ref,
                     local_sems, send_sems, phase_sems):
    _emit_torus_ag(ctx, axes, sizes, x_ref, o_ref, local_sems,
                   send_sems, phase_sems)


def _ag_fallback_1axis(x, ctx: TorusContext, axes):
    from triton_distributed_tpu.kernels.allgather import (
        AllGatherContext, all_gather)
    return all_gather(x, AllGatherContext(
        axis=axes[0], world_size=ctx.world_size,
        collective_id=ctx.collective_id, interpret=ctx.interpret,
        straggler=ctx.straggler, for_correctness=ctx.for_correctness))


def all_gather_torus(x, ctx: TorusContext):
    """Gather row shards over ALL torus axes concurrently.

    Input (inside shard_map over the axes): this device's (m, n)
    shard of a (world * m, n) array, row-major device order over
    ``ctx.axes``.  Output: the full array, replicated.
    """
    world = ctx.world_size
    if world <= 1:
        return x
    method = ctx.resolve_method(x.size * x.dtype.itemsize)
    axes, sizes = ctx.active()
    if method == "xla" or len(axes) > 1:
        # Degenerate tori delegate to all_gather, which emits its own
        # launch-metadata event.
        from triton_distributed_tpu.observability import record_collective
        # Hop annotation: the torus schedule keeps all 2·nd per-axis
        # lanes busy concurrently (axes/sizes let link attribution
        # rebuild the exact torus).
        record_collective("all_gather_torus", axis=ctx.axes, world=world,
                          method=method, shape=x.shape, dtype=x.dtype,
                          payload_bytes=x.size * x.dtype.itemsize,
                          sizes=sizes if len(sizes) > 1 else None,
                          hops="torus" if len(sizes) > 1 else "ring",
                          axes=axes)
    if method == "xla":
        return jax.lax.all_gather(x, ctx.axes, tiled=True)
    if len(axes) == 1:
        # Degenerate torus: a single-axis ring is the right algorithm.
        return _ag_fallback_1axis(x, ctx, axes)

    nd = len(sizes)
    L = 2 * nd
    m, _ = x.shape
    # Pieces must be SUBLANE-ALIGNED (row counts) and LANE-ALIGNED
    # (column counts): Mosaic rejects DMA slices of unaligned blocks
    # in either dim (topology-compile catches — interpret mode
    # accepts any shape).
    xp, n_orig = pad_lanes(x)
    n = xp.shape[1]
    ms = round_up_rows(pl.cdiv(m, L), x.dtype)
    pad = L * ms - m
    if pad:
        xp = jnp.pad(xp, ((0, pad), (0, 0)))
    maxw = max(sizes)

    out = pl.pallas_call(
        functools.partial(_torus_ag_kernel, ctx, axes, sizes),
        name="all_gather_torus",
        out_shape=jax.ShapeDtypeStruct(sizes + (L, ms, n), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((L,)),           # local copies
            pltpu.SemaphoreType.DMA((L,)),           # per-lane send
            pltpu.SemaphoreType.DMA((nd, L, maxw)),  # per-phase arrivals
        ],
        compiler_params=comm_compiler_params(ctx.collective_id, world),
        interpret=default_interpret(ctx.interpret),
    )(xp.reshape(L, ms, n))
    out = out.reshape(world, L * ms, n)
    if pad:
        out = out[:, :m]
    return unpad_lanes(out, n_orig).reshape(world * m, n_orig)


# ---------------------------------------------------------------------------
# ReduceScatter over a 2- or 3-axis torus
# ---------------------------------------------------------------------------


class _ReduceLane:
    """One ring-reduce lane (running partial sums + 2-slot staging with
    ack credit flow, the `reduce_scatter._ring_rs_kernel` pattern),
    split into per-step wait-ack/send/finish pieces so ALL lanes — one
    per directed torus link — can be interleaved step-by-step."""

    def __init__(self, axes, sizes, axis_idx, direction, take_chunk,
                 out_ref, staging_slot, accum_slot, send_sem, recv_sems,
                 ack_sem, chunk_shape):
        self.wsz = sizes[axis_idx]
        self.nsteps = self.wsz - 1
        self.p = jax.lax.axis_index(axes[axis_idx])
        self.fwd = _neighbor(axes, sizes, axis_idx, direction)
        self.bwd = _neighbor(axes, sizes, axis_idx, -direction)
        self.direction = direction
        self.take_chunk = take_chunk
        self.out_ref = out_ref
        self.staging_slot = staging_slot    # slot -> ref
        self.accum_slot = accum_slot        # slot -> ref
        self.send_sem = send_sem
        self.recv_sems = recv_sems          # (2,) per-slot arrivals
        self.ack_sem = ack_sem
        self.chunk_shape = chunk_shape

    def wait_ack(self, s):
        if s >= 2:
            # The slot we are about to overwrite on the right neighbor
            # must have been consumed there.
            pltpu.semaphore_wait(self.ack_sem, 1)

    def send(self, s):
        slot = s % 2
        send_chunk = jax.lax.rem(
            self.p - (1 + s) * self.direction + (1 + s) * self.wsz,
            self.wsz)
        src = (self.take_chunk(send_chunk) if s == 0
               else self.accum_slot(slot))
        rdma = pltpu.make_async_remote_copy(
            src_ref=src,
            dst_ref=self.staging_slot(slot),
            send_sem=self.send_sem,
            recv_sem=self.recv_sems.at[slot],
            device_id=self.fwd,
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        rdma.start()
        return rdma

    def finish(self, s, rdma):
        slot = s % 2
        recv_chunk = jax.lax.rem(
            self.p - (2 + s) * self.direction + (2 + s) * self.wsz,
            self.wsz)
        dl.wait_recv(self.staging_slot(slot), self.recv_sems.at[slot])
        dst = (self.accum_slot((s + 1) % 2) if s < self.nsteps - 1
               else self.out_ref)
        _add_into(dst, self.staging_slot(slot),
                  self.take_chunk(recv_chunk), self.chunk_shape)
        pltpu.semaphore_signal(self.ack_sem, inc=1, device_id=self.bwd,
                               device_id_type=pltpu.DeviceIdType.MESH)
        rdma.wait_send()

    def drain(self):
        pltpu.semaphore_wait(self.ack_sem, min(2, self.nsteps))


def _run_lanes(lanes):
    """Interleave lanes step-by-step: all lanes' sends of step s are in
    flight (on distinct directed links) before any finish.  The ack
    waits are drained for ALL lanes before ANY lane's send is issued —
    interleaving wait/send per lane would let one slow lane's ack
    serialize the other lanes' step-s sends (ADVICE r3)."""
    for s in range(max(l.nsteps for l in lanes)):
        active = [l for l in lanes if s < l.nsteps]
        for l in active:
            l.wait_ack(s)
        pending = [(l, l.send(s)) for l in active]
        for l, rdma in pending:
            l.finish(s, rdma)
    for l in lanes:
        l.drain()


def _rs_stage_dims(scheds, q: int, t: int, nd: int):
    """Mesh-sorted axes that remain gathered AFTER stage ``t`` of lane
    ``q``'s reduce (stage t reduces along sched[nd-1-t][0])."""
    return sorted(scheds[q][j][0] for j in range(nd - 1 - t))


def _torus_rs_kernel(ctx, axes, sizes, ms, n, x_ref, out_ref, *refs):
    """x_ref: (*sizes, L, ms, n) partials; out_ref: (L, ms, n).

    Per lane q (reversing its AG schedule): stage t ring-reduces the
    slabs of AG phase nd-1-t (each = the block over the lane's first
    nd-1-t axes), landing the fully-reduced own chunk in
    ``out_ref[q]`` at the last stage.  All lanes interleave so every
    stage's slab traffic rides all 2·nd directed links concurrently.

    ``refs``: per stage t: staging pair (s_t, a_t) and, for t < nd-1,
    the inter-stage landing buffer mid_t; then scratch send_sems,
    stage_sems (nd, L, 2), ack_sems (nd·L,).
    """
    nd = len(sizes)
    scheds = lane_schedules(nd)
    L = len(scheds)
    w = sizes
    pos = tuple(jax.lax.axis_index(a) for a in axes)

    send_sems, stage_sems, ack_sems = refs[-3:]
    s_refs, a_refs, mid_refs = [], [], []
    i = 0
    for t in range(nd):
        s_refs.append(refs[i])
        a_refs.append(refs[i + 1])
        i += 2
        if t < nd - 1:
            mid_refs.append(refs[i])
            i += 1

    _inject_faults(ctx)
    for ai, a in enumerate(axes):
        dl.entry_barrier(a, w[ai])

    def buf_idx(q, dims, ring_ax=None, c=None, lead=()):
        """Index tuple into a (L, *lead_dims, maxw^k, ms, n) buffer:
        lane q, then per mesh-sorted gathered axis either the ring
        position ``c`` or the full 0:w slice."""
        idx = [q, *lead]
        for ax in dims:
            idx.append(c if ax == ring_ax else slice(0, w[ax]))
        return tuple(idx)

    for t in range(nd):
        r_idx = nd - 1 - t
        lanes = []
        for q, sched in enumerate(scheds):
            ar, ad = sched[r_idx]
            dims_after = _rs_stage_dims(scheds, q, t, nd)
            dims_before = sorted(sched[j][0] for j in range(r_idx + 1))
            shape = tuple(w[ax] for ax in dims_after) + (ms, n)

            if t == 0:
                def take(c, q=q, sched=sched):
                    return _slab_ref(x_ref, sched, nd - 1, c, pos, q)
            else:
                def take(c, q=q, t=t, ar=ar, dims=dims_before):
                    return mid_refs[t - 1].at[buf_idx(q, dims, ar, c)]

            if t == nd - 1:
                dst = out_ref.at[q]
            else:
                dst = mid_refs[t].at[buf_idx(q, dims_after)]

            lanes.append(_ReduceLane(
                axes, sizes, ar, ad, take, dst,
                lambda slot, q=q, t=t, dims=dims_after:
                    s_refs[t].at[buf_idx(q, dims, lead=(slot,))],
                lambda slot, q=q, t=t, dims=dims_after:
                    a_refs[t].at[buf_idx(q, dims, lead=(slot,))],
                send_sems.at[q], stage_sems.at[t, q],
                ack_sems.at[t * L + q],
                chunk_shape=shape))
        _run_lanes(lanes)


def _rs_fallback_1axis(x, ctx: TorusContext, axes):
    from triton_distributed_tpu.kernels.reduce_scatter import (
        ReduceScatterContext, reduce_scatter)
    return reduce_scatter(x, ReduceScatterContext(
        axis=axes[0], world_size=ctx.world_size,
        collective_id=ctx.collective_id, interpret=ctx.interpret,
        straggler=ctx.straggler, for_correctness=ctx.for_correctness))


def reduce_scatter_torus(x, ctx: TorusContext):
    """Reduce per-device partials of the full array over ALL torus
    axes concurrently and keep this device's chunk.

    Input: (world * m, n) partials, row-major device order; output:
    this device's reduced (m, n) chunk.
    """
    world = ctx.world_size
    if world <= 1:
        return x
    mt0 = x.shape[0]
    chunk_bytes = mt0 // world * x.shape[1] * x.dtype.itemsize
    method = ctx.resolve_method(chunk_bytes)
    axes, sizes = ctx.active()
    if method == "xla" or len(axes) > 1:
        from triton_distributed_tpu.observability import record_collective
        record_collective("reduce_scatter_torus", axis=ctx.axes,
                          world=world, method=method, shape=x.shape,
                          dtype=x.dtype, payload_bytes=chunk_bytes,
                          sizes=sizes if len(sizes) > 1 else None,
                          hops="torus" if len(sizes) > 1 else "ring",
                          axes=axes)
    if method == "xla":
        return jax.lax.psum_scatter(
            x.reshape(world, mt0 // world, -1), ctx.axes,
            scatter_dimension=0, tiled=False)
    if len(axes) == 1:
        return _rs_fallback_1axis(x, ctx, axes)

    nd = len(sizes)
    L = 2 * nd
    mt, _ = x.shape
    assert mt % world == 0, (x.shape, world)
    m = mt // world
    # Sublane- and lane-aligned pieces (see all_gather_torus).
    xp, n_orig = pad_lanes(x)
    n = xp.shape[1]
    ms = round_up_rows(pl.cdiv(m, L), x.dtype)
    pad = L * ms - m
    xr = xp.reshape(world, m, n)
    if pad:
        xr = jnp.pad(xr, ((0, 0), (0, pad), (0, 0)))
    maxw = max(sizes)

    # Out-buffer list mirrors the kernel's unpack: per stage t the
    # (s_t, a_t) staging pair (2 slots each), plus mid_t for t < nd-1.
    out_shapes = [jax.ShapeDtypeStruct((L, ms, n), x.dtype)]
    for t in range(nd):
        k = nd - 1 - t                    # leading slab dims at stage t
        slab = (maxw,) * k + (ms, n)
        out_shapes.append(jax.ShapeDtypeStruct((L, 2) + slab, x.dtype))
        out_shapes.append(jax.ShapeDtypeStruct((L, 2) + slab, x.dtype))
        if t < nd - 1:
            out_shapes.append(
                jax.ShapeDtypeStruct((L,) + slab, x.dtype))

    out, *_ = pl.pallas_call(
        functools.partial(_torus_rs_kernel, ctx, axes, sizes, ms, n),
        name="reduce_scatter_torus",
        out_shape=tuple(out_shapes),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),) * len(out_shapes),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((L,)),          # per-lane send
            pltpu.SemaphoreType.DMA((nd, L, 2)),    # staging slots
            pltpu.SemaphoreType.REGULAR((nd * L,)),  # per-stage acks
        ],
        compiler_params=comm_compiler_params(ctx.collective_id, world),
        interpret=default_interpret(ctx.interpret),
    )(xr.reshape(sizes + (L, ms, n)))
    out = out.reshape(L * ms, n)
    if pad:
        out = out[:m]
    return unpad_lanes(out, n_orig)


# ---------------------------------------------------------------------------
# Fused torus AG-GEMM / GEMM-RS (all torus axes drive the overlap)
# ---------------------------------------------------------------------------

def _ag_gemm_torus_kernel(ctx, axes, sizes, ms, n, k,
                          x_ref, b_ref, g_ref, out_ref,
                          local_sems, send_sems, phase_sems):
    """Arrival-order consumer over the multi-lane torus AG: every
    piece (local, phase-p slab) is matmul'ed against the resident B
    shard as soon as its semaphore fires, while the next pieces ride
    all 2·nd ICI links — the torus analogue of
    `allgather_gemm._ag_gemm_fused_kernel`."""
    nd = len(sizes)
    scheds = lane_schedules(nd)
    L = len(scheds)
    w = sizes
    pos = tuple(jax.lax.axis_index(a) for a in axes)

    def mm(cell, q):
        emit_matmul(g_ref.at[cell + (q,)], b_ref, out_ref.at[cell + (q,)],
                    m=ms, n=n, k=k, config=ctx.gemm)

    def consume_local():
        for q in range(L):
            mm(pos, q)

    def consume_piece(q, p, c):
        sched = scheds[q]
        ring_ax = sched[p][0]
        gathered = [sched[j][0] for j in range(p)]
        for combo in itertools.product(
                *[range(w[ax]) for ax in gathered]):
            cell = list(pos)
            cell[ring_ax] = c
            for ax, i in zip(gathered, combo):
                cell[ax] = i
            mm(tuple(cell), q)

    _emit_torus_ag(ctx, axes, sizes, x_ref, g_ref, local_sems,
                   send_sems, phase_sems, consume_local=consume_local,
                   consume_piece=consume_piece)


def ag_gemm_torus(a_shard, b, ctx: TorusContext,
                  return_gathered: bool = False):
    """C = all_gather_torus(a) @ b with the gather and the GEMM fused
    in one kernel: pieces are consumed in arrival order while later
    pieces ride all 2·nd ICI links (reference: the consumer-side
    swizzle of `allgather_gemm.py:211-216`, lifted to the torus the
    way `low_latency_allgather.py:345-400` lifts push-1d to
    push-2d/3d)."""
    world = ctx.world_size
    m, k = a_shard.shape
    k2, n = b.shape
    assert k == k2, (a_shard.shape, b.shape)

    axes, sizes = ctx.active()
    if world <= 1 or len(axes) <= 1:
        # Degenerate torus: the single-axis fused ring is the right
        # algorithm (and handles world == 1 itself).
        from triton_distributed_tpu.kernels.allgather_gemm import (
            AllGatherGEMMContext, ag_gemm)
        ax = axes[0] if axes else ctx.axes[0]
        return ag_gemm(a_shard, b, AllGatherGEMMContext(
            axis=ax, world_size=world, gemm=ctx.gemm,
            collective_id=ctx.collective_id, interpret=ctx.interpret,
            straggler=ctx.straggler,
            for_correctness=ctx.for_correctness),
            return_gathered)

    # Honor ctx.method (explicit "xla", or the auto crossover on the
    # gathered payload): below the crossover — or when the user forces
    # the fallback — run the XLA composition.
    if ctx.resolve_method(m * k * a_shard.dtype.itemsize) == "xla":
        a_full = jax.lax.all_gather(a_shard, ctx.axes, tiled=True)
        out = jnp.dot(a_full, b, preferred_element_type=jnp.float32
                      ).astype(a_shard.dtype)
        return (out, a_full) if return_gathered else out

    nd = len(sizes)
    L = 2 * nd
    # Pad to L sublane-aligned pieces (sliced back below), and
    # lane-align BOTH GEMM dims: K (contraction — a cols + b rows)
    # and N (b cols — the out/gathered slabs are rank-4+ sliced
    # blocks, same Mosaic lane rule as the collectives).
    k_orig, n_orig = k, n
    a_shard, b, k = pad_contraction_lanes(a_shard, b)
    b, _ = pad_lanes(b)
    n = b.shape[1]
    ms = round_up_rows(pl.cdiv(m, L), a_shard.dtype)
    mL = L * ms
    a_p = (a_shard if mL == m
           else jnp.pad(a_shard, ((0, mL - m), (0, 0))))
    maxw = max(sizes)

    gathered, out = pl.pallas_call(
        functools.partial(_ag_gemm_torus_kernel, ctx, axes, sizes,
                          ms, n, k),
        name="ag_gemm_torus",
        out_shape=(
            jax.ShapeDtypeStruct(sizes + (L, ms, k), a_shard.dtype),
            jax.ShapeDtypeStruct(sizes + (L, ms, n), a_shard.dtype),
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),) * 2,
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((L,)),           # local copies
            pltpu.SemaphoreType.DMA((L,)),           # per-lane send
            pltpu.SemaphoreType.DMA((nd, L, maxw)),  # per-phase arrivals
        ],
        compiler_params=comm_compiler_params(ctx.collective_id, world),
        cost_estimate=pl.CostEstimate(
            flops=2 * world * mL * n * k,
            bytes_accessed=(world * mL * k + k * n
                            + world * mL * n) * a_shard.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=default_interpret(ctx.interpret),
    )(a_p.reshape(L, ms, k), b)

    out = out.reshape(world, mL, n)
    if mL != m:
        out = out[:, :m]
    out = unpad_lanes(out, n_orig).reshape(world * m, n_orig)
    if return_gathered:
        g = gathered.reshape(world, mL, k)
        if mL != m:
            g = g[:, :m]
        g = unpad_lanes(g, k_orig)
        return out, g.reshape(world * m, k_orig)
    return out


def gemm_rs_torus(a, b, ctx: TorusContext):
    """reduce_scatter_torus(a @ b): the partial GEMM (B streamed once)
    composed with the multi-lane torus reduce-scatter.  XLA overlaps
    the matmul's tail with the kernel's entry; the RS itself drives
    all 2·nd ICI links."""
    from triton_distributed_tpu.kernels.matmul import matmul

    world = ctx.world_size
    axes, sizes = ctx.active()
    if world <= 1 or len(axes) <= 1:
        from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
            GEMMReduceScatterContext, gemm_rs)
        ax = axes[0] if axes else ctx.axes[0]
        return gemm_rs(a, b, GEMMReduceScatterContext(
            axis=ax, world_size=world, gemm=ctx.gemm,
            collective_id=ctx.collective_id, interpret=ctx.interpret,
            straggler=ctx.straggler,
            for_correctness=ctx.for_correctness))
    mt, _ = a.shape
    n = b.shape[1]
    if ctx.resolve_method(mt // world * n * a.dtype.itemsize) == "xla":
        partial = jnp.dot(a, b, preferred_element_type=jnp.float32)
        return jax.lax.psum_scatter(
            partial.reshape(world, mt // world, n), ctx.axes,
            scatter_dimension=0, tiled=False).astype(a.dtype)
    partial = matmul(a, b, config=ctx.gemm, interpret=ctx.interpret)
    return reduce_scatter_torus(partial, ctx)


def all_reduce_torus(x, ctx: TorusContext):
    """Sum per-device partials over ALL torus axes: the canonical
    RS -> AG composition, each stage the multi-lane torus schedule —
    all 2·nd ICI links busy through both phases (completes the torus
    method family alongside AG and RS).

    Input (inside shard_map over the axes): (m, n) partials; output:
    the full reduced (m, n), replicated.
    """
    world = ctx.world_size
    if world <= 1:
        return x
    method = ctx.resolve_method(x.size * x.dtype.itemsize // world)
    if method == "xla":
        # The non-XLA path composes reduce_scatter_torus +
        # all_gather_torus, which emit their own events — only the
        # directly-run XLA collective is recorded here (no double
        # counting).
        from triton_distributed_tpu.observability import (
            record_collective)
        _axes, _sizes = ctx.active()
        record_collective("all_reduce_torus", axis=ctx.axes,
                          world=world, method=method, shape=x.shape,
                          dtype=x.dtype,
                          payload_bytes=x.size * x.dtype.itemsize,
                          sizes=_sizes if len(_sizes) > 1 else None,
                          hops="torus" if len(_sizes) > 1 else "ring",
                          axes=_axes)
        return jax.lax.psum(x, ctx.axes)
    m, n = x.shape
    pad = (-m) % world
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    # Distinct id for the second kernel: RS and AG run sequentially in
    # one program (same convention as allreduce.py's RING compose) —
    # derived UNCONDITIONALLY, so a user-supplied id also gets a
    # distinct AG-stage id instead of silently sharing one.
    ag_ctx = dataclasses.replace(
        ctx, collective_id=_paired_ag_id(ctx.collective_id))
    chunk = reduce_scatter_torus(xp, ctx)          # (mp / world, n)
    full = all_gather_torus(chunk, ag_ctx)         # (mp, n)
    return full[:m] if pad else full


# ---------------------------------------------------------------------------
# Comm-sanitizer registration (analysis.registry; docs/analysis.md).
# ---------------------------------------------------------------------------

from triton_distributed_tpu.analysis.registry import (  # noqa: E402
    KernelSpec,
    RefSpec,
    SemSpec,
    register_comm_kernel,
)


def _torus_ctx(axis_sizes):
    if len(axis_sizes) < 2:
        raise ValueError("torus kernels need a multi-axis mesh")
    axes = tuple(axis_sizes)
    sizes = tuple(axis_sizes[a] for a in axes)
    ctx = TorusContext(axes=axes, sizes=sizes)
    return ctx, axes, sizes


_TORUS_MESHES = ({"x": 2, "y": 2}, {"x": 2, "y": 4},
                 {"x": 2, "y": 2, "z": 2})


@register_comm_kernel("torus.allgather", meshes=_TORUS_MESHES)
def _analysis_torus_ag(axis_sizes):
    ctx, axes, sizes = _torus_ctx(axis_sizes)
    nd = len(sizes)
    L = 2 * nd
    ms, n = 8, 128
    maxw = max(sizes)
    return KernelSpec(
        name="torus.allgather",
        body=functools.partial(_torus_ag_kernel, ctx, axes, sizes),
        axis_sizes=axis_sizes,
        refs=[RefSpec("x", (L, ms, n), jnp.float32),
              RefSpec("o", sizes + (L, ms, n), jnp.float32)],
        sems=[SemSpec("local", (L,)), SemSpec("send", (L,)),
              SemSpec("phase", (nd, L, maxw))],
    )


@register_comm_kernel("torus.reduce_scatter", meshes=_TORUS_MESHES)
def _analysis_torus_rs(axis_sizes):
    ctx, axes, sizes = _torus_ctx(axis_sizes)
    nd = len(sizes)
    L = 2 * nd
    ms, n = 8, 128
    maxw = max(sizes)
    refs = [RefSpec("x", sizes + (L, ms, n), jnp.float32),
            RefSpec("out", (L, ms, n), jnp.float32)]
    # Per stage t: the (s_t, a_t) staging pair, plus mid_t for t<nd-1
    # (mirrors the out_shape list in `reduce_scatter_torus`).
    for t in range(nd):
        slab = (maxw,) * (nd - 1 - t) + (ms, n)
        refs.append(RefSpec(f"s{t}", (L, 2) + slab, jnp.float32))
        refs.append(RefSpec(f"a{t}", (L, 2) + slab, jnp.float32))
        if t < nd - 1:
            refs.append(RefSpec(f"mid{t}", (L,) + slab, jnp.float32))
    return KernelSpec(
        name="torus.reduce_scatter",
        body=functools.partial(_torus_rs_kernel, ctx, axes, sizes, ms, n),
        axis_sizes=axis_sizes,
        refs=refs,
        sems=[SemSpec("send", (L,)), SemSpec("stage", (nd, L, 2)),
              SemSpec("ack", (nd * L,))],
    )


@register_comm_kernel("torus.ag_gemm", meshes=({"x": 2, "y": 2},))
def _analysis_torus_ag_gemm(axis_sizes):
    ctx, axes, sizes = _torus_ctx(axis_sizes)
    nd = len(sizes)
    L = 2 * nd
    ms, n, k = 8, 128, 128
    maxw = max(sizes)
    return KernelSpec(
        name="torus.ag_gemm",
        body=functools.partial(_ag_gemm_torus_kernel, ctx, axes, sizes,
                               ms, n, k),
        axis_sizes=axis_sizes,
        refs=[RefSpec("x", (L, ms, k), jnp.bfloat16),
              RefSpec("b", (k, n), jnp.bfloat16),
              RefSpec("g", sizes + (L, ms, k), jnp.bfloat16),
              RefSpec("out", sizes + (L, ms, n), jnp.bfloat16)],
        sems=[SemSpec("local", (L,)), SemSpec("send", (L,)),
              SemSpec("phase", (nd, L, maxw))],
    )
