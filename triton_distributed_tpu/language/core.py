"""Core in-kernel primitives: one-sided DMA, signals, waits, barriers.

Reference parity (cited file:line are in /root/reference):
- `dl.wait` / `dl.notify` / `dl.consume_token`
  (`python/triton_dist/language/distributed_ops.py:57-109`): lowered on
  NVIDIA to PTX spin loops and `st.release`/`nvshmemx_signal_op`
  (`lib/Conversion/TritonDistributedToLLVM/NVIDIA/DistributedOpToLLVM.cpp:146-342`).
  Here they are Pallas semaphore ops: TPU DMA hardware counts bytes into
  semaphores and Mosaic emits the spin.
- `libshmem_device.putmem_nbi_block` / `putmem_signal_nbi_block`
  (`python/triton_dist/language/extra/libshmem_device.py`): here
  :func:`put_nbi` / :func:`put_signal_nbi` built on
  `pltpu.make_async_remote_copy`, which is precisely a one-sided
  put-with-signal (recv semaphore on the target).

Design note (TPU-first): there is no device-initiated *get* on ICI —
remote reads are expressed as flipped puts (the owner pushes).  This is
the same discipline the reference's fast paths use anyway (push-mode
allgather, put-based all_to_all), so no capability is lost.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# SPMD identity
# ---------------------------------------------------------------------------

def rank(axis: str):
    """This device's index along a mesh axis (reference: `dl.rank`,
    `distributed_ops.py:84`)."""
    return jax.lax.axis_index(axis)


def num_ranks(axis: str) -> int:
    """World size along a mesh axis (reference: `dl.num_ranks`)."""
    return jax.lax.axis_size(axis)


# Team-API parity aliases: a mesh axis IS a team, so the team variants
# are the same functions (docs/device_language.md).
team_my_pe = rank
team_n_pes = num_ranks


def peer_id(axis: str, index):
    """Address of the device at ``index`` along ``axis``, keeping this
    device's coordinates on every other mesh axis.

    All kernels address peers this way (MESH-coordinate dict) rather
    than with flat LOGICAL ids: an axis-local index is only a valid
    logical id on a 1-axis mesh, and silently targets the wrong chip on
    any multi-axis mesh (dp×tp, dcn×ici, ...).  Reference analogue:
    NVSHMEM PE ids are team-relative for the same reason
    (`libshmem_device.py` team APIs).
    """
    return {axis: index}


# ---------------------------------------------------------------------------
# One-sided data movement
# ---------------------------------------------------------------------------

def put_nbi(src_ref, dst_ref, send_sem, recv_sem, device_id,
            device_id_type=pltpu.DeviceIdType.MESH):
    """Non-blocking one-sided put: start an async remote DMA and return
    its descriptor (call ``.wait_send()`` / ``.wait_recv()`` later).

    Reference: `libshmem_device.putmem_nbi_block`.  The returned copy
    descriptor doubles as the "signal": TPU remote DMA always signals
    the destination's ``recv_sem`` on delivery, i.e. every put is a
    `putmem_signal_nbi_block`.
    """
    rdma = pltpu.make_async_remote_copy(
        src_ref=src_ref,
        dst_ref=dst_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=device_id,
        device_id_type=device_id_type,
    )
    rdma.start()
    return rdma


def put(src_ref, dst_ref, send_sem, recv_sem, device_id,
        device_id_type=pltpu.DeviceIdType.MESH):
    """Blocking put (reference: `libshmem_device.putmem_block`):
    start + wait-send.  NOTE: waits only for local completion (source
    reusable), not remote delivery — matching SHMEM put semantics."""
    rdma = put_nbi(src_ref, dst_ref, send_sem, recv_sem, device_id,
                   device_id_type)
    rdma.wait_send()
    return rdma


def local_copy(src_ref, dst_ref, sem):
    """Async local DMA (HBM<->HBM/VMEM), blocking until done.
    Reference analogue: the copy-engine `Tensor.copy_` path
    (`kernels/nvidia/allgather.py:81-139`)."""
    cp = pltpu.make_async_copy(src_ref, dst_ref, sem)
    cp.start()
    cp.wait()


def wait_recv(ref, recv_sem):
    """Wait until a put of ``ref.shape`` bytes has landed (drains the
    recv semaphore).  Reference: the consumer side of
    `putmem_signal` + `signal_wait_until`."""
    pltpu.make_async_copy(ref, ref, recv_sem).wait()


def wait_send(ref, send_sem):
    """Wait until a started put of ``ref.shape`` bytes has left (drains
    the send semaphore)."""
    pltpu.make_async_copy(ref, ref, send_sem).wait()


# ---------------------------------------------------------------------------
# Signals (flags) — the reference's signal/notify/wait triplet
# ---------------------------------------------------------------------------

def notify(sem, device_id=None, inc: int = 1,
           device_id_type=pltpu.DeviceIdType.MESH):
    """Set/advance a signal, optionally on a remote device.

    Reference: `dl.notify` (`distributed_ops.py:103`, lowered at
    `DistributedOpToLLVM.cpp:233-342`).  ``sem`` must be a REGULAR
    semaphore ref; with ``device_id`` the signal rides ICI to the
    peer's semaphore (the nvshmemx_signal_op path), without it the
    signal is chip-local (the st.release path).
    """
    if device_id is None:
        pltpu.semaphore_signal(sem, inc=inc)
    else:
        pltpu.semaphore_signal(sem, inc=inc, device_id=device_id,
                               device_id_type=device_id_type)


# `signal_op` with SIGNAL_SET has no TPU analogue (semaphores are
# counters); SIGNAL_ADD is notify().  Alias for parity with
# `libshmem_device.signal_op(..., NVSHMEM_SIGNAL_ADD, ...)`.
signal_op = notify
remote_sem_signal = notify


def signal_wait_until(sem, value: int):
    """Spin until the semaphore reaches ``value``, consuming it.

    Reference: `libshmem_device.signal_wait_until(sig, NVSHMEM_CMP_GE,
    value)`.  NOTE consuming semantics: TPU semaphore waits *decrement*
    by ``value`` — kernels must re-arm by convention (every wait is
    matched by exactly the signals it consumes; see the double-buffer
    phase pattern in kernels/low_latency_all_to_all.py).
    """
    pltpu.semaphore_wait(sem, value)


def wait(sem, value: int = 1):
    """`dl.wait(barrier_ptrs, n, scope, semantic)` analogue
    (`distributed_ops.py:57`): block until ``sem`` has accumulated
    ``value`` signals, then consume them.  Returns a token to thread
    through :func:`consume_token`."""
    pltpu.semaphore_wait(sem, value)
    return ()


def consume_token(value, token):
    """Tie a value's availability to a completed wait.

    Reference: `dl.consume_token` (`distributed_ops.py:74`), a pure
    dataflow edge erased at lowering
    (`DistributedOpToLLVM.cpp:221-231`).  In Pallas, program order of
    semaphore ops inside a kernel is already preserved by Mosaic, but
    XLA-level code motion across the boundary is prevented with an
    optimization barrier; use this when mixing waits with reads of
    DMA-written buffers in the same basic block.
    """
    del token
    return jax.lax.optimization_barrier(value)


# ---------------------------------------------------------------------------
# Barriers
# ---------------------------------------------------------------------------

def barrier_all(axis: str, sem=None):
    """All-device barrier over a mesh axis, usable inside a kernel.

    Reference: `libshmem_device.barrier_all` / the atomic-CAS intra-node
    barrier (`kernels/nvidia/common_ops.py:135-207`).  Implementation:
    every device signals every other device's barrier semaphore, then
    waits for world-1 signals.  Uses the global Mosaic barrier
    semaphore unless an explicit REGULAR sem ref is passed.

    Kernels using this must set a ``collective_id`` in CompilerParams.
    """
    barrier_all_signal(axis, sem)
    barrier_all_wait(axis, sem)


def barrier_all_signal(axis: str, sem=None):
    """First half of :func:`barrier_all`: tell every peer this device
    has arrived.  Does not block.

    Issued at kernel entry with :func:`barrier_all_wait` immediately
    before this device's first remote put, the pair is the SECOND
    allowed form of the entry barrier (the first: :func:`entry_barrier`).
    It is as safe: the barrier exists so that no device puts into a
    peer that is still in the previous program; a put is only issued
    after the wait, and the wait only returns once EVERY peer has
    signalled from inside this program.  What moves is that purely
    local work (a weight stream, a local copy) runs between the
    halves, so the barrier's latency and the skew between chips hide
    behind it.  A second launch cannot confuse the counts: a device
    leaves the program only after every peer's data has arrived, and
    a peer sends only after its own wait returned."""
    n = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    bsem = pltpu.get_barrier_semaphore() if sem is None else sem

    def body(i, _):
        peer = jax.lax.rem(me + i, n)
        pltpu.semaphore_signal(bsem, inc=1, device_id=peer_id(axis, peer),
                               device_id_type=pltpu.DeviceIdType.MESH)
        return 0

    jax.lax.fori_loop(1, n, body, 0)


def barrier_all_wait(axis: str, sem=None):
    """Second half of :func:`barrier_all`: block until every peer has
    signalled its arrival (consumes the n-1 signals)."""
    bsem = pltpu.get_barrier_semaphore() if sem is None else sem
    pltpu.semaphore_wait(bsem, jax.lax.axis_size(axis) - 1)


# NVSHMEM `sync_all` parity: barrier without a DMA-drain (quiet); see
# docs/device_language.md for the barrier-vs-sync distinction.
sync_all = barrier_all


def entry_barrier(axis: str, world: int, neighbors_only: bool = False):
    """Barrier with the peers that will DMA into this device's output
    buffers, issued at kernel entry before the first remote put.

    Why: on real hardware a fast device can start its RDMA while a
    slow peer is still executing the *previous* program, whose live
    intermediates may alias the (reused) destination buffer —
    timing-dependent corruption.  The canonical Pallas distributed
    pattern barriers at kernel entry (reference analogue: the
    `barrier_all_on_stream` reset before every overlap op,
    `kernels/nvidia/allgather_gemm.py:101-117`).

    ``world`` is the static axis size: at 1 this is a no-op so
    single-device programs need no collective_id.  ``neighbors_only``
    is enough for ring kernels (only left/right write into us).
    """
    if world <= 1:
        return
    if neighbors_only:
        barrier_neighbors(axis)
    else:
        barrier_all(axis)


def emit_broadcast(axis: str, world: int, root, src_ref, dst_ref,
                   local_sem, send_sem, recv_sem):
    """Broadcast ``src_ref`` from ``root`` into every device's
    ``dst_ref`` (reference: `libshmem_device.broadcast/broadcastmem`).

    No ICI multicast exists (the NVLS path has no analogue), so the
    root pushes explicitly to each peer — the same fan-out the
    one-shot allgather uses, restricted to one source.  ``root`` may
    be a traced scalar.  Callers barrier beforehand if dst_ref may
    still be read by the previous program (see entry_barrier).
    """
    me = jax.lax.axis_index(axis)

    @pl.when(me == root)
    def _():
        local_copy(src_ref, dst_ref, local_sem)

        def send(i, _):
            peer = jax.lax.rem(root + i, world)
            pltpu.make_async_remote_copy(
                src_ref=src_ref, dst_ref=dst_ref,
                send_sem=send_sem, recv_sem=recv_sem,
                device_id=peer_id(axis, peer),
                device_id_type=pltpu.DeviceIdType.MESH,
            ).start()
            return 0

        jax.lax.fori_loop(1, world, send, 0, unroll=True)

        def drain(i, _):
            wait_send(src_ref, send_sem)
            return 0

        jax.lax.fori_loop(1, world, drain, 0, unroll=True)

    @pl.when(me != root)
    def _():
        wait_recv(dst_ref, recv_sem)


# ---------------------------------------------------------------------------
# Fault injection (straggler / race-widening delays)
# ---------------------------------------------------------------------------

def _flat_rank(axis):
    """Rank along ``axis``; for a SEQUENCE of axes, the flattened
    row-major rank over all of them (multi-axis torus kernels straggle
    by flat rank so one knob addresses any lane/quadrant)."""
    if isinstance(axis, str):
        return jax.lax.axis_index(axis)
    flat = None
    for a in axis:
        idx = jax.lax.axis_index(a)
        flat = idx if flat is None else flat * jax.lax.axis_size(a) + idx
    return flat


def maybe_straggle(axis, straggler):
    """Delay one rank before it communicates (reference
    `_run_straggler`, `kernels/nvidia/allreduce.py:146`; stress use
    `test/stress/stress_test_ag_gemm.py:119-121`).

    ``axis``: one mesh axis name, or a sequence of axis names — then
    ``rank`` addresses the row-major flattened rank over them (the
    multi-axis torus kernels' convention).
    ``straggler``: None or (rank, cycles).  On TPU the rank spins
    ``cycles`` ns (`pl.delay`); in interpret mode it sleeps the
    simulated device's host thread — a *real* wall-clock skew, so the
    cross-thread semaphore machinery sees genuinely late arrivals.
    """
    if straggler is None:
        return
    rank, cycles = straggler
    from triton_distributed_tpu.utils.platform import is_tpu

    me = _flat_rank(axis)
    if is_tpu():
        @pl.when(me == rank)
        def _():
            pl.delay(cycles)
    else:
        _host_sleep(me == rank, cycles)


def correctness_delay(axis, enabled: bool, cycles: int = 100_000):
    """Rank-staggered delay before communication on EVERY rank — the
    reference's `for_correctness` knob (`allgather_gemm.py:506-508`):
    widen race windows so ordering bugs surface deterministically
    instead of once a week.  ``axis`` as in :func:`maybe_straggle`."""
    if not enabled:
        return
    from triton_distributed_tpu.utils.platform import is_tpu

    my = _flat_rank(axis)
    if is_tpu():
        pl.delay((my + 1) * cycles)
    else:
        _host_sleep(my >= 0, (my + 1) * cycles)


def _host_sleep(cond, cycles):
    """Interpret-mode delay: sleep this simulated device's thread
    (ordered io_callback so it is neither elided nor reordered)."""
    import numpy as np

    from jax.experimental import io_callback

    def _sleep(c, ns):
        if bool(c):
            import time
            time.sleep(min(float(ns) / 1e9, 0.05))
        return np.int32(0)

    io_callback(_sleep, jax.ShapeDtypeStruct((), jnp.int32), cond,
                jnp.asarray(cycles, jnp.int32), ordered=True)


def barrier_neighbors(axis: str):
    """Cheap ring barrier with left/right neighbors only (enough to
    order ring-collective phases)."""
    n = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    left = jax.lax.rem(me - 1 + n, n)
    right = jax.lax.rem(me + 1, n)
    bsem = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(bsem, inc=1, device_id=peer_id(axis, left),
                           device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_signal(bsem, inc=1, device_id=peer_id(axis, right),
                           device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(bsem, 2)
