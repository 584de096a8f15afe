"""Tiled MXU matmul building blocks.

The reference's GEMMs are Triton tile kernels (persistent TMA consumers,
`kernels/nvidia/allgather_gemm.py:146-286`).  The TPU equivalents here:

- :func:`matmul` — standalone Pallas blocked matmul (pallas_call grid);
- :func:`emit_matmul` — an *inner pipeline* over HBM refs, for use
  inside larger overlap kernels (`pltpu.emit_pipeline` plays the role
  of the persistent kernel's software pipelining: double-buffered
  HBM→VMEM DMA feeding the MXU).

Both accumulate in float32 regardless of input dtype.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.analysis import resources
from triton_distributed_tpu.utils.platform import (
    SCOPED_VMEM_LIMIT as MATMUL_VMEM_LIMIT,
    default_interpret,
)


def _pick_block(dim: int, preferred: int, align: int) -> int:
    """Largest block <= preferred that divides dim, multiple of align
    when possible."""
    if dim <= preferred:
        return dim
    # Mosaic requires sublane/lane blocks to be align-multiples (or the
    # whole dim); a misaligned `preferred` would make every candidate
    # below misaligned too, so round it down first.
    preferred = max(align, preferred // align * align)
    for b in range(preferred, align - 1, -align):
        if dim % b == 0:
            return b
    return dim  # fall back to un-tiled


@dataclasses.dataclass(frozen=True)
class MatmulConfig:
    """Block sizes for the MXU pipeline.

    Defaults were tuned on a real v5e at the flagship shape
    (M=4096, K=N=7168 bf16): large blocks minimise HBM re-reads —
    the A panel is re-fetched ceil(n/block_n) times and B
    ceil(m/block_m) times — and with the raised scoped-VMEM limit
    (see ``MATMUL_VMEM_LIMIT``) the f32 accumulator can afford to be
    MBs large.  Measured ~180 TFLOP/s vs XLA's ~190 at that shape
    (both ≈ peak); `contextual_autotune` over `matmul_config_space`
    picks the winner per shape.
    """

    block_m: int = 1024
    block_n: int = 2048
    block_k: int = 1024

    def resolve(self, m: int, n: int, k: int) -> "MatmulConfig":
        return MatmulConfig(
            block_m=_pick_block(m, self.block_m, 8),
            block_n=_pick_block(n, self.block_n, 128),
            block_k=_pick_block(k, self.block_k, 128),
        )




def matmul_config_space(m: int, n: int, k: int):
    """Candidate configs for `contextual_autotune` (the reference's
    `triton.Config` spaces, `allgather_gemm.py:383-402`)."""
    cands = [
        MatmulConfig(1024, 2048, 1024),
        MatmulConfig(1024, 2048, 512),
        MatmulConfig(2048, 1024, 1024),
        MatmulConfig(1024, 3584, 1024),
        MatmulConfig(2048, 3584, 512),
        MatmulConfig(1024, 1024, 512),
        MatmulConfig(512, 1024, 512),
        MatmulConfig(512, 512, 1024),
        MatmulConfig(256, 512, 512),
    ]
    seen, out = set(), []
    for c in cands:
        r = c.resolve(m, n, k)
        if r not in seen:
            seen.add(r)
            out.append(r)
    return out


def _matmul_kernel(nk: int, a_ref, b_ref, o_ref, acc_ref):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(a_ref[:], b_ref[:],
                          preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def matmul(a, b, config: Optional[MatmulConfig] = None,
           out_dtype=None, interpret: Optional[bool] = None):
    """C[m,n] = A[m,k] @ B[k,n], blocked for the MXU."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    cfg = (config or MatmulConfig()).resolve(m, n, k)
    nk = pl.cdiv(k, cfg.block_k)
    grid = (pl.cdiv(m, cfg.block_m), pl.cdiv(n, cfg.block_n), nk)
    # Shared-estimator pre-flight: a config whose working set cannot
    # fit fails here with a readable message, not deep inside Mosaic.
    # Hardware-only (same convention as flash_attention's lane guard):
    # interpret mode has no VMEM ceiling.
    interp = default_interpret(interpret)
    if interp is False:
        resources.check_vmem_fit(
            "matmul",
            [((cfg.block_m, cfg.block_k), a.dtype),
             ((cfg.block_k, cfg.block_n), b.dtype),
             ((cfg.block_m, cfg.block_n), out_dtype)],
            [((min(cfg.block_m, m), min(cfg.block_n, n)),
              jnp.float32)],
            limit=MATMUL_VMEM_LIMIT)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, nk),
        name="matmul",
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pl.GridSpec(
            grid=grid,
            in_specs=[
                pl.BlockSpec((cfg.block_m, cfg.block_k),
                             lambda i, j, kk: (i, kk),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((cfg.block_k, cfg.block_n),
                             lambda i, j, kk: (kk, j),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((cfg.block_m, cfg.block_n),
                                   lambda i, j, kk: (i, j),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((min(cfg.block_m, m), min(cfg.block_n, n)),
                           jnp.float32)
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=MATMUL_VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * k,
            bytes_accessed=(m * k + k * n) * a.dtype.itemsize
            + m * n * jnp.dtype(out_dtype).itemsize,
            transcendentals=0,
        ),
        interpret=interp,
    )(a, b)


def emit_matmul(a_ref, b_ref, o_ref, *, m, n, k,
                config: Optional[MatmulConfig] = None):
    """Run a pipelined matmul over HBM refs from inside a kernel body.

    ``a_ref``: (m, k), ``b_ref``: (k, n), ``o_ref``: (m, n) — all HBM/ANY
    refs (may be `.at[...]` views of larger buffers).
    """
    cfg = (config or MatmulConfig()).resolve(m, n, k)
    nk = pl.cdiv(k, cfg.block_k)

    def inner(a_blk, b_blk, o_blk, acc_ref):
        kk = pl.program_id(2)

        @pl.when(kk == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        acc_ref[:] += jnp.dot(a_blk[:], b_blk[:],
                              preferred_element_type=jnp.float32)

        @pl.when(kk == nk - 1)
        def _():
            o_blk[:] = acc_ref[:].astype(o_blk.dtype)

    def run(acc_ref):
        pipeline = pltpu.emit_pipeline(
            functools.partial(inner, acc_ref=acc_ref),
            grid=(pl.cdiv(m, cfg.block_m), pl.cdiv(n, cfg.block_n), nk),
            in_specs=[
                pl.BlockSpec((cfg.block_m, cfg.block_k),
                             lambda i, j, kk: (i, kk)),
                pl.BlockSpec((cfg.block_k, cfg.block_n),
                             lambda i, j, kk: (kk, j)),
            ],
            out_specs=[
                pl.BlockSpec((cfg.block_m, cfg.block_n),
                             lambda i, j, kk: (i, j)),
            ],
        )
        pipeline(a_ref, b_ref, o_ref)

    pl.run_scoped(
        run,
        acc_ref=pltpu.VMEM((min(cfg.block_m, m), min(cfg.block_n, n)),
                           jnp.float32),
    )


#: The few-rows stream's DMA granule: an N block of `MatmulConfig` is
#: fetched and multiplied in column slices of at most this many bytes.
#: Measured on one v5e chip at the four-chip cell's decode shapes
#: (PERF.md section 5, PR 34): 4 MB blocks stream at 77-89% of the HBM's
#: peak, 1 MB slices at 85-93% — the first slice arrives sooner and
#: the last one's multiply, which nothing hides, is a quarter as long.
_STREAM_BLOCK_BYTES = 1 << 20
#: How far the stream's DMAs run ahead of the MXU: enough bytes in
#: flight (~15 us of HBM time) that a fused kernel's talk with its
#: peers passes behind them, in at most this many blocks.
_STREAM_AHEAD_BYTES = 12 << 20
_STREAM_MAX_AHEAD = 12


def _stream_plan(cfg: "MatmulConfig", n: int, k: int, itemsize: int):
    """(bn, ahead) of the few-rows stream for resolved blocks ``cfg``:
    the column slice of an N block that one DMA fetches, and how many
    blocks are in flight ahead of the one being multiplied.  Only N is
    sliced — block_k, and with it the order of every sum over K, is
    `MatmulConfig.resolve`'s — so the results do not depend on it."""
    bn = min(cfg.block_n, n)
    while cfg.block_k * bn * itemsize > _STREAM_BLOCK_BYTES and bn % 256 == 0:
        bn //= 2
    total = pl.cdiv(n, bn) * pl.cdiv(k, cfg.block_k)
    ahead = max(2, _STREAM_AHEAD_BYTES // (cfg.block_k * bn * itemsize))
    return bn, min(ahead, _STREAM_MAX_AHEAD, total)


def emit_chunked_matmul(a_ref, b_ref, o_ref=None, *, chunks, mc, n, k,
                        config: Optional[MatmulConfig] = None,
                        while_prefetching=None, write_block=None,
                        resident=()):
    """O[w] = A[w] @ B for all ``chunks`` row-chunks, B streamed ONCE.

    ``a_ref``: (chunks, mc, k), ``b_ref``: (k, n), ``o_ref``:
    (chunks, mc, n) HBM refs.

    For the latency regime (decode: mc is a handful of rows) the cost
    of a GEMM is streaming B from HBM, not FLOPs — so unlike a loop of
    per-chunk `emit_matmul` (which would re-read B per chunk, a
    ``chunks``x bandwidth blowup) every B block is fetched exactly
    once and multiplied against *all* chunks while resident in VMEM.
    The accumulator holds all chunks of one N block: chunks*mc rows,
    small by the regime's definition.  Reference analogue: the
    low-latency AG + single GEMM composition
    (`kernels/nvidia/low_latency_allgather.py:48-217`).

    The stream is written by hand (`_stream_plan`: B fetched in ~1 MB
    column slices, a dozen of them in flight ahead of the MXU) rather
    than with `emit_pipeline`, because the fused ``ll`` kernels need
    two things a pipeline has no hook for — at a 10-60 us call every
    microsecond the B stream stands still while the kernel talks to a
    peer is a tenth of the call:

    - ``while_prefetching()`` runs AFTER the first B blocks are in
      flight and BEFORE A is read: communication that produces
      ``a_ref`` (the all-gather) goes here and hides behind them.
    - ``write_block(j, blk, cols, sem)`` replaces the write of N block
      ``j`` to ``o_ref``: ``blk`` is the finished (chunks, mc, bn)
      block in VMEM, ``cols`` its column slice of the output.  It
      must START DMAs out of ``blk`` that move the block's bytes in
      total, all signalling ``sem`` (local copies as their semaphore,
      remote puts as their send semaphore), and wait for none: the
      stream waits before it reuses the buffer, and drains the rest
      before it returns — so block j travels while block j+1 streams.

    block_k and the order over K are `MatmulConfig.resolve`'s, the
    accumulation is float32 with one cast at the end of a block: the
    results are those of the pipelined form this replaces, bit for bit
    (tier-1 holds it; on the chip at the cell's four shapes, PR 34).

    The stream keeps all of A, ``ahead + 1`` B blocks, two output
    blocks and the accumulator in VMEM — more than the pipelined form
    did, and growing with the rows — so its working set, plus the
    ``resident`` (shape, dtype) buffers the calling kernel holds in
    VMEM beside it, is checked against ``MATMUL_VMEM_LIMIT`` here,
    with a readable message instead of a Mosaic abort.
    """
    cfg = (config or MatmulConfig()).resolve(chunks * mc, n, k)
    bk = cfg.block_k
    bn, ahead = _stream_plan(cfg, n, k, jnp.dtype(b_ref.dtype).itemsize)
    nk, nj = pl.cdiv(k, bk), pl.cdiv(n, bn)
    total = nj * nk
    depth = ahead + 1
    dtype = a_ref.dtype

    if write_block is None:
        def write_block(j, blk, cols, sem):
            del j
            pltpu.make_async_copy(blk, o_ref.at[:, :, cols], sem).start()

    def run(a_buf, b_buf, o_buf, acc_ref, a_sem, b_sems, o_sems):
        def b_copy(j, kk, slot):
            return pltpu.make_async_copy(
                b_ref.at[pl.ds(kk * bk, bk),
                         pl.ds(pl.multiple_of(j * bn, bn), bn)],
                b_buf.at[slot], b_sems.at[slot])

        def o_wait(slot):
            # Drains one block's bytes, whatever DMAs carried them.
            pltpu.make_async_copy(o_buf.at[slot], o_buf.at[slot],
                                  o_sems.at[slot]).wait()

        for t in range(ahead):
            b_copy(t // nk, t % nk, t).start()
        if while_prefetching is not None:
            while_prefetching()
        a_copies = [
            pltpu.make_async_copy(a_ref.at[:, :, pl.ds(kk * bk, bk)],
                                  a_buf.at[kk], a_sem)
            for kk in range(nk)]
        for cp in a_copies:
            cp.start()
        for cp in a_copies:
            cp.wait()

        def n_block(j, carry):
            for kk in range(nk):
                t = j * nk + kk
                slot = jax.lax.rem(t, depth)
                b_copy(j, kk, slot).wait()

                @pl.when(t + ahead < total)
                def _():
                    b_copy(j + (kk + ahead) // nk, (kk + ahead) % nk,
                           jax.lax.rem(t + ahead, depth)).start()

                if kk == 0:
                    acc_ref[...] = jnp.zeros_like(acc_ref)
                a2 = a_buf[kk].reshape(chunks * mc, bk)
                acc_ref[...] += jnp.dot(
                    a2, b_buf[slot], preferred_element_type=jnp.float32)

            oslot = jax.lax.rem(j, 2)

            @pl.when(j >= 2)
            def _():
                o_wait(oslot)

            o_buf[oslot] = acc_ref[...].reshape(chunks, mc, bn).astype(dtype)
            write_block(j, o_buf.at[oslot],
                        pl.ds(pl.multiple_of(j * bn, bn), bn),
                        o_sems.at[oslot])
            return carry

        jax.lax.fori_loop(0, nj, n_block, 0)
        for j in range(max(nj - 2, 0), nj):
            o_wait(j % 2)

    buffers = dict(
        a_buf=((nk, chunks, mc, bk), dtype),
        b_buf=((depth, bk, bn), b_ref.dtype),
        o_buf=((2, chunks, mc, bn), dtype),
        acc_ref=((chunks * mc, bn), jnp.float32),
    )
    resources.check_vmem_fit(
        "emit_chunked_matmul", [],
        list(buffers.values()) + list(resident),
        limit=MATMUL_VMEM_LIMIT, double_buffer=False)
    pl.run_scoped(
        run,
        **{name: pltpu.VMEM(shape, dt)
           for name, (shape, dt) in buffers.items()},
        a_sem=pltpu.SemaphoreType.DMA(()),
        b_sems=pltpu.SemaphoreType.DMA((depth,)),
        o_sems=pltpu.SemaphoreType.DMA((2,)),
    )


def round_up_rows(m: int, dtype) -> int:
    """Pad row counts to the Mosaic sublane multiple for the dtype.

    Native tiling is (8, 128) for 4-byte, (16, 128) for 2-byte and
    (32, 128) for 1-byte elements — int8 rows must pad to 32 or the
    ring kernels' small-m shards force relayouts (or fail to compile)
    on hardware.  The per-dtype multiple comes from the shared
    resource estimator so the tiling the guards enforce is the tiling
    the sanitizer checks."""
    min_rows = resources.sublane_rows(jnp.dtype(dtype))
    return (m + min_rows - 1) // min_rows * min_rows


def pad_lanes(x, multiple: int = resources.LANE):
    """Zero-pad the LAST dim to a 128 multiple and return (padded,
    original_width).

    Mosaic's `memref_slice` requires the lane (last) extent of any
    rank-3+ sliced block to be a 128 multiple — even when the slice
    covers the whole dim (topology-compile catch at n=192 on the
    torus AG slabs).  Collective hosts pad payload columns on entry
    and slice them back on exit."""
    n = x.shape[-1]
    pad = (-n) % multiple
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x, n


def unpad_lanes(x, n_orig: int):
    """Inverse of :func:`pad_lanes`: slice the last dim back to the
    original width.  Unconditional — when nothing was padded the
    slice is a jit no-op, so call sites need no guard."""
    return x[..., :n_orig]


def pad_contraction_lanes(a, b, axis_a: int = -1, axis_b: int = 0):
    """Zero-pad the shared contraction dim of ``a`` (its ``axis_a``)
    and ``b`` (its ``axis_b``) to the 128-lane multiple.

    Mosaic rejects lane-dim slices of rank-3+ blocks that aren't
    128-aligned (caught by the topology-compile suite at
    k_local = 64), so every kernel that streams rank-3+ A chunks pads
    K on the host.  Zero-padding the contraction dim is exact: zero
    columns of A times zero rows of B contribute nothing.

    Returns (a, b, k_padded)."""
    k = a.shape[axis_a]
    pad = (-k) % 128
    if pad:
        pa = [(0, 0)] * a.ndim
        pa[axis_a if axis_a >= 0 else a.ndim + axis_a] = (0, pad)
        pb = [(0, 0)] * b.ndim
        pb[axis_b] = (0, pad)
        a = jnp.pad(a, pa)
        b = jnp.pad(b, pb)
    return a, b, k + pad


# ---------------------------------------------------------------------------
# Resource-sanitizer registration (analysis.resources).
# ---------------------------------------------------------------------------


@resources.register_resource_kernel("matmul.blocked")
def _resource_matmul():
    records = []
    for dtype in (jnp.float32, jnp.bfloat16):
        a = jnp.zeros((512, 1024), dtype)
        b = jnp.zeros((1024, 512), dtype)
        with resources.capture_pallas_calls() as recs:
            matmul(a, b, interpret=False)
        records.extend(recs)
    return records
