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


def emit_chunked_matmul(a_ref, b_ref, o_ref, *, chunks, mc, n, k,
                        config: Optional[MatmulConfig] = None):
    """O[w] = A[w] @ B for all ``chunks`` row-chunks in ONE pipeline.

    ``a_ref``: (chunks, mc, k), ``o_ref``: (chunks, mc, n) HBM refs.

    For the latency regime (decode: mc is a handful of rows) the cost
    of a GEMM is streaming B from HBM, not FLOPs — so unlike a loop of
    per-chunk `emit_matmul` (which would re-read B per chunk, a
    ``chunks``× bandwidth blowup) every B block is fetched exactly
    once and multiplied against *all* chunks while resident in VMEM.
    The accumulator holds all chunks of one N block: chunks*mc rows,
    small by the regime's definition.  Reference analogue: the
    low-latency AG + single GEMM composition
    (`kernels/nvidia/low_latency_allgather.py:48-217`).
    """
    cfg = (config or MatmulConfig()).resolve(chunks * mc, n, k)
    nk = pl.cdiv(k, cfg.block_k)
    bn = min(cfg.block_n, n)

    def inner(a_blk, b_blk, o_blk, acc_ref):
        kk = pl.program_id(1)

        @pl.when(kk == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        a2 = a_blk[:].reshape(chunks * mc, a_blk.shape[-1])
        acc_ref[:] += jnp.dot(a2, b_blk[:],
                              preferred_element_type=jnp.float32)

        @pl.when(kk == nk - 1)
        def _():
            o_blk[:] = acc_ref[:].reshape(o_blk.shape).astype(o_blk.dtype)

    def run(acc_ref):
        pipeline = pltpu.emit_pipeline(
            functools.partial(inner, acc_ref=acc_ref),
            grid=(pl.cdiv(n, bn), nk),
            in_specs=[
                pl.BlockSpec((chunks, mc, cfg.block_k),
                             lambda j, kk: (0, 0, kk)),
                pl.BlockSpec((cfg.block_k, bn), lambda j, kk: (kk, j)),
            ],
            out_specs=[
                pl.BlockSpec((chunks, mc, bn), lambda j, kk: (0, 0, j)),
            ],
        )
        pipeline(a_ref, b_ref, o_ref)

    pl.run_scoped(
        run,
        acc_ref=pltpu.VMEM((chunks * mc, bn), jnp.float32),
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
