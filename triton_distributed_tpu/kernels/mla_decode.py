"""Latent-attention (MLA) decode over a paged pool of latent rows.

A latent layer caches ONE row a token: the normalised ``lat``-wide
latent, then the rotated key all heads share, zero-padded to a lane
multiple (`models.kv_cache`: pool ``(P, 1, page, R)``).  In the
absorbed form of the attention the row is the key AND the value of
every head:

    scores[h, t] = q[h] . row[t]            (q = [q_nope W^K | q_rope | 0])
    out[h]       = sum_t softmax(scores)[h, t] * row[t, :lat]

so a block of rows is copied from HBM once and used twice.  The walk
is `flash_decode_paged`'s: grid over batch rows, an in-kernel loop over
blocks of pages gathered by async copies through the scalar-prefetched
page table, bounded by each row's live length — nothing at or past a
row's length is read.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.analysis.resources import (
    ManualBlocks,
    capture_pallas_calls,
    register_resource_kernel,
)
from triton_distributed_tpu.kernels.flash_attention import zero_oob_rows
from triton_distributed_tpu.kernels.flash_decode import (
    _PAGED_BLOCK_ROWS,
    _PAGED_KV_VMEM_BYTES,
    NEG_INF,
)
from triton_distributed_tpu.utils.platform import default_interpret

def _pages_per_block(t: int, ps: int, r: int, dtype) -> int:
    """Pages one block gathers: `flash_decode_paged`'s rows a block,
    fewer where the two (rows, R) gather slots would pass its VMEM
    budget, at least one, at most the table's width."""
    rows = _PAGED_KV_VMEM_BYTES // (2 * r * jnp.dtype(dtype).itemsize)
    return max(1, min(t, min(rows, _PAGED_BLOCK_ROWS) // ps))


def _mla_decode_kernel(n, ps, lat, scale, kvlen_ref, ptab_ref, q_ref,
                       pool_hbm, o_ref, buf, sem, m_scr, l_scr, acc_scr):
    """Grid: (B,).  One grid step is one batch row: all its heads, and
    only the pages below its length.  ``buf`` is (2, n, page, R): block
    ``blk``'s live pages land in slot ``blk % 2`` while the other slot
    is computed on; only the last block is masked."""
    bb = pl.program_id(0)
    r = buf.shape[3]
    rows = n * ps
    kv_len = kvlen_ref[bb]
    npages = pl.cdiv(kv_len, ps)
    nblk = pl.cdiv(npages, n)

    def gather(blk, slot, wait):
        def page(i, _):
            # A wait needs the copy's shape and semaphore only.
            src = 0 if wait else ptab_ref[bb, blk * n + i]
            copy = pltpu.make_async_copy(
                pool_hbm.at[src, 0], buf.at[slot, i], sem.at[slot])
            if wait:
                copy.wait()
            else:
                copy.start()
        jax.lax.fori_loop(0, jnp.minimum(n, npages - blk * n), page,
                          None)

    def update(blk, slot, masked):
        q = q_ref[0]                                    # (H, R)
        kv = buf[slot].reshape(rows, r)                 # K and V
        if masked:
            # 0 x NaN: rows no copy wrote must not reach the sums.
            kv = zero_oob_rows(kv, blk, rows, kv_len)
        s = jax.lax.dot_general(
            q, kv, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (H, rows)
        if masked:
            col_live = blk * rows + jax.lax.broadcasted_iota(
                jnp.int32, (1, rows), 1) < kv_len
            s = jnp.where(col_live, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1,
                                                  keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :lat],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(nblk > 0)
    def _():
        gather(0, 0, wait=False)

    def block(blk, _):
        slot = blk % 2

        @pl.when(blk + 1 < nblk)
        def _():
            gather(blk + 1, 1 - slot, wait=False)

        gather(blk, slot, wait=True)

        @pl.when(blk + 1 < nblk)
        def _():
            update(blk, slot, masked=False)

        @pl.when(blk + 1 == nblk)
        def _():
            update(blk, slot, masked=True)

    jax.lax.fori_loop(0, nblk, block, None)

    l = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def mla_decode_paged(q, pool, page_table, kv_len, *, lat: int,
                     scale: float, interpret: Optional[bool] = None):
    """Single-position latent attention over a PAGED pool of latent
    rows.

    q: (B, H, R) — per head ``[q_nope W^K (lat) | q_rope | 0 pad]``;
    pool: (P, 1, page, R) — ``[latent (lat) | rotated key | 0 pad]`` a
    token (`models.kv_cache.PagedKVCache`, latent layout); page_table:
    (B, T) int32; kv_len: (B,) int32 true filled lengths.  Returns
    (B, H, lat): the softmax-weighted LATENT of each head, for the
    caller's ``W^V`` to expand.

    ``lat`` and ``R`` are lane multiples (128).  The pad columns of the
    pool must be finite (they are written as zeros); q's are zero, so
    they add nothing to a score.  A row with ``kv_len`` 0 returns
    zeros.  The program is the same for every batch: lengths are read
    in the kernel, not traced.
    """
    b, heads, r = q.shape
    _, one, ps, r2 = pool.shape
    assert one == 1 and r2 == r, (q.shape, pool.shape)
    assert lat % 128 == 0 and r % 128 == 0 and lat <= r, (lat, r)
    # whole sublane tiles of heads (16 rows of a 2-byte type): a zero
    # query row scores 0 everywhere and is cut off below
    h = -(-heads // 16) * 16
    if h != heads:
        q = jnp.pad(q, ((0, 0), (0, h - heads), (0, 0)))
    t = page_table.shape[1]
    n = _pages_per_block(t, ps, r, pool.dtype)

    kernel = functools.partial(_mla_decode_kernel, n, ps, lat, scale)
    # What the resource sanitizer bounds in place of a BlockSpec index
    # map: the pages `gather` copies for row `bb`.
    kernel.manual_blocks = {1: ManualBlocks(
        (1, 1, ps, r),
        lambda bb, kvlen, ptab: [
            (ptab[bb, j], 0, 0, 0)
            for j in range(-(-int(kvlen[bb]) // ps))])}

    def row_spec(width):
        return pl.BlockSpec((1, h, width), lambda bb, *pre: (bb, 0, 0),
                            memory_space=pltpu.VMEM)

    out = pl.pallas_call(
        kernel,
        name="mla_decode_paged",
        out_shape=jax.ShapeDtypeStruct((b, h, lat), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[row_spec(r), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row_spec(lat),
            scratch_shapes=[
                pltpu.VMEM((2, n, ps, r), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, lat), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        cost_estimate=pl.CostEstimate(
            # The worst case, T full pages a row: live lengths are not
            # known when tracing.
            flops=2 * b * h * t * ps * (r + lat),
            bytes_accessed=b * t * ps * r * pool.dtype.itemsize,
            transcendentals=b * h * t * ps,
        ),
        interpret=default_interpret(interpret),
    )(kv_len.astype(jnp.int32), page_table.astype(jnp.int32), q, pool)
    return out[:, :heads]


def mla_decode_reference(q, pool, page_table, kv_len, *, lat: int,
                         scale: float):
    """`mla_decode_paged` in plain float32 `jax.numpy` (tests)."""
    b = q.shape[0]
    rows = pool[page_table][:, :, 0]                # (B, T, page, R)
    rows = rows.reshape(b, -1, rows.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bhr,btr->bht", q.astype(jnp.float32), rows) * scale
    live = jnp.arange(rows.shape[1])[None, None, :] < kv_len[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
    p = jnp.where(live, p, 0.0)
    return jnp.einsum("bht,btl->bhl", p, rows[..., :lat])


@register_resource_kernel("mla_decode.paged")
def _resource_mla_paged():
    import numpy as np

    b, h, lat, r = 2, 20, 512, 640
    p, ps, t = 9, 16, 4
    table = np.zeros((b, t), np.int32)
    table[0] = (3, 5, 0, 0)       # short row: NULL (trash) tail
    table[1] = (8, 1, 2, 7)       # full row, permuted physical pages
    with capture_pallas_calls() as records:
        mla_decode_paged(
            jnp.zeros((b, h, r), jnp.bfloat16),
            jnp.zeros((p, 1, ps, r), jnp.bfloat16), jnp.asarray(table),
            jnp.asarray([20, t * ps], jnp.int32), lat=lat,
            scale=256 ** -0.5, interpret=False)
    return records
