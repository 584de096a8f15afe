"""Flash-Decode: split-KV GQA decode attention, single-chip and
sequence-parallel distributed.

Reference: `python/triton_dist/kernels/nvidia/flash_decode.py` (1161
LoC) — split-kv kernel (`:130`), intra-rank combine (`:393`),
inter-rank LSE-weighted combine (`:482`), distributed hosts
(`:763-1160`); layer `SpGQAFlashDecodeAttention`
(`layers/nvidia/sp_flash_decode_layer.py:83-183`).

TPU re-design:
- single chip: one Pallas kernel, grid over KV splits, online-softmax
  partials (acc, m, l) carried in VMEM, masked by the true cache
  length (static shapes; `kv_len` rides in SMEM).
- paged (`flash_decode_paged`): the same mathematics over a page
  pool; grid over batch rows, an in-kernel loop over blocks of pages
  gathered by async copies through the page table, bounded by each
  row's live length.
- distributed (SP): every rank runs the local kernel over its KV shard
  emitting (out, lse); the tiny partials are exchanged with the
  one-shot push allgather (the reference's LL-allgather of (out, lse))
  and combined with LSE weights — `sp_flash_decode`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu import collective_ids as cids

from triton_distributed_tpu.analysis.resources import (
    MOSAIC_DEFAULT_VMEM_LIMIT,
    ManualBlocks,
    block_bytes,
)
from triton_distributed_tpu.kernels.flash_attention import zero_oob_rows
from triton_distributed_tpu.utils.platform import default_interpret

NEG_INF = -1e30


def _decode_kernel(nk: int, s_cache: int, scale: float, block_k: int,
                   quantized: bool, compute_dtype,
                   kvlen_ref, q_ref, k_ref, v_ref, *rest):
    """Grid: (B, Hkv, nk).  Blocks: q (1, 1, G, D) — all grouped query
    heads of one kv head; k/v (1, 1, bk, D).

    With ``quantized`` the caches are int8 with per-token f32 scales
    (blocks (1, 1, bk)); both dequant multiplies are folded into the
    tiny (G, bk) tiles — the K scale onto the scores, the V scale onto
    p — so int8 halves the KV bandwidth (the decode bottleneck) at
    ~zero extra VPU cost on the big (bk, D) tiles."""
    if quantized:
        ks_ref, vs_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
        ks_ref = vs_ref = None
    ki = pl.program_id(2)
    bb = pl.program_id(0)

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                        # (G, D)
    k = k_ref[0, 0]                        # (bk, D)
    v = v_ref[0, 0]
    if quantized:
        # int8 → compute dtype is exact; the scales follow below.
        k = k.astype(compute_dtype)
        v = v.astype(compute_dtype)
    if s_cache % block_k != 0:
        # Rows in [kv_len, s_cache) are real allocated cache (finite,
        # handled by the mask alone); only rows past the cache end are
        # uninitialized and need the shared ragged-tail guard.
        v = zero_oob_rows(v, ki, block_k, s_cache)

    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale     # (G, bk)
    if quantized:
        # Dequant K on the (G, bk) scores: one row-broadcast multiply
        # (the scale block is laid out (1, bk) — lane-aligned).
        s = s * ks_ref[0, 0]

    kv_len = kvlen_ref[bb]
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(k_pos < kv_len, s, NEG_INF)

    m_prev = m_scr[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
    if quantized:
        # Dequant V on p (masked cols have p = 0, so a garbage scale
        # in the ragged tail must be zeroed or 0 × NaN poisons p).
        # The l sum above uses the unscaled softmax weights.
        vs = vs_ref[0, 0]                               # (1, bk)
        if s_cache % block_k != 0:
            col = (ki * block_k
                   + jax.lax.broadcasted_iota(jnp.int32, vs.shape, 1))
            vs = jnp.where(col < s_cache, vs, 0)
        p = p * vs
    acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[:] = m_new

    @pl.when(ki == nk - 1)
    def _():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)
        # log-sum-exp for cross-rank combine, (G, 1)
        lse_ref[0, 0] = m_scr[:] + jnp.log(l)


def quantize_kv(k, v):
    """Per-token symmetric int8 quantization of a KV cache (amax over
    D): returns (k_q, v_q int8, k_scale, v_scale f32 (B, Hkv, S)).
    Halves decode's KV bandwidth — the decode bottleneck — and the
    cache's HBM footprint."""
    from triton_distributed_tpu.kernels.quantized import quantize_sym

    k_q, ks = quantize_sym(k, axis=3)
    v_q, vs = quantize_sym(v, axis=3)
    return k_q, v_q, ks, vs


def flash_decode_config_space(s: int):
    """block_k candidates for the contextual autotuner — the KV block
    length trades DMA granularity against grid bookkeeping (a hand
    sweep on the chip before the ledger picked 4096; the tuner
    re-derives it per shape and persists it)."""
    out = [bk for bk in (1024, 2048, 4096, 8192) if bk <= s]
    return out or [s]


def flash_decode_tunable(q, k_cache, v_cache, kv_len, *, config, **kw):
    """`flash_decode` under the autotuner calling convention
    (``config`` = block_k).  Module-level so the tuner's disk key is
    shared between benches and AOT builders."""
    return flash_decode(q, k_cache, v_cache, kv_len, block_k=config,
                        **kw)


def flash_decode(q, k_cache, v_cache, kv_len, *,
                 k_scale=None, v_scale=None,
                 scale: Optional[float] = None, block_k: int = 4096,
                 interpret: Optional[bool] = None):
    """Single-position GQA decode.

    q: (B, H, D); k_cache/v_cache: (B, Hkv, S, D); kv_len: (B,) int32
    (true filled length, ≤ S).  Returns (out (B, H, D), lse (B, H)).

    With ``k_scale``/``v_scale`` ((B, Hkv, S) f32, from `quantize_kv`)
    the caches are int8: half the KV streaming bytes, dequantized
    in-kernel on the tiny (G, bk) tiles.
    """
    b, h, d = q.shape
    _, hkv, s, _ = k_cache.shape
    assert h % hkv == 0
    g = h // hkv
    quantized = k_scale is not None
    assert quantized == (v_scale is not None)
    if quantized:
        assert k_cache.dtype == jnp.int8 and v_cache.dtype == jnp.int8
    scale = scale if scale is not None else d ** -0.5
    bk = min(block_k, s)
    nk = pl.cdiv(s, bk)

    def kv_spec():
        return pl.BlockSpec((1, 1, bk, d),
                            lambda bb, hh, ki, *pre: (bb, hh, ki, 0),
                            memory_space=pltpu.VMEM)

    in_specs = [
        pl.BlockSpec((1, 1, g, d),
                     lambda bb, hh, ki, *pre: (bb, hh, 0, 0),
                     memory_space=pltpu.VMEM),
        kv_spec(),
        kv_spec(),
    ]
    operands = [q.reshape(b, hkv, g, d), k_cache, v_cache]
    if quantized:
        # (B, Hkv, 1, S) layout: the (1, 1, 1, bk) block's trailing
        # (1, bk) shape is Mosaic-legal AND already the broadcast
        # shape the kernel multiplies against the (G, bk) tiles.
        sspec = pl.BlockSpec((1, 1, 1, bk),
                             lambda bb, hh, ki, *pre: (bb, hh, 0, ki),
                             memory_space=pltpu.VMEM)
        in_specs += [sspec, sspec]
        operands += [k_scale.astype(jnp.float32).reshape(b, hkv, 1, s),
                     v_scale.astype(jnp.float32).reshape(b, hkv, 1, s)]

    out, lse = pl.pallas_call(
        functools.partial(_decode_kernel, nk, s, scale, bk, quantized,
                          q.dtype),
        name="flash_decode",
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
            jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, nk),
            in_specs=in_specs,
            out_specs=(
                pl.BlockSpec((1, 1, g, d),
                             lambda bb, hh, ki, *pre: (bb, hh, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, g, 1),
                             lambda bb, hh, ki, *pre: (bb, hh, 0, 0),
                             memory_space=pltpu.VMEM),
            ),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, d), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            # KV streaming dominates; flops are negligible at M=G.
            flops=4 * b * h * s * d,
            bytes_accessed=2 * b * hkv * s * d * k_cache.dtype.itemsize,
            transcendentals=b * h * s,
        ),
        interpret=default_interpret(interpret),
    )(kv_len.astype(jnp.int32), *operands)
    return out.reshape(b, h, d), lse.reshape(b, h)



#: Rows of KV one online-softmax update of the paged kernel covers at
#: most (pages per block x page size).
_PAGED_BLOCK_ROWS = 512

#: VMEM the paged kernel's gather buffers (K and V, two slots each)
#: may take together: a quarter of Mosaic's default scoped limit.
_PAGED_KV_VMEM_BYTES = MOSAIC_DEFAULT_VMEM_LIMIT // 4


def _pages_per_block(t: int, hkv: int, ps: int, d: int, dtype) -> int:
    """Pages one block of the paged kernel gathers: as many as give
    `_PAGED_BLOCK_ROWS` rows, fewer where the four (Hkv, rows, D)
    buffers would pass `_PAGED_KV_VMEM_BYTES`, at least one, at most
    the table's width."""
    rows = _PAGED_KV_VMEM_BYTES // (4 * block_bytes((hkv, d), dtype))
    return max(1, min(t, min(rows, _PAGED_BLOCK_ROWS) // ps))


def _paged_decode_kernel(n, ps, scale, quantized, compute_dtype, window,
                         front_hidden, kvlen_ref, ptab_ref, q_ref, k_hbm,
                         v_hbm, *rest):
    """Grid: (B,).  One grid step is one row: all its KV heads, and
    only the pages below its length — with ``window``, only the pages
    that hold one of its last ``window`` positions.

    The pools stay in HBM.  A block is ``n`` consecutive logical pages:
    page ``ptab[b, j]`` — all KV heads of it, contiguous in the pool —
    is copied into slot ``blk % 2`` of the (2, n, Hkv, page, D) VMEM
    buffers while the other slot is computed on.  The loop runs
    ``cdiv(kv_len[b], n * page)`` times; within a block only pages
    below ``cdiv(kv_len[b], page)`` are copied, and only the last
    block is masked (its tail holds whatever the slot held before).
    The online-softmax update is `_decode_kernel`'s over ``n * page``
    rows: f32 running max / sum / accumulator per KV head, p in the
    value dtype for the PV product.

    ``window`` (a static int; None: the program it always was): the
    row's query stands at position ``kv_len - 1`` and sees the keys at
    and above ``kv_len - window``.  The loop starts at the block that
    holds that position, copies no page below it (the table may map
    those anywhere: their owner gave them back), and masks the first
    block as it masks the last.

    ``front_hidden`` (a static int; None: the program it always was):
    the FIRST HALF of each KV head's query rows sees no key at or above
    ``kv_len - front_hidden``; the second half sees them all.  Every
    block that holds such a key is masked as the last one is.

    With ``quantized`` the per-token scales arrive as dense
    (1, Hkv, 1, T * page) rows (gathered by the wrapper) and are
    folded onto the (G, rows) tiles as in the dense kernel."""
    if quantized:
        ks_ref, vs_ref, o_ref, lse_ref = rest[:4]
    else:
        o_ref, lse_ref = rest[:2]
    kbuf, vbuf, sem, m_scr, l_scr, acc_scr = rest[-6:]
    bb = pl.program_id(0)
    hkv, d = kbuf.shape[2], kbuf.shape[4]
    rows = n * ps
    kv_len = kvlen_ref[bb]
    npages = pl.cdiv(kv_len, ps)
    nblk = pl.cdiv(npages, n)
    # the first position, page and block the row's query sees
    first = jnp.maximum(kv_len - window, 0) if window else 0
    page0 = first // ps
    blk0 = page0 // n

    def gather(blk, slot, wait):
        """Start (or wait for) the copies of block ``blk``'s live
        pages into ``slot``."""
        def page(i, _):
            # A wait needs the copy's shape and semaphore only.
            src = 0 if wait else ptab_ref[bb, blk * n + i]
            for a, (hbm, buf) in enumerate(((k_hbm, kbuf),
                                            (v_hbm, vbuf))):
                copy = pltpu.make_async_copy(
                    hbm.at[src], buf.at[slot, i], sem.at[a, slot])
                if wait:
                    copy.wait()
                else:
                    copy.start()
        jax.lax.fori_loop(jnp.maximum(page0 - blk * n, 0) if window else 0,
                          jnp.minimum(n, npages - blk * n), page, None)

    def update(blk, slot, masked):
        if quantized:
            cols = pl.ds(pl.multiple_of(blk * rows, rows), rows)
        if masked:
            col = blk * rows + jax.lax.broadcasted_iota(
                jnp.int32, (1, rows), 1)
            col_live = col < kv_len
            if window:
                col_live = jnp.logical_and(col_live, col >= first)
            s_live = col_live
            if front_hidden:
                g = q_ref.shape[2]
                back = jax.lax.broadcasted_iota(
                    jnp.int32, (g, rows), 0) >= g // 2
                s_live = jnp.logical_and(col_live, jnp.logical_or(
                    back, col < kv_len - front_hidden))
        # Unrolled over the KV heads: their chains are independent, and
        # a loop would leave each matmul's latency exposed.
        for h in range(hkv):
            q = q_ref[0, h]                             # (G, D)
            k = kbuf[slot, :, h].reshape(rows, d)
            v = vbuf[slot, :, h].reshape(rows, d)
            if quantized:
                k = k.astype(compute_dtype)
                v = v.astype(compute_dtype)
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if quantized:
                s = s * ks_ref[0, h, :, cols]           # (1, rows)
                vs = vs_ref[0, h, :, cols]
            if masked:
                s = jnp.where(s_live, s, NEG_INF)
                # 0 x NaN: rows no copy wrote must not reach the sums.
                v = zero_oob_rows(v, blk, rows, kv_len)
                if window:
                    v = jnp.where(
                        blk * rows + jax.lax.broadcasted_iota(
                            jnp.int32, v.shape, 0) >= first, v, 0)
                if quantized:
                    vs = jnp.where(col_live, vs, 0)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1,
                                                  keepdims=True)
            if quantized:
                p = p * vs
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(nblk > 0)
    def _():
        gather(blk0, blk0 % 2, wait=False)

    def block(blk, _):
        slot = blk % 2

        @pl.when(blk + 1 < nblk)
        def _():
            gather(blk + 1, 1 - slot, wait=False)

        gather(blk, slot, wait=True)
        edge = blk + 1 == nblk
        if window:
            edge = jnp.logical_or(edge, blk == blk0)
        if front_hidden:
            edge = jnp.logical_or(
                edge, (blk + 1) * rows > kv_len - front_hidden)

        @pl.when(jnp.logical_not(edge))
        def _():
            update(blk, slot, masked=False)

        @pl.when(edge)
        def _():
            update(blk, slot, masked=True)

    jax.lax.fori_loop(blk0, nblk, block, None)

    l = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
    # log-sum-exp for cross-rank combine, (Hkv, G, 1)
    lse_ref[0] = m_scr[...] + jnp.log(l)


def flash_decode_paged(q, k_pool, v_pool, page_table, kv_len, *,
                       k_scale=None, v_scale=None,
                       scale: Optional[float] = None,
                       window: Optional[int] = None,
                       front_hidden: Optional[int] = None,
                       name: str = "flash_decode_paged",
                       interpret: Optional[bool] = None):
    """Single-position GQA decode over a PAGED KV pool
    (`models.kv_cache.PagedKVCache` layout).

    q: (B, H, D); k_pool/v_pool: (P, Hkv, page, D) — ONE pool of
    fixed-size pages shared by all sequences; page_table: (B, T) int32
    mapping logical KV block j of row b to a physical page; kv_len:
    (B,) int32 true filled lengths.  Returns (out (B, H, D),
    lse (B, H)).

    The H query rows of a batch row are ANY queries that see the same
    ``kv_len[b]`` keys, ``H / Hkv`` consecutive ones to a KV head: one
    position's heads (plain decode: ``G`` a KV head), or — a model that
    generates by blocks, `layers.tp_attn.TPAttention.block_paged` — the
    heads of the ``2 n`` positions of a finished block and the block in
    flight, laid out ``(Hkv, 2, G * n)``, whose K/V the caller has
    written into the pages first.  Without a ``window`` or a
    ``front_hidden`` nothing is masked inside a row's length.  ``G`` is
    ANY whole number (7 query heads a KV head is served as it is): the
    ``(Hkv, G, D)`` block is the whole of its axes, nothing here pads
    or masks the group, and where ``G`` is no multiple of the 8-row
    sublane tile Mosaic pads the tile's rows in VMEM — rows no output
    row is read from; no head is dropped or doubled.

    ``front_hidden`` (keys, static): the last ``front_hidden`` keys of
    every row are hidden from the FIRST HALF of each KV head's query
    rows (``H / Hkv`` even); the second half sees all ``kv_len[b]``.
    That is the block-causal mask over two blocks in ONE read of the
    pages: the finished block's queries in front, which must not see
    the block in flight behind them.  A query row left with no key
    returns finite numbers that mean nothing.  None compiles to the
    program without the argument.

    ``window`` (tokens, static; a sliding-window layer): row b's query
    stands at position ``kv_len[b] - 1`` and sees key j iff
    ``kv_len[b] - window <= j < kv_len[b]``.  The block loop starts at
    the block that holds the first such key, no page below it is read
    (a window layer's pool gives those back: the table may say
    `NULL_PAGE` there) and the first block is masked like the last.
    None compiles to the program without the argument.  ``name``: the
    kernel's name in a device trace (a window layer passes its own).

    The work follows each row's LIVE length, not the table's width:
    the grid is (B,), the pools stay in HBM, and each grid step loops
    over blocks of `_pages_per_block` pages — gathered through the
    scalar-prefetched table with one async copy a page (all KV heads
    of a page are contiguous), double-buffered, one online-softmax
    update of `flash_decode`'s mathematics a block — for
    ``cdiv(kv_len[b], pages * page)`` blocks.  No page at or beyond a
    row's length is read, so the table may map those anywhere
    (`NULL_PAGE`, a stale page); a row with ``kv_len`` 0 returns zeros
    and lse ~ -1e30.  The program is the same for every batch: the
    lengths are read in the kernel, not traced.

    With ``k_scale``/``v_scale`` ((P, Hkv, page) f32 pools) the KV
    pools are int8 — half the streaming bytes, dequantized in-kernel
    exactly as the dense path.  The scales (4 bytes a token) are
    gathered for the whole table by XLA ahead of the kernel.
    """
    b, h, d = q.shape
    _, hkv, ps, _ = k_pool.shape
    t = page_table.shape[1]
    assert h % hkv == 0
    g = h // hkv
    quantized = k_scale is not None
    assert quantized == (v_scale is not None)
    if quantized:
        assert k_pool.dtype == jnp.int8 and v_pool.dtype == jnp.int8
    scale = scale if scale is not None else d ** -0.5
    n = _pages_per_block(t, hkv, ps, d, k_pool.dtype)
    page_table = page_table.astype(jnp.int32)

    def row_spec(*tail):
        return pl.BlockSpec((1, hkv) + tail,
                            lambda bb, *pre: (bb, 0, 0, 0),
                            memory_space=pltpu.VMEM)

    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [row_spec(g, d), pool_spec, pool_spec]
    operands = [q.reshape(b, hkv, g, d), k_pool, v_pool]
    if quantized:
        # (B, Hkv, 1, T*page): the trailing (1, rows) slices are the
        # broadcast shape the kernel multiplies onto the (G, rows)
        # tiles.  Columns past a row's length are masked in-kernel.
        # Padded to whole blocks, so the last block's slice is in bounds.
        t_pad = pl.cdiv(t, n) * n

        def dense(sc):
            sc = sc.astype(jnp.float32)[page_table]     # (B,T,Hkv,ps)
            sc = jnp.pad(sc, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
            return sc.transpose(0, 2, 1, 3).reshape(b, hkv, 1,
                                                    t_pad * ps)

        in_specs += [row_spec(1, t_pad * ps)] * 2
        operands += [dense(k_scale), dense(v_scale)]

    window = int(window) if window else None
    front_hidden = int(front_hidden) if front_hidden else None
    assert not front_hidden or g % 2 == 0, (g, front_hidden)
    kernel = functools.partial(_paged_decode_kernel, n, ps, scale,
                               quantized, q.dtype, window, front_hidden)
    # What the resource sanitizer bounds in place of a BlockSpec index
    # map (`analysis.resources.ManualBlocks`): the pages `gather`
    # copies for row `bb`.
    pages = ManualBlocks(
        (1, hkv, ps, d),
        lambda bb, kvlen, ptab: [
            (ptab[bb, j], 0, 0, 0)
            for j in range(max(int(kvlen[bb]) - (window or 1 << 40), 0)
                           // ps, -(-int(kvlen[bb]) // ps))])
    kernel.manual_blocks = {1: pages, 2: pages}

    out, lse = pl.pallas_call(
        kernel,
        name=name,
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
            jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=in_specs,
            out_specs=(row_spec(g, d), row_spec(g, 1)),
            scratch_shapes=[
                pltpu.VMEM((2, n, hkv, ps, d), k_pool.dtype),
                pltpu.VMEM((2, n, hkv, ps, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hkv, g, 1), jnp.float32),
                pltpu.VMEM((hkv, g, 1), jnp.float32),
                pltpu.VMEM((hkv, g, d), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        cost_estimate=pl.CostEstimate(
            # The worst case — T full pages per row (the dense
            # kernel's bound at S=T*ps): the live lengths are not
            # known when tracing.
            flops=4 * b * h * t * ps * d,
            bytes_accessed=(2 * b * hkv * t * ps * d
                            * k_pool.dtype.itemsize),
            transcendentals=b * h * t * ps,
        ),
        interpret=default_interpret(interpret),
    )(kv_len.astype(jnp.int32), page_table, *operands)
    return out.reshape(b, h, d), lse.reshape(b, h)


def combine_partials(outs, lses):
    """LSE-weighted combine of per-shard decode partials (reference
    inter-rank combine kernel, `flash_decode.py:482`).

    outs: (R, B, H, D); lses: (R, B, H) → (B, H, D)."""
    m = jnp.max(lses, axis=0, keepdims=True)          # (1, B, H)
    w = jnp.exp(lses - m)                             # (R, B, H)
    denom = jnp.sum(w, axis=0)                        # (B, H)
    # An empty shard (lse ≈ -inf) may carry garbage partials — e.g. a
    # kv_len=0 rank whose kernel averaged uninitialized rows; 0 × NaN
    # would poison the sum.  Gate on the shard's own lse (NOT on the
    # relative weight w: when ALL shards are empty every w is exp(0)=1
    # and garbage would pass; NOT on finiteness: a live shard's
    # genuine NaN/Inf must still propagate rather than be silently
    # replaced by a finite wrong answer).
    outs = jnp.where((lses > NEG_INF / 2)[..., None], outs, 0)
    num = jnp.einsum("rbh,rbhd->bhd", w, outs.astype(jnp.float32))
    return (num / jnp.maximum(denom, 1e-30)[..., None]).astype(outs.dtype)


def _sp_gather_combine(op_name: str, out, lse, kv_len_local, q,
                       axis: str, collective_id: int,
                       interpret: Optional[bool]):
    """Shared distributed tail of both sp decode compositions: mask
    empty shards, allgather the packed (out, lse) payload, LSE-combine.

    The payload row is LANE-PADDED to a 128 multiple: Mosaic rejects
    DMA slices of rank-3 blocks whose last dim isn't tile-aligned
    (topology-compile catch at D+1 = 129).  The pad bytes are dead
    weight on a KB-scale latency-bound transfer — irrelevant, and far
    cheaper than a second AG for the 1-column lse."""
    from triton_distributed_tpu.kernels.allgather import (
        AllGatherContext, AllGatherMethod, all_gather)

    world = jax.lax.axis_size(axis)
    b, h, d = q.shape
    # Empty shards (kv_len 0) have lse = -inf ⇒ zero weight.
    lse = jnp.where(kv_len_local[:, None] > 0, lse, NEG_INF)

    # Marker event for the composition: the inner all_gather emits the
    # byte-carrying event (bytes_moved=0 here — no double counting on
    # the link counters), but doctor/flight views see the decode step
    # as one op with its collective id.
    from triton_distributed_tpu.observability import emit_kernel_event
    emit_kernel_event(op_name, kind="collective",
                      method="push_all", axis=axis, world=world,
                      shape=(b, h, d), dtype=q.dtype,
                      delegates="all_gather", hops="none")

    ag_ctx = AllGatherContext(axis=axis, world_size=world,
                              method=AllGatherMethod.PUSH_ALL,
                              collective_id=collective_id,
                              interpret=interpret)
    dp = d + 1 + ((-(d + 1)) % 128)
    payload = jnp.zeros((b * h, dp), jnp.float32)
    payload = payload.at[:, :d].set(
        out.astype(jnp.float32).reshape(b * h, d))
    payload = payload.at[:, d].set(lse.reshape(b * h))
    gathered = all_gather(payload, ag_ctx)            # (world*B*H, dp)
    gathered = gathered.reshape(world, b, h, dp)
    return combine_partials(gathered[..., :d],
                            gathered[..., d]).astype(q.dtype)


def sp_flash_decode(q, k_shard, v_shard, kv_len_local, axis: str, *,
                    k_scale=None, v_scale=None,
                    scale: Optional[float] = None, block_k: int = 4096,
                    collective_id: int = cids.FLASH_DECODE_AG,
                    interpret: Optional[bool] = None):
    """Sequence-parallel distributed flash-decode.  Call inside
    shard_map over `axis`; each rank holds a KV shard.

    q: (B, H, D) replicated; k/v_shard: (B, Hkv, S_loc, D);
    kv_len_local: (B,) tokens valid in this rank's shard.
    Returns (B, H, D) combined on every rank.

    Pipeline = reference's: local split-KV kernel → LL allgather of
    (out, lse) (KB-scale, latency-bound: one-shot push) → LSE combine.
    """
    out, lse = flash_decode(q, k_shard, v_shard, kv_len_local,
                            k_scale=k_scale, v_scale=v_scale,
                            scale=scale, block_k=block_k,
                            interpret=interpret)
    return _sp_gather_combine("sp_flash_decode", out, lse,
                              kv_len_local, q, axis, collective_id,
                              interpret)


def sp_flash_decode_paged(q, k_pool, v_pool, page_table, kv_len_local,
                          axis: str, *, k_scale=None, v_scale=None,
                          scale: Optional[float] = None,
                          collective_id: int = cids.FLASH_DECODE_AG,
                          interpret: Optional[bool] = None):
    """Sequence-parallel distributed decode over PAGED local pools:
    each rank holds a page pool + table covering its KV shard
    (`kv_len_local` tokens valid).  Same pipeline as
    `sp_flash_decode` — local paged split-KV kernel → one-shot push
    allgather of the KB-scale (out, lse) payload → LSE-weighted
    combine (shared `_sp_gather_combine` tail) — so the two differ
    only in the local kernel's KV addressing."""
    out, lse = flash_decode_paged(q, k_pool, v_pool, page_table,
                                  kv_len_local, k_scale=k_scale,
                                  v_scale=v_scale, scale=scale,
                                  interpret=interpret)
    return _sp_gather_combine("sp_flash_decode_paged", out, lse,
                              kv_len_local, q, axis, collective_id,
                              interpret)


# ---------------------------------------------------------------------------
# Comm-sanitizer registration (analysis.registry; docs/analysis.md).
# The decode kernel itself is pure compute; the distributed step is a
# one-shot push allgather of the packed (out, lse) payload under the
# FLASH_DECODE_AG collective id — register that footprint (the padded
# f32 payload row the composition actually ships).  The paged variant
# ships the identical payload (paging changes only local KV
# addressing), registered separately so a future divergence of either
# composition is swept on its own.
# ---------------------------------------------------------------------------

from triton_distributed_tpu.analysis.registry import (  # noqa: E402
    KernelSpec,
    RefSpec,
    SemSpec,
    register_comm_kernel,
    single_axis,
)


def _partials_ag_spec(name: str, axis_sizes):
    from triton_distributed_tpu.kernels.allgather import (
        _push_all_ag_kernel)

    axis, world = single_axis(axis_sizes)
    b, h, d = 1, 2, 64
    dp = d + 1 + ((-(d + 1)) % 128)   # lane-padded out+lse row
    return KernelSpec(
        name=name,
        body=functools.partial(_push_all_ag_kernel, axis, world, None,
                               False),
        axis_sizes=axis_sizes,
        refs=[RefSpec("payload", (b * h, dp), jnp.float32),
              RefSpec("gathered", (world, b * h, dp), jnp.float32)],
        sems=[SemSpec("local"), SemSpec("send"), SemSpec("recv", (world,))],
    )


@register_comm_kernel("flash_decode.partials_ag",
                      meshes=({"sp": 2}, {"sp": 4}))
def _analysis_flash_decode_ag(axis_sizes):
    return _partials_ag_spec("flash_decode.partials_ag", axis_sizes)


@register_comm_kernel("flash_decode.paged_partials_ag",
                      meshes=({"sp": 2}, {"sp": 4}))
def _analysis_flash_decode_paged_ag(axis_sizes):
    return _partials_ag_spec("flash_decode.paged_partials_ag",
                             axis_sizes)


# ---------------------------------------------------------------------------
# Resource-sanitizer registration (analysis.resources): the decode
# kernels' pallas_call geometry captured from the real host wrappers.
# The paged builders use a PERMUTED physical page table with NULL
# (trash-page) tail entries — the layout a live PagedKV produces — so
# the bounds proof covers the gather's `(ptab[b, j], 0, 0, 0)` for
# every page below a row's length (`ManualBlocks` on the kernel).
# ---------------------------------------------------------------------------

from triton_distributed_tpu.analysis.resources import (  # noqa: E402
    capture_pallas_calls,
    register_resource_kernel,
)


def _fd_capture(quantized: bool):
    b, h, hkv, d, s = 2, 4, 2, 128, 8192
    q = jnp.zeros((b, h, d), jnp.float32)
    kv_len = jnp.asarray([100, s], jnp.int32)
    if quantized:
        kc = jnp.zeros((b, hkv, s, d), jnp.int8)
        sc = jnp.ones((b, hkv, s), jnp.float32)
        args = dict(k_scale=sc, v_scale=sc)
    else:
        kc = jnp.zeros((b, hkv, s, d), jnp.float32)
        args = {}
    with capture_pallas_calls() as records:
        flash_decode(q, kc, kc, kv_len, interpret=False, **args)
    return records


def _fd_paged_capture(quantized: bool):
    import numpy as np

    b, h, hkv, d = 2, 4, 2, 128
    p, ps, t = 9, 128, 4
    q = jnp.zeros((b, h, d), jnp.float32)
    kv_len = jnp.asarray([100, t * ps], jnp.int32)
    table = np.zeros((b, t), np.int32)
    table[0] = (3, 5, 0, 0)       # short row: NULL (trash) tail
    table[1] = (8, 1, 2, 7)       # full row, permuted physical pages
    if quantized:
        pool = jnp.zeros((p, hkv, ps, d), jnp.int8)
        sc = jnp.ones((p, hkv, ps), jnp.float32)
        args = dict(k_scale=sc, v_scale=sc)
    else:
        pool = jnp.zeros((p, hkv, ps, d), jnp.float32)
        args = {}
    with capture_pallas_calls() as records:
        flash_decode_paged(q, pool, pool, jnp.asarray(table), kv_len,
                           interpret=False, **args)
    return records


@register_resource_kernel("flash_decode.dense")
def _resource_fd_dense():
    return _fd_capture(False)


@register_resource_kernel("flash_decode.dense_int8")
def _resource_fd_dense_int8():
    return _fd_capture(True)


@register_resource_kernel("flash_decode.paged")
def _resource_fd_paged():
    return _fd_paged_capture(False)


@register_resource_kernel("flash_decode.paged_int8")
def _resource_fd_paged_int8():
    return _fd_paged_capture(True)
