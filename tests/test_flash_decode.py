"""Flash-decode tests (reference: `test/nvidia/test_decode_attn.py`,
`test_sp_decode_attn.py`)."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu.kernels.flash_decode import (
    combine_partials,
    flash_decode,
    sp_flash_decode,
)
from triton_distributed_tpu.ops import shard_map_op
from triton_distributed_tpu.utils.testing import assert_allclose


def _decode_ref(q, k, v, kv_len):
    b, h, d = q.shape
    _, hkv, s, _ = k.shape
    g = h // hkv
    kf = jnp.repeat(k.astype(jnp.float32), g, axis=1)
    vf = jnp.repeat(v.astype(jnp.float32), g, axis=1)
    sc = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32), kf) * d**-0.5
    mask = jnp.arange(s)[None, None, :] < kv_len[:, None, None]
    sc = jnp.where(mask, sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bhk,bhkd->bhd", p, vf).astype(q.dtype)


@pytest.mark.parametrize("gqa", [1, 4])
def test_flash_decode(gqa):
    b, h, s, d = 2, 8, 128, 32
    hkv = h // gqa
    q = jax.random.normal(jax.random.key(0), (b, h, d))
    k = jax.random.normal(jax.random.key(1), (b, hkv, s, d))
    v = jax.random.normal(jax.random.key(2), (b, hkv, s, d))
    kv_len = jnp.array([s, s // 2], jnp.int32)
    out, lse = flash_decode(q, k, v, kv_len, block_k=32)
    ref = _decode_ref(q, k, v, kv_len)
    assert_allclose(out, ref, atol=2e-3, rtol=2e-3, name=f"decode-g{gqa}")
    assert jnp.isfinite(lse).all()


def test_combine_partials_matches_full():
    """Splitting KV across R shards + LSE combine == full attention."""
    b, h, s, d, shards = 1, 4, 64, 32, 4
    q = jax.random.normal(jax.random.key(3), (b, h, d))
    k = jax.random.normal(jax.random.key(4), (b, h, s, d))
    v = jax.random.normal(jax.random.key(5), (b, h, s, d))
    s_loc = s // shards
    outs, lses = [], []
    for r in range(shards):
        o, l = flash_decode(q, k[:, :, r*s_loc:(r+1)*s_loc],
                            v[:, :, r*s_loc:(r+1)*s_loc],
                            jnp.array([s_loc], jnp.int32), block_k=16)
        outs.append(o)
        lses.append(l)
    combined = combine_partials(jnp.stack(outs), jnp.stack(lses))
    ref = _decode_ref(q, k, v, jnp.array([s], jnp.int32))
    assert_allclose(combined, ref, atol=2e-3, rtol=2e-3)


def test_sp_flash_decode(sp4_mesh):
    world, b, h, s_loc, d = 4, 2, 4, 32, 32
    s = world * s_loc
    q = jax.random.normal(jax.random.key(6), (b, h, d))
    k = jax.random.normal(jax.random.key(7), (b, h, s, d))
    v = jax.random.normal(jax.random.key(8), (b, h, s, d))
    kv_lens = jnp.full((world, b), s_loc, jnp.int32)

    fn = shard_map_op(
        lambda qq, kk, vv, ll: sp_flash_decode(
            qq, kk, vv, ll[0], axis="sp", block_k=16),
        sp4_mesh,
        in_specs=(P(None, None, None), P(None, None, "sp", None),
                  P(None, None, "sp", None), P("sp", None)),
        out_specs=P(None, None, None))
    out = jax.jit(fn)(q, k, v, kv_lens)
    ref = _decode_ref(q, k, v, jnp.array([s] * b, jnp.int32))
    assert_allclose(out, ref, atol=3e-3, rtol=3e-3, name="sp_decode")


def test_sp_flash_decode_ragged(sp4_mesh):
    """Last shard partially filled (growing KV cache)."""
    world, b, h, s_loc, d = 4, 1, 4, 32, 32
    s = world * s_loc
    q = jax.random.normal(jax.random.key(9), (b, h, d))
    k = jax.random.normal(jax.random.key(10), (b, h, s, d))
    v = jax.random.normal(jax.random.key(11), (b, h, s, d))
    fill = jnp.array([s_loc, s_loc, 7, 0], jnp.int32)[:, None]  # per rank
    kv_lens = jnp.broadcast_to(fill, (world, b))

    fn = shard_map_op(
        lambda qq, kk, vv, ll: sp_flash_decode(
            qq, kk, vv, ll[0], axis="sp", block_k=16),
        sp4_mesh,
        in_specs=(P(None, None, None), P(None, None, "sp", None),
                  P(None, None, "sp", None), P("sp", None)),
        out_specs=P(None, None, None))
    out = jax.jit(fn)(q, k, v, kv_lens)

    # golden: concatenate the valid prefixes of each shard
    ks = [k[:, :, r*s_loc:r*s_loc+int(fill[r, 0])] for r in range(world)]
    vs = [v[:, :, r*s_loc:r*s_loc+int(fill[r, 0])] for r in range(world)]
    kcat = jnp.concatenate(ks, axis=2)
    vcat = jnp.concatenate(vs, axis=2)
    total = int(fill.sum())
    ref = _decode_ref(q, kcat, vcat, jnp.array([total], jnp.int32))
    assert_allclose(out, ref, atol=3e-3, rtol=3e-3, name="sp_decode_ragged")


def test_combine_partials_all_empty_shards():
    """All-empty shards (every lse = -inf) must combine to 0, not NaN:
    the relative weight w is exp(0) = 1 for every shard in that case,
    so the garbage gate must key on each shard's own lse."""
    outs = jnp.full((3, 2, 4, 8), jnp.nan, jnp.float32)
    lses = jnp.full((3, 2, 4), -1e30, jnp.float32)
    c = np.asarray(combine_partials(outs, lses))
    assert (c == 0).all(), c


def test_combine_partials_live_nan_propagates():
    """A live shard's genuine NaN must NOT be silently sanitized."""
    outs = jnp.stack([jnp.full((1, 2, 4), jnp.nan, jnp.float32),
                      jnp.ones((1, 2, 4), jnp.float32)])
    lses = jnp.stack([jnp.zeros((1, 2), jnp.float32),
                      jnp.zeros((1, 2), jnp.float32)])
    c = np.asarray(combine_partials(outs, lses))
    assert np.isnan(c).all(), c


def test_zero_oob_rows():
    from triton_distributed_tpu.kernels.flash_attention import (
        zero_oob_rows,
    )

    v = jnp.ones((8, 4))
    # block 2 of 8-row blocks, bound 19: rows 16..18 valid, 19+ zeroed.
    out = np.asarray(zero_oob_rows(v, 2, 8, 19))
    assert (out[:3] == 1).all() and (out[3:] == 0).all(), out


@pytest.mark.parametrize("ragged", [False, True])
def test_flash_decode_int8_kv(ragged):
    """int8 KV-cache decode matches the dequantized float golden
    within quantization error (incl. the ragged cache tail)."""
    from triton_distributed_tpu.kernels.flash_decode import quantize_kv

    b, h, hkv, s, d = 2, 8, 4, 96 if ragged else 128, 32
    q = jax.random.normal(jax.random.key(0), (b, h, d), jnp.float32) / 4
    k = jax.random.normal(jax.random.key(1), (b, hkv, s, d),
                          jnp.float32) / 4
    v = jax.random.normal(jax.random.key(2), (b, hkv, s, d),
                          jnp.float32) / 4
    kv_len = jnp.array([s, s // 2], jnp.int32)

    k_q, v_q, ks, vs = quantize_kv(k, v)
    out, lse = flash_decode(q, k_q, v_q, kv_len, k_scale=ks, v_scale=vs,
                            block_k=64)

    # golden on the dequantized cache (so only kernel error remains)
    k_dq = k_q.astype(jnp.float32) * ks[..., None]
    v_dq = v_q.astype(jnp.float32) * vs[..., None]
    ref = _decode_ref(q, k_dq, v_dq, kv_len)
    assert_allclose(out, ref, atol=3e-3, rtol=3e-3,
                    name=f"decode_int8_ragged={ragged}")


def test_sp_flash_decode_int8(sp4_mesh):
    """SP decode over int8 KV shards matches the dequantized golden."""
    from triton_distributed_tpu.kernels.flash_decode import quantize_kv

    world, b, h, hkv, s_loc, d = 4, 2, 8, 4, 32, 32
    q = jax.random.normal(jax.random.key(0), (b, h, d), jnp.float32) / 4
    k = jax.random.normal(jax.random.key(1), (b, hkv, world * s_loc, d),
                          jnp.float32) / 4
    v = jax.random.normal(jax.random.key(2), (b, hkv, world * s_loc, d),
                          jnp.float32) / 4
    k_q, v_q, ks, vs = quantize_kv(k, v)
    kv_lens = jnp.broadcast_to(
        jnp.array([s_loc], jnp.int32), (world, b))

    fn = shard_map_op(
        lambda qq, kk, vv, kss, vss, ll: sp_flash_decode(
            qq, kk, vv, ll[0], axis="sp", k_scale=kss, v_scale=vss,
            block_k=16),
        sp4_mesh,
        in_specs=(P(None, None, None), P(None, None, "sp", None),
                  P(None, None, "sp", None), P(None, None, "sp"),
                  P(None, None, "sp"), P("sp", None)),
        out_specs=P(None, None, None))
    out = jax.jit(fn)(q, k_q, v_q, ks, vs, kv_lens)

    k_dq = k_q.astype(jnp.float32) * ks[..., None]
    v_dq = v_q.astype(jnp.float32) * vs[..., None]
    ref = _decode_ref(q, k_dq, v_dq,
                      jnp.full((b,), world * s_loc, jnp.int32))
    assert_allclose(out, ref, atol=3e-3, rtol=3e-3, name="sp_decode_int8")


# ---------------------------------------------------------------------------
# Paged (page-table-indexed) decode kernel
# ---------------------------------------------------------------------------

def _paged_pools(k, v, page_size, num_extra_pages=3, seed=99,
                 scales=None):
    """Chop a dense (B, Hkv, S, D) cache into pages scattered at a
    seeded RANDOM physical permutation of a larger pool (plus the
    reserved null page 0), returning (k_pool, v_pool, page_table[,
    scale pools]) — so a passing test proves the kernel really reads
    through the table, not dense order."""
    b, hkv, s, d = k.shape
    t = s // page_size
    num_pages = 1 + b * t + num_extra_pages
    rng = np.random.default_rng(seed)
    phys = rng.permutation(np.arange(1, num_pages))[:b * t]
    table = phys.reshape(b, t).astype(np.int32)
    k_pool = np.zeros((num_pages, hkv, page_size, d), k.dtype)
    v_pool = np.zeros((num_pages, hkv, page_size, d), v.dtype)
    s_pools = None
    if scales is not None:
        ks_, vs_ = scales
        ks_pool = np.zeros((num_pages, hkv, page_size), np.float32)
        vs_pool = np.zeros((num_pages, hkv, page_size), np.float32)
    for bb in range(b):
        for j in range(t):
            pg = table[bb, j]
            sl = slice(j * page_size, (j + 1) * page_size)
            k_pool[pg] = np.asarray(k[bb, :, sl])
            v_pool[pg] = np.asarray(v[bb, :, sl])
            if scales is not None:
                ks_pool[pg] = np.asarray(ks_[bb, :, sl])
                vs_pool[pg] = np.asarray(vs_[bb, :, sl])
    if scales is not None:
        s_pools = (jnp.asarray(ks_pool), jnp.asarray(vs_pool))
    return (jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(table), s_pools)


@pytest.mark.parametrize("gqa", [1, 4, 8])
def test_flash_decode_paged_matches_dense(gqa):
    """The page-table indirection is the ONLY difference: on the same
    logical KV (physically permuted into pages) the paged kernel must
    reproduce the dense split-KV kernel."""
    from triton_distributed_tpu.kernels.flash_decode import (
        flash_decode_paged)

    b, h, s, d, ps = 2, 8, 128, 32, 32
    hkv = h // gqa
    q = jax.random.normal(jax.random.key(0), (b, h, d))
    k = jax.random.normal(jax.random.key(1), (b, hkv, s, d))
    v = jax.random.normal(jax.random.key(2), (b, hkv, s, d))
    kv_len = jnp.array([s, s // 2 + 3], jnp.int32)
    k_pool, v_pool, table, _ = _paged_pools(k, v, ps)
    out, lse = flash_decode_paged(q, k_pool, v_pool, table, kv_len)
    ref, ref_lse = flash_decode(q, k, v, kv_len, block_k=ps)
    assert_allclose(out, ref, atol=1e-6, rtol=1e-6,
                    name=f"paged-g{gqa}")
    assert_allclose(lse, ref_lse, atol=1e-6, rtol=1e-6,
                    name=f"paged-lse-g{gqa}")


def test_flash_decode_paged_null_page_tail():
    """Logical pages at/beyond kv_len mapped to NULL page 0 (the
    allocator's convention for not-yet-allocated pages): the masked
    tail must not perturb the output."""
    from triton_distributed_tpu.kernels.flash_decode import (
        flash_decode_paged)

    b, h, s, d, ps = 2, 4, 64, 32, 16
    q = jax.random.normal(jax.random.key(3), (b, h, d))
    k = jax.random.normal(jax.random.key(4), (b, h, s, d))
    v = jax.random.normal(jax.random.key(5), (b, h, s, d))
    kv_len = jnp.array([17, 31], jnp.int32)   # 2 pages each mapped
    k_pool, v_pool, table, _ = _paged_pools(k, v, ps)
    full = flash_decode_paged(q, k_pool, v_pool, table, kv_len)[0]
    table = np.asarray(table).copy()
    table[0, 2:] = 0                          # beyond kv_len -> NULL
    table[1, 2:] = 0
    nulled = flash_decode_paged(q, k_pool, v_pool,
                                jnp.asarray(table), kv_len)[0]
    assert_allclose(nulled, full, atol=1e-6, rtol=1e-6,
                    name="paged-null-tail")
    ref = _decode_ref(q, k, v, kv_len)
    assert_allclose(nulled, ref, atol=2e-3, rtol=2e-3,
                    name="paged-null-vs-ref")


def test_flash_decode_paged_int8():
    from triton_distributed_tpu.kernels.flash_decode import (
        flash_decode_paged, quantize_kv)

    b, h, s, d, ps = 2, 4, 64, 32, 16
    q = jax.random.normal(jax.random.key(6), (b, h, d))
    k = jax.random.normal(jax.random.key(7), (b, h, s, d))
    v = jax.random.normal(jax.random.key(8), (b, h, s, d))
    k_q, v_q, ks, vs = quantize_kv(k, v)
    kv_len = jnp.array([s, 23], jnp.int32)
    k_pool, v_pool, table, s_pools = _paged_pools(
        k_q, v_q, ps, scales=(ks, vs))
    out, _ = flash_decode_paged(q, k_pool, v_pool, table, kv_len,
                                k_scale=s_pools[0], v_scale=s_pools[1])
    ref, _ = flash_decode(q, k_q, v_q, kv_len, k_scale=ks, v_scale=vs,
                          block_k=ps)
    assert_allclose(out, ref, atol=1e-6, rtol=1e-6, name="paged-int8")


def test_sp_flash_decode_paged(sp4_mesh):
    """Distributed paged decode: each rank's shard lives in a local
    page pool; the combined result matches dense reference attention
    over the concatenated valid prefixes."""
    from triton_distributed_tpu.kernels.flash_decode import (
        sp_flash_decode_paged)

    world, b, h, s_loc, d, ps = 4, 1, 4, 32, 32, 16
    s = world * s_loc
    q = jax.random.normal(jax.random.key(12), (b, h, d))
    k = jax.random.normal(jax.random.key(13), (b, h, s, d))
    v = jax.random.normal(jax.random.key(14), (b, h, s, d))
    fill = jnp.array([s_loc, s_loc, 7, 0], jnp.int32)[:, None]
    kv_lens = jnp.broadcast_to(fill, (world, b))
    pools = [_paged_pools(k[:, :, r*s_loc:(r+1)*s_loc],
                          v[:, :, r*s_loc:(r+1)*s_loc], ps,
                          seed=50 + r)
             for r in range(world)]
    k_pools = jnp.stack([p[0] for p in pools])   # (world, P, H, ps, D)
    v_pools = jnp.stack([p[1] for p in pools])
    tables = jnp.stack([p[2] for p in pools])    # (world, B, T)

    fn = shard_map_op(
        lambda qq, kk, vv, tt, ll: sp_flash_decode_paged(
            qq, kk[0], vv[0], tt[0], ll[0], axis="sp"),
        sp4_mesh,
        in_specs=(P(None, None, None), P("sp", None, None, None, None),
                  P("sp", None, None, None, None), P("sp", None, None),
                  P("sp", None)),
        out_specs=P(None, None, None))
    out = jax.jit(fn)(q, k_pools, v_pools, tables, kv_lens)

    ks = [k[:, :, r*s_loc:r*s_loc+int(fill[r, 0])] for r in range(world)]
    vs = [v[:, :, r*s_loc:r*s_loc+int(fill[r, 0])] for r in range(world)]
    total = int(fill.sum())
    ref = _decode_ref(q, jnp.concatenate(ks, axis=2),
                      jnp.concatenate(vs, axis=2),
                      jnp.array([total], jnp.int32))
    assert_allclose(out, ref, atol=3e-3, rtol=3e-3,
                    name="sp_decode_paged")


# -- ragged batches: the kernel's work follows each row's live length --

def _block_rows(ps, t, hkv, d, dtype=jnp.float32):
    from triton_distributed_tpu.kernels.flash_decode import (
        _pages_per_block)

    return ps * _pages_per_block(t, hkv, ps, d, dtype)


#: (page size, table width): both widths are NOT a multiple of the
#: kernel's block (32 pages of 16 rows, 4 pages of 128).
_RAGGED_GEOMETRY = {16: 40, 128: 6}

_RAGGED_LENS = {
    "one": lambda ps, blk, s: (1, 1),
    "page-1": lambda ps, blk, s: (ps - 1, 1),
    "page": lambda ps, blk, s: (ps, ps),
    "page+1": lambda ps, blk, s: (ps + 1, ps),
    "block": lambda ps, blk, s: (blk, blk - 1),
    "block+1": lambda ps, blk, s: (blk + 1, blk),
    "full": lambda ps, blk, s: (s, s - 1),
    "one-and-full": lambda ps, blk, s: (1, s),
}


@pytest.mark.parametrize("case", list(_RAGGED_LENS))
@pytest.mark.parametrize("ps", list(_RAGGED_GEOMETRY))
def test_flash_decode_paged_ragged(ps, case):
    """Lengths at every edge of a page and of a block, alone and mixed
    with the maximum, against the dense kernel and the plain
    reference."""
    from triton_distributed_tpu.kernels.flash_decode import (
        flash_decode_paged)

    b, h, hkv, d, t = 2, 4, 2, 32, _RAGGED_GEOMETRY[ps]
    s = t * ps
    blk = _block_rows(ps, t, hkv, d)
    assert ps < blk < s and s % blk != 0
    q = jax.random.normal(jax.random.key(20), (b, h, d))
    k = jax.random.normal(jax.random.key(21), (b, hkv, s, d))
    v = jax.random.normal(jax.random.key(22), (b, hkv, s, d))
    kv_len = jnp.array(_RAGGED_LENS[case](ps, blk, s), jnp.int32)
    k_pool, v_pool, table, _ = _paged_pools(k, v, ps)
    out, lse = flash_decode_paged(q, k_pool, v_pool, table, kv_len)
    ref, ref_lse = flash_decode(q, k, v, kv_len, block_k=ps)
    assert_allclose(out, ref, atol=2e-6, rtol=2e-6,
                    name=f"ragged-{ps}-{case}")
    assert_allclose(lse, ref_lse, atol=2e-6, rtol=2e-6,
                    name=f"ragged-lse-{ps}-{case}")
    assert_allclose(out, _decode_ref(q, k, v, kv_len), atol=2e-3,
                    rtol=2e-3, name=f"ragged-ref-{ps}-{case}")


@pytest.mark.parametrize("quantized", [False, True])
def test_flash_decode_paged_reads_nothing_past_length(quantized):
    """Poison: every row of the pool that is not below the length of
    the sequence owning it is NaN (int8 pools: 127 under a NaN scale)
    — the unowned pages, the pages a table maps beyond `kv_len`, the
    tail of a row's last page.  The output must not notice."""
    from triton_distributed_tpu.kernels.flash_decode import (
        flash_decode_paged, quantize_kv)

    b, h, hkv, d, ps, t = 3, 4, 2, 32, 16, 40
    s = t * ps
    blk = _block_rows(ps, t, hkv, d,
                      jnp.int8 if quantized else jnp.float32)
    lens = np.array([1, blk + ps + 3, s - 5], np.int32)
    q = jax.random.normal(jax.random.key(23), (b, h, d))
    k = jax.random.normal(jax.random.key(24), (b, hkv, s, d))
    v = jax.random.normal(jax.random.key(25), (b, hkv, s, d))
    scales = None
    if quantized:
        k, v, ks, vs = quantize_kv(k, v)
        scales = (ks, vs)
    k_pool, v_pool, table, s_pools = _paged_pools(k, v, ps,
                                                  scales=scales)

    live = np.zeros((k_pool.shape[0], ps), bool)    # (page, row)
    for bb in range(b):
        for j in range(t):
            live[table[bb, j]] = j * ps + np.arange(ps) < lens[bb]
    dead = jnp.asarray(~live)
    kw = {}
    if quantized:
        poison = lambda pool: jnp.where(dead[:, None, :, None], 127, pool)
        kw = {name: jnp.where(dead[:, None, :], jnp.nan, sc)
              for name, sc in zip(("k_scale", "v_scale"), s_pools)}
    else:
        poison = lambda pool: jnp.where(dead[:, None, :, None], jnp.nan,
                                        pool)
    out, lse = flash_decode_paged(q, poison(k_pool), poison(v_pool),
                                  table, jnp.asarray(lens), **kw)
    assert jnp.isfinite(out).all() and jnp.isfinite(lse).all()
    kw = dict(k_scale=scales[0], v_scale=scales[1]) if quantized else {}
    ref, ref_lse = flash_decode(q, k, v, jnp.asarray(lens), block_k=ps,
                                **kw)
    assert_allclose(out, ref, atol=2e-6, rtol=2e-6, name="poison")
    assert_allclose(lse, ref_lse, atol=2e-6, rtol=2e-6,
                    name="poison-lse")


def test_flash_decode_paged_empty_row():
    """A row with `kv_len` 0 (an empty shard of `sp_flash_decode_paged`)
    reads nothing: zeros, and an lse `combine_partials` weighs at 0."""
    from triton_distributed_tpu.kernels.flash_decode import (
        NEG_INF, flash_decode_paged)

    b, h, d, ps, t = 2, 4, 32, 16, 4
    q = jax.random.normal(jax.random.key(26), (b, h, d))
    k = jax.random.normal(jax.random.key(27), (b, h, t * ps, d))
    k_pool, v_pool, table, _ = _paged_pools(k, k, ps)
    kv_len = jnp.array([0, 20], jnp.int32)
    out, lse = flash_decode_paged(q, k_pool, v_pool, table, kv_len)
    assert (out[0] == 0).all() and (lse[0] < NEG_INF / 2).all()
    assert_allclose(out[1:], _decode_ref(q, k, k, kv_len)[1:],
                    atol=2e-3, rtol=2e-3, name="empty-row-neighbour")
