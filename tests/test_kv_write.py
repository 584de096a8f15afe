"""The decode step's one way to write a token row into a page pool
(`models.kv_cache.write_token_rows`).

Two properties, each over {bf16, int8 + scales} x {8, 2 KV heads} (the
one-chip pool and the tp=4 shard of the served model):

(a) it names the same elements as the form it replaced,
    ``pool.at[phys, :, within].set(rows)`` — bit for bit on random
    pools, offsets on both sides of a page edge, and two masked rows
    that share the trash page (whose contents are garbage by design);
(b) in the StableHLO of `TPAttention.decode_paged` every scatter into
    a pool scatters into the pool's LEADING dimensions (page, head,
    row) and its window is the last dimension alone.  That is what
    the TPU compiler's layout follows from: with the heads as a window
    between two scattered dimensions it copied each pool into another
    layout and back around the scatter
    (`tests_tpu/test_topology_pool_copies.py` reads the compiled
    program; this needs no chip).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from triton_distributed_tpu.layers.tp_attn import TPAttention
from triton_distributed_tpu.models.kv_cache import (
    NULL_PAGE, write_token_rows)

PAGES, PAGE, DIM = 9, 4, 16
CASES = [pytest.param(dt, h, id=f"{dt}-{h}kvheads")
         for dt in ("bf16", "int8") for h in (8, 2)]


def _pools(key, heads, dtype):
    """A values pool (and, for int8, its scales pool) of random
    contents, so that a write that lands elsewhere shows."""
    kv, ks = jax.random.split(key)
    shape = (PAGES, heads, PAGE, DIM)
    if dtype == "int8":
        return [jax.random.randint(kv, shape, -127, 128, jnp.int8),
                jax.random.normal(ks, shape[:3], jnp.float32)]
    return [jax.random.normal(kv, shape).astype(jnp.bfloat16)]


@pytest.mark.parametrize("dtype,heads", CASES)
def test_write_equals_the_sliced_form(dtype, heads):
    # rows 0-3: the last row of a page, the first of the next, mid
    # page, the pool's last page; rows 4-5: masked slots, NULL-mapped
    phys = jnp.asarray([3, 5, 1, PAGES - 1, NULL_PAGE, NULL_PAGE])
    within = jnp.asarray([PAGE - 1, 0, 2, PAGE - 1, 1, 1])
    key = jax.random.key(heads)
    for i, pool in enumerate(_pools(key, heads, dtype)):
        rows = jax.random.normal(
            jax.random.fold_in(key, i),
            (phys.shape[0],) + pool.shape[1:2] + pool.shape[3:])
        rows = (rows * 50).astype(pool.dtype)
        old = pool.at[phys, :, within].set(rows)
        new = jax.jit(write_token_rows)(pool, phys, within, rows)
        assert new.dtype == pool.dtype and new.shape == pool.shape
        np.testing.assert_array_equal(
            np.asarray(new[1:].astype(jnp.float32)),
            np.asarray(old[1:].astype(jnp.float32)))
        # and the old form is a fair reference: the row arrived
        np.testing.assert_array_equal(
            np.asarray(new[3, :, PAGE - 1].astype(jnp.float32)),
            np.asarray(rows[0].astype(jnp.float32)))


def _scatters(stablehlo: str):
    """(operand shape, attributes) of every scatter of a module's text;
    an attribute that is an empty list is left out of the text."""
    out = []
    for piece in stablehlo.split('"stablehlo.scatter"')[1:]:
        operand = re.search(r"\}\) : \(tensor<([\dx]+)x\w+>", piece)
        head = piece[:operand.start()]
        attrs = {name: [int(t) for t in dims.split(",") if t.strip()]
                 for name, dims in re.findall(r"(\w+) = \[([\d, ]*)\]",
                                              head)}
        attrs["unique_indices"] = re.search(
            r"unique_indices = (\w+)", head).group(1)
        out.append((tuple(int(d) for d in operand.group(1).split("x")),
                    attrs))
    return out


@pytest.mark.parametrize("dtype,heads", CASES)
def test_decode_paged_scatters_into_leading_dims(dtype, heads):
    batch = 3
    attn = TPAttention(axis="tp", world_size=1, hidden=64,
                       num_heads=2 * heads, num_kv_heads=heads,
                       head_dim=DIM, mode="xla")
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    pools = jax.eval_shape(lambda: _pools(jax.random.key(0), heads, dtype))
    scales = (pools[1], pools[1]) if dtype == "int8" else None

    def step(x, params, table, offset, k, v, kv_scales):
        return attn.decode_paged(x, params, (k, v), table, offset,
                                 kv_scales=kv_scales)

    text = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    ).lower(
        jax.ShapeDtypeStruct((batch, 64), jnp.bfloat16),
        jax.eval_shape(lambda: attn.init_params(jax.random.key(0))),
        jax.ShapeDtypeStruct((batch, 4), jnp.int32),
        jax.ShapeDtypeStruct((batch,), jnp.int32),
        pools[0], pools[0], scales).as_text()

    pool_shapes = {tuple(p.shape) for p in pools}
    seen = []
    for shape, attrs in _scatters(text):
        if shape not in pool_shapes:
            continue
        seen.append(shape)
        # page, head and row are scattered; what is left of the operand
        # (the values' last dimension; nothing of a scales pool) is the
        # window, and masked rows may share the trash page
        assert attrs["scatter_dims_to_operand_dims"] == [0, 1, 2], attrs
        assert attrs["inserted_window_dims"] == [0, 1, 2], attrs
        assert (len(attrs.get("update_window_dims", []))
                == len(shape) - 3), attrs
        assert attrs["unique_indices"] == "false", attrs
    assert sorted(seen) == sorted(2 * [tuple(p.shape) for p in pools]), (
        "K and V (and their scales) each scattered once", seen)
