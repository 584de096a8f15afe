"""Ragged row-packing plan tests (ISSUE 14): the packed block
schedule + packed combine weights `moe_utils.plan_chunks` emits for
the combine-in-epilogue MoE kernels, checked bit-exactly against the
gather-based staged reference — pure JAX, so these run on any host
(no Pallas, no shard_map).

Edge cases pinned per the issue: empty expert, all-tokens-one-expert,
occupancy exactly at a block boundary, w8a8 scale rows; plus the
allocation-drop ride-along (no dense (mc, E·cap) one-hot is ever
materialised on the hot path).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.kernels import moe_utils
from triton_distributed_tpu.utils.testing import assert_allclose


def _plan(ids, w, world, e, cap, dtype=jnp.float32, block=None):
    return moe_utils.plan_chunks(ids, w, world, e, cap, dtype=dtype,
                                 block=block)


def _random_ids(key, n, topk, e):
    ids = jax.random.randint(key, (n, topk), 0, e)
    w = jax.nn.softmax(jax.random.normal(
        jax.random.fold_in(key, 1), (n, topk)), axis=-1)
    return ids, w


def _packed_combine_sim(plan, chunk, expert_out):
    """Simulate the packed combine-in-epilogue in XLA: gather each
    occupied block's rows from the dense (E, cap, n) expert output
    via the block tables, contract with its combine weights, sum —
    exactly what `emit_packed_combine` accumulates on the MXU."""
    t_max, block, mc = plan.combine_blocks.shape[1:]
    bexp = plan.block_expert[chunk]
    bslot = plan.block_slot[chunk]
    nblk = plan.n_blocks[chunk]
    cap = expert_out.shape[1]
    # (T, B, n): packed block rows out of the dense expert output.
    rows = expert_out.reshape(-1, expert_out.shape[-1])[
        (bexp[:, None] * cap + bslot[:, None] * block
         + jnp.arange(block)[None, :]).reshape(-1)
    ].reshape(t_max, block, -1)
    mask = (jnp.arange(t_max) < nblk)[:, None, None]
    cm = plan.combine_blocks[chunk].astype(jnp.float32)
    return jnp.einsum("tbm,tbn->mn", jnp.where(mask, cm, 0.0),
                      jnp.where(mask, rows.astype(jnp.float32), 0.0))


@pytest.mark.parametrize("world,mc,e,topk,cap", [
    (1, 32, 4, 2, 16), (2, 32, 8, 2, 16), (4, 16, 4, 1, 16),
    (1, 64, 16, 4, 16),
])
def test_packed_combine_matches_gather_combine(world, mc, e, topk, cap):
    """The packed-schedule combine == the gather-based staged
    reference, chunk by chunk."""
    key = jax.random.key(world * 100 + e)
    ids, w = _random_ids(key, world * mc, topk, e)
    plan = _plan(ids, w, world, e, cap)
    ids_c = ids.reshape(world, mc, topk)
    w_c = w.reshape(world, mc, topk)
    h = 24
    for c in range(world):
        eo = jax.random.normal(jax.random.fold_in(key, 7 + c),
                               (e, cap, h))
        golden = moe_utils.combine_tokens(eo, ids_c[c],
                                          plan.slot_of_pair[c], w_c[c])
        got = _packed_combine_sim(plan, c, eo)
        assert_allclose(got.astype(golden.dtype), golden, atol=1e-5,
                        rtol=1e-5, name=f"packed-combine-chunk{c}")


def test_dense_reconstruction_bitwise():
    """`dense_combine_mats` (reconstructed from the packed plan) is
    BITWISE identical to the old dense `combine_matrix` construction
    — the packed layout loses nothing."""
    world, mc, e, topk, cap = 2, 32, 4, 2, 16
    ids, w = _random_ids(jax.random.key(3), world * mc, topk, e)
    plan = _plan(ids, w, world, e, cap)
    dense = moe_utils.dense_combine_mats(plan, cap)
    ids_c = ids.reshape(world, mc, topk)
    w_c = w.reshape(world, mc, topk)
    for c in range(world):
        ref = moe_utils.combine_matrix(
            ids_c[c], plan.slot_of_pair[c], w_c[c], e, cap
        ).transpose(1, 0, 2)                     # (E, mc, cap)
        assert (np.asarray(dense[c]) == np.asarray(ref)).all()


def test_empty_expert_skipped():
    """An expert no token routed to occupies ZERO packed blocks (the
    block-granular skip the dense layout could only do per whole
    expert), and the combine stays exact."""
    world, mc, e, cap = 1, 32, 4, 16
    # Route everything to experts 0 and 2 — experts 1, 3 are empty.
    ids = jnp.stack([jnp.zeros(mc, jnp.int32),
                     jnp.full((mc,), 2, jnp.int32)], axis=1)
    w = jnp.full((mc, 2), 0.5, jnp.float32)
    plan = _plan(ids, w, world, e, cap)
    counts = np.asarray(plan.counts[0])
    assert counts[1] == 0 and counts[3] == 0
    B = plan.pack_block_size
    expected_blocks = int(np.ceil(np.minimum(counts, cap) / B).sum())
    assert int(plan.n_blocks[0]) == expected_blocks
    # Empty experts never appear in the occupied prefix of the table.
    bexp = np.asarray(plan.block_expert[0])[:expected_blocks]
    assert set(bexp.tolist()) <= {0, 2}
    eo = jax.random.normal(jax.random.key(0), (e, cap, 8))
    golden = moe_utils.combine_tokens(eo, ids, plan.slot_of_pair[0], w)
    got = _packed_combine_sim(plan, 0, eo)
    assert_allclose(got.astype(golden.dtype), golden, atol=1e-5,
                    rtol=1e-5, name="empty-expert")


def test_all_tokens_one_expert():
    """Worst-case skew: every pair routed to one expert.  Capacity
    drops apply exactly as in the staged path, the occupied blocks
    cover exactly that expert's capacity, and the combine matches."""
    world, mc, e, cap, topk = 1, 64, 4, 16, 2
    ids = jnp.full((mc, topk), 3, jnp.int32)
    w = jnp.full((mc, topk), 0.5, jnp.float32)
    plan = _plan(ids, w, world, e, cap)
    B = plan.pack_block_size
    assert int(plan.counts[0, 3]) == cap          # capped
    assert int(plan.n_blocks[0]) == cap // B
    assert (np.asarray(plan.block_expert[0])[:cap // B] == 3).all()
    # Dropped pairs (everything past capacity) contribute zero.
    assert int((np.asarray(plan.slot_of_pair[0]) >= 0).sum()) == cap
    eo = jax.random.normal(jax.random.key(1), (e, cap, 8))
    golden = moe_utils.combine_tokens(eo, ids, plan.slot_of_pair[0], w)
    got = _packed_combine_sim(plan, 0, eo)
    assert_allclose(got.astype(golden.dtype), golden, atol=1e-5,
                    rtol=1e-5, name="one-expert")


def test_occupancy_exactly_at_block_boundary():
    """Counts landing exactly on a block multiple occupy exactly
    count/B blocks — no phantom block, no missing rows."""
    world, e, cap = 1, 2, 32
    block = 16
    # Expert 0 gets exactly 16 pairs (one full block), expert 1 the
    # other 16.
    ids = jnp.concatenate([jnp.zeros(16, jnp.int32),
                           jnp.ones(16, jnp.int32)])[:, None]
    w = jnp.ones((32, 1), jnp.float32)
    plan = _plan(ids, w, world, e, cap, block=block)
    assert int(plan.n_blocks[0]) == 2
    assert np.asarray(plan.block_expert[0])[:2].tolist() == [0, 1]
    assert np.asarray(plan.block_slot[0])[:2].tolist() == [0, 0]
    # One more pair on expert 0 tips it to a second block.
    ids2 = jnp.concatenate([jnp.zeros(17, jnp.int32),
                            jnp.ones(15, jnp.int32)])[:, None]
    plan2 = _plan(ids2, w, world, e, cap, block=block)
    assert int(plan2.n_blocks[0]) == 3
    assert np.asarray(plan2.block_expert[0])[:3].tolist() == [0, 0, 1]
    assert np.asarray(plan2.block_slot[0])[:3].tolist() == [0, 1, 0]
    eo = jax.random.normal(jax.random.key(2), (e, cap, 8))
    for p, i in ((plan, ids), (plan2, ids2)):
        golden = moe_utils.combine_tokens(eo, i, p.slot_of_pair[0], w)
        got = _packed_combine_sim(p, 0, eo)
        assert_allclose(got.astype(golden.dtype), golden, atol=1e-5,
                        rtol=1e-5, name="block-boundary")


def test_w8a8_scale_rows():
    """The packed w8a8 epilogue math (int8 grouped GEMM → per-token ⊗
    per-channel dequant → packed combine) matches the staged w8a8
    reference (dense dequant grouped matmul → gather combine)."""
    from triton_distributed_tpu.kernels.quantized import quantize_sym

    world, mc, e, cap, topk, k, n = 1, 32, 4, 16, 2, 64, 48
    key = jax.random.key(5)
    ids, w = _random_ids(key, mc, topk, e)
    plan = _plan(ids, w, world, e, cap)
    buckets = jax.random.normal(jax.random.fold_in(key, 2),
                                (e, cap, k)) / 8
    wdown = jax.random.normal(jax.random.fold_in(key, 3), (e, k, n)) / 8
    b_q, sa = quantize_sym(buckets, axis=-1)      # (E,cap,k)i8,(E,cap)
    w_q, sw = quantize_sym(wdown, axis=1)         # (E,k,n)i8, (E,n)

    # Staged reference: dequant per expert, gather combine.
    acc = jnp.einsum("eck,ekn->ecn", b_q.astype(jnp.int32),
                     w_q.astype(jnp.int32))
    deq = (acc.astype(jnp.float32) * sa[:, :, None] * sw[:, None, :])
    golden = moe_utils.combine_tokens(deq, ids, plan.slot_of_pair[0], w)

    # Packed epilogue: the same dequant applied per packed block
    # (scale rows gathered through the block tables), then the packed
    # combine — the arithmetic `emit_packed_combine` runs.
    got = _packed_combine_sim(plan, 0, deq)
    assert_allclose(got.astype(golden.dtype), golden, atol=1e-5,
                    rtol=1e-5, name="w8a8-scale-rows")
    # Per-block scale rows line up with the block tables: gathering
    # sa through (block_expert, block_slot) reproduces the dense rows.
    B = plan.pack_block_size
    nblk = int(plan.n_blocks[0])
    bexp = np.asarray(plan.block_expert[0])
    bslot = np.asarray(plan.block_slot[0])
    sa_np = np.asarray(sa)
    for t in range(nblk):
        rows = sa_np[bexp[t], bslot[t] * B:(bslot[t] + 1) * B]
        assert rows.shape == (B,)


def test_no_dense_onehot_allocation():
    """The ride-along bugfix pinned: the combine weights are built
    directly in the packed (T, B, mc) layout — at most the dense
    E·cap row budget, half the bytes of the old f32 (mc, E·cap)
    one-hot at production dtype, and no dense intermediate exists in
    the jaxpr."""
    world, mc, e, topk, cap = 1, 128, 16, 2, 32
    ids, w = _random_ids(jax.random.key(8), world * mc, topk, e)
    plan = moe_utils.plan_chunks(ids, w, world, e, cap,
                                 dtype=jnp.bfloat16)
    t_max, block = plan.num_blocks_static, plan.pack_block_size
    assert t_max * block <= e * cap
    dense_f32_bytes = mc * e * cap * 4            # the old one-hot
    assert plan.combine_blocks.nbytes * 2 <= dense_f32_bytes
    # No (mc, e, cap)-shaped f32 intermediate is ever materialised.
    jaxpr = jax.make_jaxpr(
        lambda i, ww: moe_utils.plan_chunks(i, ww, world, e, cap,
                                            dtype=jnp.bfloat16)
    )(ids, w)
    shapes = {tuple(v.aval.shape)
              for eqn in jaxpr.eqns for v in eqn.outvars}
    assert (mc, e, cap) not in shapes and (e, mc, cap) not in shapes


def test_static_block_budget_bound():
    """T never exceeds either bound: pairs/B + E (alignment waste) or
    the dense grid E·(cap/B); extreme skew still fits."""
    for n_pairs, e, cap, block in [(64, 4, 16, 16), (4096, 64, 128, 128),
                                   (4096, 8, 512, 128), (8, 64, 16, 16)]:
        t = moe_utils.packed_block_bound(n_pairs, e, cap, block)
        assert t >= 1
        assert t <= e * (cap // block)
        assert t * block <= e * cap
        # all-to-one-expert occupancy fits
        assert (cap // block) <= t


def test_moe_mlp_xla_path_world2(devices):
    """The rewritten XLA golden path (gather combine — no dense
    one-hot) on a real 2-device mesh matches a hand-computed
    composition of the same sharded math."""
    from jax.sharding import Mesh, PartitionSpec as P

    from triton_distributed_tpu.layers.moe_mlp import MoEMLP

    world, mc, h, ffn, e = 2, 16, 32, 32, 4
    mesh = Mesh(np.array(devices[:world]), ("tp",))
    layer = MoEMLP(axis="tp", world_size=world, hidden=h, ffn=ffn,
                   num_experts=e, topk=2, mode="xla")
    x = jax.random.normal(jax.random.key(30), (world * mc, h),
                          jnp.float32) / 4
    params = layer.init_params(jax.random.key(31), dtype=jnp.float32)

    fn = jax.shard_map(
        lambda xx, pp: layer(xx, pp), mesh=mesh,
        in_specs=(P("tp", None), layer.global_param_specs()),
        out_specs=P("tp", None), check_vma=False)
    got = jax.jit(fn)(x, params)

    # Hand-rolled reference: same routing/capacity semantics, the
    # per-rank ffn shards computed explicitly and summed.
    from triton_distributed_tpu.kernels.allgather_group_gemm import (
        gated_silu)

    cap = layer.capacity(mc)
    ids, w = layer._route(x, params["router"])
    plan = layer._chunk_plan(ids, w, cap)
    s_gu = params["gate_up"].shape[2] // world
    s_dn = params["down"].shape[1] // world
    out = jnp.zeros((world, mc, h), jnp.float32)
    for r in range(world):
        gu = params["gate_up"][:, :, r * s_gu:(r + 1) * s_gu]
        dn = params["down"][:, r * s_dn:(r + 1) * s_dn, :]
        xc = x.reshape(world, mc, h)
        buckets = jax.vmap(moe_utils.gather_tokens)(
            xc, plan.dispatch_index)
        inter = jnp.einsum("wech,ehf->wecf", buckets, gu,
                           preferred_element_type=jnp.float32
                           ).astype(x.dtype)
        act = gated_silu(inter)
        partial = jnp.einsum("wecf,efh->wech", act, dn,
                             preferred_element_type=jnp.float32)
        ids_c = ids.reshape(world, mc, 2)
        w_c = w.reshape(world, mc, 2)
        out = out + jax.vmap(moe_utils.combine_tokens)(
            partial, ids_c, plan.slot_of_pair, w_c)
    ref = out.reshape(world * mc, h).astype(got.dtype)
    assert_allclose(got, ref, atol=2e-3, rtol=2e-3,
                    name="moe-mlp-xla-world2")
