"""MoE routing utilities: histograms, capacity-padded routing, gather
/ combine index computation.

Reference: `python/triton_dist/kernels/nvidia/moe_utils.py` (394 LoC —
gather/scatter index calc `:32-88`, histogram `:89+`) and the native
alignment ops `csrc/lib/moe_utils.cu` (`moe_ag_scatter_align_block_size`)
which compute block-aligned expert offsets so grouped-GEMM tiles are
uniform.

TPU re-design, two plans.  `pack_by_expert` is DROPLESS: pairs sorted
by expert into block-aligned groups under a static bound, for the
one-chip expert layer (`layers.moe_mlp.SparseMoE`).  For the
communication-overlapped tensor-parallel layer, dynamic token counts
per expert are handled by **capacity padding** (fixed expert capacity, drop-or-pad), which keeps
every shape static so XLA can tile the grouped GEMM onto the MXU — the
TPU equivalent of block-aligning expert segments.  All routines are
jit-friendly (no data-dependent shapes).  For exact no-drop parity with
the reference, pass ``capacity = n_tokens * topk``.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


def histogram(expert_ids, num_experts: int):
    """Tokens per expert (reference `moe_utils.py` histogram kernel).
    expert_ids: int32 (...,) → (num_experts,)."""
    return jnp.zeros(num_experts, jnp.int32).at[expert_ids.reshape(-1)].add(1)


class Routing(NamedTuple):
    """Capacity-padded routing plan for one (token, topk) assignment.

    dispatch_index: (num_experts, capacity) int32 — source token index
      for each expert slot; `n_tokens` marks an empty slot.
    slot_of_pair:   (n_tokens, topk) int32 — slot each (token, k) pair
      landed in, -1 if dropped by capacity.
    counts:         (num_experts,) int32 — true (uncapped) tokens/expert.
    """

    dispatch_index: jnp.ndarray
    slot_of_pair: jnp.ndarray
    counts: jnp.ndarray


def route_capacity(expert_ids, num_experts: int, capacity: int) -> Routing:
    """Build a capacity-padded routing plan.

    expert_ids: (n_tokens, topk) int32.  Deterministic: earlier tokens
    win slots (the stable order the reference gets from its sort-based
    `calc_gather_index`).
    """
    n_tokens, topk = expert_ids.shape
    npairs = n_tokens * topk
    flat_e = expert_ids.reshape(-1)
    flat_tok = jax.lax.broadcasted_iota(
        jnp.int32, (n_tokens, topk), 0).reshape(-1)

    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_tok = flat_tok[order]
    pos_in_expert = (
        jax.lax.broadcasted_iota(jnp.int32, (npairs, 1), 0)[:, 0]
        - jnp.searchsorted(sorted_e, sorted_e, side="left").astype(jnp.int32)
    )
    kept = pos_in_expert < capacity

    dispatch_index = (
        jnp.full((num_experts, capacity), n_tokens, jnp.int32)
        .at[sorted_e, jnp.where(kept, pos_in_expert, capacity)]
        .set(sorted_tok, mode="drop")
    )
    slot_sorted = jnp.where(kept, pos_in_expert, -1)
    slot_of_pair = (
        jnp.zeros(npairs, jnp.int32).at[order].set(slot_sorted)
        .reshape(n_tokens, topk)
    )
    return Routing(dispatch_index=dispatch_index,
                   slot_of_pair=slot_of_pair,
                   counts=histogram(flat_e, num_experts))


def gather_tokens(tokens, dispatch_index):
    """Expand tokens into per-expert buckets: (E, capacity, hidden).
    Empty slots read a zero row (sentinel index n_tokens)."""
    padded = jnp.concatenate(
        [tokens, jnp.zeros((1,) + tokens.shape[1:], tokens.dtype)], axis=0)
    return padded[dispatch_index]


def combine_tokens(expert_out, expert_ids, slot_of_pair, weights):
    """Weighted combine of expert outputs back to token order.

    expert_out: (E, capacity, H); expert_ids / slot_of_pair / weights:
    (n_tokens, topk).  Dropped pairs contribute zero.  Returns
    (n_tokens, H)."""
    kept = slot_of_pair >= 0
    safe_slot = jnp.where(kept, slot_of_pair, 0)
    vals = expert_out[expert_ids, safe_slot]            # (n, topk, H)
    w = jnp.where(kept, weights, 0.0)[..., None].astype(jnp.float32)
    return (vals.astype(jnp.float32) * w).sum(axis=1).astype(expert_out.dtype)


def combine_matrix(expert_ids, slot_of_pair, weights, num_experts: int,
                   capacity: int, dtype=jnp.float32):
    """Materialise the topk-weighted combine as a dense one-hot matrix
    W (n_tokens, num_experts, capacity): token i's output row is
    `sum_e W[i, e] @ expert_out[e]` — a gather turned into MXU work so
    the fused epilogue can run it inside a Pallas kernel.

    Dropped pairs (slot < 0) contribute zero.  Duplicate (expert,
    slot) pairs accumulate, matching `combine_tokens`."""
    n_tokens, topk = expert_ids.shape
    kept = slot_of_pair >= 0
    rows = jax.lax.broadcasted_iota(jnp.int32, (n_tokens, topk), 0)
    safe_slot = jnp.where(kept, slot_of_pair, 0)
    w = jnp.where(kept, weights, 0.0).astype(dtype)
    return (jnp.zeros((n_tokens, num_experts, capacity), dtype)
            .at[rows.reshape(-1),
                expert_ids.reshape(-1),
                safe_slot.reshape(-1)]
            .add(w.reshape(-1)))


def pack_block(capacity: int) -> int:
    """Default ragged-packing row-block: the largest power-of-two ≤ 128
    that divides ``capacity``.  Capacity is sublane-aligned upstream
    (16 for 2-byte, 32 for int8 — `MoEMLP.capacity`), so the result is
    always a legal Mosaic sublane multiple for the bucket dtype."""
    return math.gcd(capacity, 128)


def packed_block_bound(n_pairs: int, num_experts: int, capacity: int,
                       block: int) -> int:
    """Static row-block budget T of a packed plan (shape-only).

    Each expert occupies ``ceil(min(count_e, capacity) / block)``
    blocks; over all experts that is bounded both by
    ``floor(n_pairs / block) + num_experts`` (every expert wastes less
    than one block of alignment) and by ``num_experts *
    (capacity // block)`` (the dense capacity grid).  The min of the
    two is tight enough that a packed plan never allocates more
    combine rows than the dense layout did."""
    assert capacity % block == 0, (capacity, block)
    return max(min(n_pairs // block + num_experts,
                   num_experts * (capacity // block)), 1)


class ChunkPlan(NamedTuple):
    """Per-chunk (destination-rank) routing for the fused MoE epilogue.

    The dense (E, cap) slot grid is *iterated* raggedly: only the
    leading ``ceil(min(count_e, cap) / block)`` row-blocks of each
    expert are visited, and the visit order packs all experts'
    occupied blocks front-to-back.  Blocks are (expert, slot-block)
    coordinates into the DENSE bucket tensor, so no data moves — the
    packed layout is an index-table schedule (the scalar-prefetch
    idiom of `flash_decode_paged`), the TPU analogue of MegaBlocks'
    block-sparse ragged layout.

    All fields are replicated on every rank (each rank computes every
    chunk's partial output):

    dispatch_index: (world, E, cap) int32 — chunk-local source token
      index per expert slot (sentinel mc = empty).
    counts:         (world, E) int32 — true tokens per (chunk, expert)
      bucket (≤ cap); drives empty-tile skipping in the AG-side
      grouped GEMM (the token-count-driven scheduling of the
      reference's `threadblock_swizzle_ag_moe`).
    slot_of_pair:   (world, mc, topk) int32 — slot each (token, k)
      pair landed in (-1 = dropped); the gather-based golden combine
      reads this directly, so no path needs a dense one-hot.
    block_expert:   (world, T) int32 — expert of packed block t
      (0 padding past ``n_blocks``).
    block_slot:     (world, T) int32 — slot-block index within that
      expert (slot rows [block_slot·B, block_slot·B + B)).
    n_blocks:       (world,) int32 — per-chunk packed-block occupancy.
    combine_blocks: (world, T, B, mc) — per-packed-block combine
      weights, transposed so the epilogue's combine matmul slices
      along the B sublanes (mc rides the lanes whole).  Built
      directly from the packed tables — the dense
      (mc, E·cap) one-hot of the old `combine_mats` is never
      materialised.
    """

    dispatch_index: jnp.ndarray
    counts: jnp.ndarray
    slot_of_pair: jnp.ndarray
    block_expert: jnp.ndarray
    block_slot: jnp.ndarray
    n_blocks: jnp.ndarray
    combine_blocks: jnp.ndarray

    @property
    def pack_block_size(self) -> int:
        return self.combine_blocks.shape[2]

    @property
    def num_blocks_static(self) -> int:
        return self.combine_blocks.shape[1]


def _pack_chunk(ids, w, num_experts: int, capacity: int, block: int,
                t_max: int, dtype):
    """Route + pack ONE chunk (vmapped by `plan_chunks`)."""
    mc, topk = ids.shape
    r = route_capacity(ids, num_experts, capacity)
    counts = jnp.minimum(r.counts, capacity).astype(jnp.int32)

    # Ragged block tables: expert e owns ceil(counts_e / block)
    # packed blocks, laid out front-to-back in expert order.
    blocks_e = (counts + block - 1) // block            # (E,)
    cum = jnp.cumsum(blocks_e)                          # inclusive
    off = cum - blocks_e                                # exclusive
    total = cum[-1]
    t_ids = jax.lax.broadcasted_iota(jnp.int32, (t_max, 1), 0)[:, 0]
    used = t_ids < total
    bexp = jnp.where(
        used,
        jnp.searchsorted(cum, t_ids, side="right").astype(jnp.int32),
        0)
    bslot = jnp.where(used, t_ids - off[bexp], 0).astype(jnp.int32)

    # Combine weights per packed block, scattered straight into the
    # (T, B, mc) layout: pair (token i, slot s of expert e) lands in
    # block off_e + s // B, row s % B, column i.  Dropped pairs
    # (slot -1) get an out-of-range block index and mode="drop".
    kept = r.slot_of_pair >= 0
    safe_slot = jnp.where(kept, r.slot_of_pair, 0)
    pair_e = ids.reshape(-1)
    pair_s = safe_slot.reshape(-1)
    pair_t = jnp.where(kept.reshape(-1),
                       off[pair_e] + pair_s // block, t_max)
    pair_row = pair_s % block
    pair_tok = jax.lax.broadcasted_iota(
        jnp.int32, (mc, topk), 0).reshape(-1)
    wv = jnp.where(kept, w, 0.0).astype(dtype).reshape(-1)
    cmatb = (jnp.zeros((t_max, block, mc), dtype)
             .at[pair_t, pair_row, pair_tok].add(wv, mode="drop"))

    return (r.dispatch_index, counts, r.slot_of_pair, bexp, bslot,
            total.astype(jnp.int32), cmatb)


def plan_chunks(expert_ids, weights, world: int, num_experts: int,
                capacity: int, dtype=jnp.float32,
                block: Optional[int] = None) -> ChunkPlan:
    """Build per-chunk routing plans: tokens are row-partitioned into
    `world` chunks (chunk c = rows destined for rank c after the
    reduce-scatter) and each chunk is routed independently with its
    own capacity, then ragged-row-packed at ``block`` granularity
    (default `pack_block(capacity)`).  expert_ids / weights:
    (n_tokens, topk)."""
    n_tokens, topk = expert_ids.shape
    assert n_tokens % world == 0, (n_tokens, world)
    mc = n_tokens // world
    block = block or pack_block(capacity)
    t_max = packed_block_bound(mc * topk, num_experts, capacity, block)
    ids_c = expert_ids.reshape(world, mc, topk)
    w_c = weights.reshape(world, mc, topk)

    fields = jax.vmap(
        lambda i, w: _pack_chunk(i, w, num_experts, capacity, block,
                                 t_max, dtype))(ids_c, w_c)
    return ChunkPlan(*fields)


def dense_combine_mats(plan: ChunkPlan, capacity: int):
    """Reconstruct the dense (world, E, mc, cap) combine tensor from a
    packed plan — golden/test utility only (the hot paths consume the
    packed layout directly)."""
    world, t_max, block, mc = plan.combine_blocks.shape
    e = plan.counts.shape[1]

    def per_chunk(bexp, bslot, nblk, cmatb):
        t_ids = jax.lax.broadcasted_iota(jnp.int32, (t_max, 1), 0)[:, 0]
        safe_e = jnp.where(t_ids < nblk, bexp, e)
        dense = jnp.zeros((e, capacity // block, block, mc),
                          plan.combine_blocks.dtype)
        dense = dense.at[safe_e, bslot].add(cmatb, mode="drop")
        # (E, cap/B, B, mc) -> (E, mc, cap)
        return dense.reshape(e, capacity, mc).transpose(0, 2, 1)

    return jax.vmap(per_chunk)(plan.block_expert, plan.block_slot,
                               plan.n_blocks, plan.combine_blocks)


class PackedPlan(NamedTuple):
    """DROPLESS routing: every (token, k) pair gets a row.

    Pairs are sorted by expert and laid front to back into row-blocks
    of ``block`` rows, each expert's group padded up to whole blocks —
    the block-aligned ragged segments of the reference
    (`moe_align_block_size`), with a static bound in place of a
    capacity: ``T = packed_blocks_bound(...)`` blocks hold ANY
    assignment, however uneven, so nothing is ever dropped.

    row_token:    (T * block,) int32 — source token of each packed row;
      ``n_tokens`` marks a padding row (reads a zero row).
    row_weight:   (T * block,) f32 — combine weight of the pair in
      that row, 0 for padding.
    pair_row:     (n_tokens, topk) int32 — the row each pair landed in.
    block_expert: (T,) int32 — expert of each block; blocks past
      ``n_blocks`` repeat the last used expert, so a kernel that maps
      a weight block by this table fetches nothing new for them.
    n_blocks:     () int32 — blocks in use.
    counts:       (E,) int32 — pairs per expert.
    """

    row_token: jnp.ndarray
    row_weight: jnp.ndarray
    pair_row: jnp.ndarray
    block_expert: jnp.ndarray
    n_blocks: jnp.ndarray
    counts: jnp.ndarray


def packed_blocks_bound(n_pairs: int, num_experts: int,
                        block: int) -> int:
    """Blocks that hold any assignment of ``n_pairs`` pairs: every
    expert in use wastes less than one block of alignment."""
    return min(num_experts, n_pairs) + n_pairs // block


def pack_by_expert(expert_ids, weights, num_experts: int,
                   block: int, held=None) -> PackedPlan:
    """Build the dropless packed plan.  expert_ids / weights:
    (n_tokens, topk).  Deterministic (stable sort: earlier pairs come
    first within an expert).

    ``held = (lo, hi)``: only experts ``lo <= e < hi`` live here.  The
    plan is then over the ``hi - lo`` held experts, numbered from 0;
    pairs routed elsewhere are sorted behind them under one more
    number, ``hi - lo``, whose blocks lie past ``n_blocks`` (never
    computed): ``counts[hi - lo]`` says how many there were, their
    rows read the zero row with weight 0, and their ``pair_row`` names
    rows no kernel wrote."""
    n_tokens, topk = expert_ids.shape
    npairs = n_tokens * topk
    flat_e = expert_ids.reshape(-1).astype(jnp.int32)
    flat_w = weights.reshape(-1).astype(jnp.float32)
    n_plan = num_experts
    if held is not None:
        lo, hi = held
        n_plan = hi - lo + 1            # the last: "not here"
        flat_e = jnp.where((flat_e >= lo) & (flat_e < hi), flat_e - lo,
                           hi - lo)
    t_max = packed_blocks_bound(npairs, n_plan, block)
    order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
    sorted_e = flat_e[order]
    counts = histogram(flat_e, n_plan)
    blocks_e = (counts + block - 1) // block
    cum = jnp.cumsum(blocks_e)
    first_pair = jnp.cumsum(counts) - counts            # (E,)
    pos = jnp.arange(npairs, dtype=jnp.int32) - first_pair[sorted_e]
    dest = ((cum - blocks_e) * block)[sorted_e] + pos   # packed row
    rows = t_max * block
    t_ids = jnp.arange(t_max, dtype=jnp.int32)
    token, weight = order // topk, flat_w[order]
    if held is None:
        n_blocks = cum[-1].astype(jnp.int32)
        block_expert = jnp.where(
            t_ids < cum[-1],
            jnp.searchsorted(cum, t_ids, side="right").astype(jnp.int32),
            sorted_e[-1])
    else:
        # At least one block "in use", whatever the routing: the
        # grouped GEMMs map every block past the last to block
        # n_blocks - 1, and a step whose pairs ALL went elsewhere (a
        # few rows, an eighth of the experts here) would name block -1
        # — a read outside the array, which hangs the chip.  That one
        # block then holds zero rows of weight 0.  Blocks past the held
        # ones repeat the last held expert in use (the last held one
        # where none is): nothing new is fetched for them.
        n_held = n_plan - 1
        n_blocks = jnp.maximum(cum[n_held - 1], 1).astype(jnp.int32)
        block_expert = jnp.minimum(jnp.searchsorted(
            cum[:n_held], jnp.minimum(t_ids, n_blocks - 1),
            side="right").astype(jnp.int32), n_held - 1)
        here = sorted_e < n_held
        token = jnp.where(here, token, n_tokens)
        weight = jnp.where(here, weight, 0.0)
    return PackedPlan(
        row_token=jnp.full((rows,), n_tokens, jnp.int32)
        .at[dest].set(token),
        row_weight=jnp.zeros((rows,), jnp.float32).at[dest].set(weight),
        pair_row=jnp.zeros((npairs,), jnp.int32).at[order].set(dest)
        .reshape(n_tokens, topk),
        block_expert=block_expert,
        n_blocks=n_blocks,
        counts=counts)


def tokens_per_rank(expert_ids, num_experts: int, ep_size: int):
    """Split counts by destination EP rank (reference `bincount` +
    cumsum preprocessing, `ep_a2a.py:310-377`)."""
    experts_per_rank = num_experts // ep_size
    counts = histogram(expert_ids, num_experts)
    return counts.reshape(ep_size, experts_per_rank).sum(axis=1)
