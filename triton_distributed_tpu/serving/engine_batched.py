"""Slot-batched decode: the jitted functions behind both the
continuous-batching scheduler and `models.engine.Engine`.

Three compiled programs cover the whole serving loop:

- the **masked decode step** — ONE program for all slots, whatever mix
  of requests occupies them.  Free/finished slots are masked: they
  emit ``pad_id`` deterministically (never sample stale logits), their
  cache offsets don't advance, and their RNG keys don't advance, so a
  request's token stream is a function of its own (prompt, seed) and
  not of whoever shares the batch;
- the **bucketed prefill** — the model's ordinary prefill jitted per
  length bucket (prompts are right-padded to a small fixed set of
  lengths, bounding XLA recompiles to ``len(buckets)`` programs);
- the **slot insert** — `dynamic_update_slice` of a freshly prefilled
  single-row cache into a free slot of the donated decode cache, with
  the slot's offset set to ``prompt_len - 1``.

The insert sets offset to ``prompt_len - 1`` (not ``prompt_len``) and
seeds the slot's input token with the *last prompt token*: the next
masked step then recomputes position ``s-1``'s KV (bit-identical —
same token, same rope position) and emits the request's first
generated token.  This is what makes right-padded bucket prefill
exact: the padded tail's logits and KV are never consumed (causal
attention keeps positions ``< s`` untouched by the pad, offsets mask
the tail), so no gather-at-true-length correction pass is needed.

`Engine` builds its unmasked single-batch step/rollout from the same
`make_step_fn`/`make_rollout_fn`, keeping one sampling/step
composition for both the static-batch and continuous paths.

A fourth program, `make_spec_verify_fn`, extends the masked block
variant into a speculative draft–verify pass: K proposed tokens per
slot are scored in one scanned dispatch, emitting a per-row
accept-length plus the bonus token, with the rejected tail's KV
cursor and PRNG key chain rolled back in-program (drafters live in
`serving.speculative`; the scheduler's ``spec_k`` mode drives it).

A fifth, `make_block_pass_fn`, is the step of a model that generates
by diffusion over blocks (`models.sdar_moe`): every slot's block in
flight through the model for one denoise pass, with the block the slot
finished last in front of it — a finished block's commit rides on the
next block's first denoise pass, and no pass is a commit alone.  The
two blocks' tokens and their revealed flags are data.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from triton_distributed_tpu.models.kv_cache import KVCache
from triton_distributed_tpu.models.utils import sample_token

#: Default prefill length buckets: one compiled prefill program per
#: entry actually used.  Powers of two keep padding waste < 2x.
DEFAULT_PREFILL_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048, 4096)


# ---------------------------------------------------------------------------
# Shared step composition (Engine's static-batch path uses these too)
# ---------------------------------------------------------------------------


def make_step_fn(decode_fn, temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0):
    """Unmasked decode+sample step: one batch-wide PRNG key
    (`Engine`'s original semantics)."""

    def step(params, tokens, cache, key):
        logits, cache = decode_fn(params, tokens, cache)
        key, sub = jax.random.split(key)
        nxt = sample_token(logits, sub, temperature, top_k=top_k,
                           top_p=top_p)
        return nxt, cache, key

    return step


def make_rollout_fn(step_fn):
    """`lax.scan` of ``step_fn`` over a static number of steps —
    steady-state decode as one dispatch (the CUDA-graph analogue)."""

    def rollout(params, first_tokens, cache, key, gen_len):
        def body(carry, _):
            tokens, cache, key = carry
            nxt, cache, key = step_fn(params, tokens, cache, key)
            return (nxt, cache, key), nxt

        (_, cache, _), toks = jax.lax.scan(
            body, (first_tokens, cache, key), length=gen_len)
        return toks.T, cache          # (B, gen_len)

    return rollout


# ---------------------------------------------------------------------------
# Masked (slot-batched) step
# ---------------------------------------------------------------------------


def masked_sample(logits, keys, active, pad_id: int,
                  temperature: float = 0.0, top_k: int = 0,
                  top_p: float = 1.0):
    """Per-slot sampling under an activity mask.

    logits: (B, V); keys: (B, 2) uint32 legacy PRNG keys; active: (B,)
    bool.  Active rows sample with their OWN key (vmapped
    `sample_token`, so temperature/top-k/top-p semantics match the
    single-request engine exactly); masked rows return ``pad_id``
    deterministically — stale logits of a free slot must never reach
    the sampler.
    """
    def row(lg, k):
        return sample_token(lg[None, :], k, temperature, top_k=top_k,
                            top_p=top_p)[0]

    sampled = jax.vmap(row)(logits, keys)
    return jnp.where(active, sampled,
                     jnp.int32(pad_id)).astype(jnp.int32)


def _masked_body(decode_fn, temperature, top_k, top_p, pad_id):
    """One masked decode+sample step (unjitted): the shared core of
    the single-step and scanned-block variants."""

    def body(params, tokens, cache, keys, active):
        prev_offset = cache.offset
        logits, cache = decode_fn(params, tokens, cache)
        new_keys, subs = _split_rows(keys)
        nxt = masked_sample(logits, subs, active, pad_id, temperature,
                            top_k=top_k, top_p=top_p)
        cache = dataclasses.replace(
            cache, offset=jnp.where(active, cache.offset, prev_offset))
        keys = jnp.where(active[:, None], new_keys, keys)
        return nxt, cache, keys

    return body


def make_masked_step_fn(decode_fn, temperature: float = 0.0,
                        top_k: int = 0, top_p: float = 1.0,
                        pad_id: int = 0, donate: bool = True):
    """One jitted decode step over all B slots.

    ``(params, tokens (B,), cache, keys (B,2), active (B,) bool) ->
    (next_tokens (B,), cache, keys)``

    Masked rows: emit ``pad_id``, keep their cache offset (the model's
    decode advances every row; the step restores masked rows'), and
    keep their PRNG key — so a slot's stream depends only on its own
    request.  The cache and keys are donated: XLA updates them in
    place, and the caller must rebind to the returned ones.
    """
    step = _masked_body(decode_fn, temperature, top_k, top_p, pad_id)
    if donate:
        return jax.jit(step, donate_argnums=(2, 3))
    return jax.jit(step)


def make_masked_block_fn(decode_fn, temperature: float = 0.0,
                         top_k: int = 0, top_p: float = 1.0,
                         pad_id: int = 0, block: int = 8,
                         donate: bool = True):
    """``block`` scanned masked steps per dispatch — multi-step
    scheduling: amortizes per-step host/dispatch overhead when the
    model step is cheap relative to it (small models, CPU).

    ``(params, tokens, cache, keys, active) ->
    (tokens (B, block), cache, keys)``

    The activity mask is FIXED for the block: rows that hit EOS
    mid-block keep decoding and their post-EOS tokens are discarded by
    the scheduler (bounded over-generation, <= block-1 steps — exactly
    the waste the serial engine pays for its WHOLE ``gen_len``).  The
    caller must ensure every active row has >= ``block`` KV positions
    of headroom (the scheduler falls back to single steps near the
    horizon).  A row's pre-EOS tokens and key chain are identical to
    the single-step path's.
    """
    body = _masked_body(decode_fn, temperature, top_k, top_p, pad_id)

    def blockstep(params, tokens, cache, keys, active):
        def scan_body(carry, _):
            tokens, cache, keys = carry
            nxt, cache, keys = body(params, tokens, cache, keys, active)
            return (nxt, cache, keys), nxt

        (_, cache, keys), toks = jax.lax.scan(
            scan_body, (tokens, cache, keys), length=block)
        return toks.T, cache, keys

    if donate:
        return jax.jit(blockstep, donate_argnums=(2, 3))
    return jax.jit(blockstep)


def make_spec_verify_fn(decode_fn, temperature: float = 0.0,
                        top_k: int = 0, top_p: float = 1.0,
                        pad_id: int = 0, k: int = 4,
                        donate: bool = True):
    """Speculative draft–verify pass: score ``k`` PROPOSED tokens per
    slot in one scanned dispatch and emit a per-row accept-length plus
    the bonus token, with the rejected tail's KV write cursor and PRNG
    key chain rolled back inside the program.

    ``(params, tokens (B,), drafts (B, k), cache, keys (B, 2),
    active (B,) bool, n_draft (B,)) ->
    (targets (B, k+1), accept (B,), cache, keys)``

    This is the masked K-step block variant re-pointed at a proposal
    block: the scan feeds ``[prev_token, d_1, ..., d_k]`` instead of
    its own samples, so step ``j`` scores the target model's token
    choice for position ``j`` under the PROPOSED context.  Each step
    samples (or argmaxes, at temperature 0) with the row's own key
    chain — exactly the tokens the non-speculative engine would have
    emitted had the context matched.  The accept rule is exact-match
    verification: row ``b`` accepts the longest prefix of its drafts
    where ``targets[b, j] == drafts[b, j]`` (capped at ``n_draft[b]``),
    and emits ``accept + 1`` tokens — the accepted drafts plus the
    target's own token at the first mismatch (the correction), or the
    bonus token when everything matched.  Because every emitted token
    IS the target's sample under its true context and key chain, the
    emitted stream is token-for-token identical to the non-speculative
    engine at ANY temperature, not just greedy — rejection changes how
    many tokens a dispatch commits, never which tokens.

    Rollback (the invariant `analysis.serving_model` proves): the scan
    wrote KV for all ``k+1`` fed tokens and split every row's key
    ``k+1`` times, but only the accepted prefix happened.  The program
    therefore restores ``offset = off0 + accept + 1`` (rejected
    positions hold garbage KV above the cursor — never attended before
    the next step overwrites them, the same masking argument that
    makes `KVCache.reset_slot` free) and selects the key state after
    exactly ``accept + 1`` splits from the scan's stacked key history,
    so a slot's key chain advances ONE SPLIT PER EMITTED TOKEN — the
    accounting `cluster.replica.advance_request_key` relies on for
    bit-exact failover resume.  Paged mode additionally unmaps the
    pages the rejected tail reached (`serving.pages.PagedKV.rollback`,
    host-side).  Masked rows behave as in the masked step: pad tokens,
    frozen offsets, frozen keys, ``accept = 0``.
    """
    assert k >= 1, k
    body = _masked_body(decode_fn, temperature, top_k, top_p, pad_id)

    def verify(params, tokens, drafts, cache, keys, active, n_draft):
        off0 = cache.offset
        keys0 = keys

        def scan_body(carry, tok):
            cache, keys = carry
            nxt, cache, keys = body(params, tok, cache, keys, active)
            return (cache, keys), (nxt, keys)

        feed = jnp.concatenate(
            [tokens[:, None], drafts.astype(jnp.int32)], axis=1)
        (cache, _), (targets, key_stack) = jax.lax.scan(
            scan_body, (cache, keys0), feed.T)
        targets = targets.T                         # (B, k+1)
        match = ((targets[:, :k] == drafts)
                 & (jnp.arange(k)[None, :] < n_draft[:, None]))
        # leading-match count: cumprod zeroes everything after the
        # first mismatch, so the sum is the accepted prefix length
        accept = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(
            axis=1)
        accept = jnp.where(active, accept, 0)
        cache = dataclasses.replace(
            cache, offset=jnp.where(active, off0 + accept + 1, off0))
        rows = jnp.arange(targets.shape[0])
        # key state after exactly accept+1 splits (key_stack[j] is the
        # keys AFTER step j)
        keys = jnp.where(active[:, None], key_stack[accept, rows],
                         keys0)
        return targets, accept, cache, keys

    if donate:
        return jax.jit(verify, donate_argnums=(3, 4))
    return jax.jit(verify)


def make_block_pass_fn(decode_fn, block: int, mask_id: int,
                       remasking: str = "sequential",
                       donate: bool = True):
    """One pass of generation by diffusion over blocks, for every slot:
    ONE jitted program, in which the revealed flags, the reveal counts
    and whether a finished block rides along are data.

    ``(params, blk (B, 2, 2n) int32, cache, host_blk (B, 2, 2n), fresh
    (B,) bool, active (B,) bool, n_reveal (B,) int32) -> (blk (B, 2,
    2n), cache)``

    A row's state is TWO block-widths.  The BACK half, ``blk[b, :,
    n:]``, is its block in flight: ``blk[b, 0]`` the tokens and ``blk[b,
    1]`` whether each is REVEALED (a flag, never ``token == mask_id``:
    a prompt may hold the mask id and an arg-max may return it).  The
    FRONT half, ``blk[b, :, :n]``, is the block the row finished last,
    as long as its K/V in the pages is not final: all flags set — the
    row is then FOLDED, this pass carries that block's commit — or all
    clear, a dead half.  The first position that is not final is
    ``cache.offset[b]``: the front half's where the row is folded,
    else the back half's.  ``blk`` stays on the device between passes
    — it is what the last pass returned — and rows ``fresh`` take the
    host's (a newly admitted row: a dead front half, the prompt's tail
    revealed in the back half, the rest masked).

    ``decode_fn(params, tokens (B, 2n), cache, active, folded) ->
    (logits (B, n, V), cache)`` is fed both halves with ``mask_id``
    where a position is not revealed; it writes their K/V into the
    pages mapped from the cursor on — a folded row's front half FINAL,
    every back half provisional — attends block-causally over the two
    blocks and returns the logits of the block in flight.  Then, for an
    active row,

    - the block in flight reveals ``n_reveal[b]`` of its masked
      positions with their arg-max tokens: the leftmost
      (``"sequential"``) or those whose arg-max is most probable
      (``"low_confidence_static"``; ties to the left).  A revealed
      token is never masked again;
    - a folded row's cursor moves on by ``n`` — its finished block is
      committed — and its front half is dead from here on;
    - a block in flight with nothing masked left is finished: it moves
      to the front half, to ride on the next pass, and the back half
      it leaves is the next block, all masked.  (Where the request ends
      with it, no pass follows and nothing ever reads its K/V.)

    An inactive row keeps its state and its cursor.  Greedy only.  The
    cache is donated (rebind to the returned one); the block state is a
    few integers a row and is not.
    """
    if remasking not in ("sequential", "low_confidence_static"):
        raise ValueError(f"unknown remasking {remasking!r}")
    n = int(block)

    def block_pass(params, blk, cache, host_blk, fresh, active, n_reveal):
        blk = jnp.where(fresh[:, None, None], host_blk, blk)
        toks, flags = blk[:, 0], blk[:, 1] != 0
        folded = active & flags[:, 0]
        shown = flags[:, n:]
        cursor = cache.offset
        logits, cache = decode_fn(
            params, jnp.where(flags, toks, jnp.int32(mask_id)), cache,
            active, folded)
        best = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (B, n)
        place = jnp.arange(n, dtype=jnp.float32)
        if remasking == "sequential":
            score = jnp.broadcast_to(-place, best.shape)
        else:
            # the arg-max's softmax probability, float32
            score = jnp.exp(jnp.max(logits, axis=-1)
                            - jax.nn.logsumexp(logits, axis=-1))
        score = jnp.where(shown, -jnp.inf, score)
        # rank among the row's positions: how many score higher (the
        # left one of a tie first)
        ahead = ((score[:, None, :] > score[:, :, None])
                 | ((score[:, None, :] == score[:, :, None])
                    & (place[None, None, :] < place[None, :, None])))
        rank = ahead.sum(axis=-1)
        reveal = ~shown & (rank < n_reveal[:, None])
        back = jnp.where(reveal, best, toks[:, n:])
        shown = shown | reveal
        done = shown.all(axis=1)[:, None]
        after = jnp.stack([
            jnp.concatenate([jnp.where(done, back, toks[:, :n]), back],
                            axis=1),
            jnp.concatenate([jnp.broadcast_to(done, shown.shape),
                             shown & ~done], axis=1).astype(jnp.int32),
        ], axis=1)
        cache = dataclasses.replace(
            cache, offset=jnp.where(folded, cursor + n, cursor))
        return jnp.where(active[:, None, None], after, blk), cache

    if donate:
        return jax.jit(block_pass, donate_argnums=(2,))
    return jax.jit(block_pass)


def _split_rows(keys):
    """Split each row's legacy (2,) uint32 key -> (carry, subkey)."""

    def one(k):
        ks = jax.random.split(k)
        return ks[0], ks[1]

    return jax.vmap(one)(keys)


def request_key(seed: int):
    """The slot key a request starts from: pure function of its seed."""
    return jax.random.PRNGKey(seed)


# ---------------------------------------------------------------------------
# Slot insert
# ---------------------------------------------------------------------------


def make_insert_fn(donate: bool = True):
    """``(big_cache, keys, row_cache, key, slot, offset) ->
    (big_cache, keys)`` — write a freshly prefilled single-row cache
    (batch 1, max_seq = its length bucket) into slot ``slot`` of the
    decode cache, set that slot's offset, and set its PRNG key — one
    dispatch per admission.  One compiled program per (bucket,
    cache-geometry); ``slot``/``offset`` are traced scalars, so slot
    choice never recompiles.  The big cache and keys are donated."""

    def insert(big: KVCache, keys, row: KVCache, key, slot, offset):
        slot = jnp.asarray(slot, jnp.int32)

        def rows(big_list, row_list):
            return [jax.lax.dynamic_update_slice(
                        bk, rk.astype(bk.dtype), (slot, 0, 0, 0))
                    for bk, rk in zip(big_list, row_list)]

        off = jax.lax.dynamic_update_slice(
            big.offset, jnp.reshape(jnp.asarray(offset, jnp.int32), (1,)),
            (slot,))
        rep = dict(ks=rows(big.ks, row.ks), offset=off)
        if big.vs is not None:          # None: latent rows (K is V)
            rep["vs"] = rows(big.vs, row.vs)
        if big.quantized:
            rep["kss"] = [jax.lax.dynamic_update_slice(
                              bs, rs, (slot, 0, 0))
                          for bs, rs in zip(big.kss, row.kss)]
            rep["vss"] = [jax.lax.dynamic_update_slice(
                              bs, rs, (slot, 0, 0))
                          for bs, rs in zip(big.vss, row.vss)]
        keys = jax.lax.dynamic_update_slice(
            keys, key.astype(keys.dtype)[None, :], (slot, 0))
        return dataclasses.replace(big, **rep), keys

    if donate:
        return jax.jit(insert, donate_argnums=(0, 1))
    return jax.jit(insert)


def _scatter_pages(page_ids, ps: int, dst_list, src_list,
                   scales: bool):
    """Each LOCAL page j of a row's layers ``src_list`` into physical
    page ``page_ids[j]`` of the pools ``dst_list``, where they lie."""
    bucket = int(src_list[0].shape[2])
    out = []
    for dst, src in zip(dst_list, src_list):
        for j in range(-(-bucket // ps)):
            lo, hi = j * ps, min((j + 1) * ps, bucket)
            blk = (src[:, :, lo:hi] if scales
                   else src[:, :, lo:hi, :])
            blk = blk.astype(dst.dtype)
            idx = ((page_ids[j], 0, 0) if scales
                   else (page_ids[j], 0, 0, 0))
            dst = jax.lax.dynamic_update_slice(dst, blk, idx)
        out.append(dst)
    return out


def make_paged_rows_fn():
    """``(pools, offset, row_cache, page_ids) -> (pools, offset)`` —
    the scatter of `make_paged_insert_fn` and nothing else: a CHUNK of
    a prompt whose prefill is still under way goes into its pages, and
    the slot's offset, key and page table are not touched (the slot is
    masked until its last chunk, which takes the insert proper).
    ``pools`` is ``(ks, vs, kss, vss)`` of the paged cache, None where
    it has none; donated, and every pool comes back placed as it went
    in.  ``wpools``, ``window_ids``: ``(wks, wvs)`` of a model with
    sliding-window layers (donated too) and the pages of THEIR pools
    the rows go into — the result then has those pools as a third
    member.  The cache's ``offset`` passes through unchanged: whoever asks
    whether the program enqueued last has finished asks that array
    (`scheduler._starved`), so it has to be this program's output too.
    """

    def rows(pools, offset, row: KVCache, page_ids, wpools=None,
             window_ids=None):
        ps = int(pools[0][0].shape[2])
        out = tuple(
            dst if dst is None else _scatter_pages(
                page_ids, ps, dst, src, scales)
            for dst, src, scales in zip(
                pools, (row.ks, row.vs, row.kss, row.vss),
                (False, False, True, True)))
        if wpools is None:
            return out, offset
        # sliding-window layers: their pools, their pages
        wks, wvs = wpools
        return out, offset, tuple(
            _scatter_pages(window_ids, ps, dst, src, False)
            for dst, src in ((wks, row.wks), (wvs, row.wvs)))

    return jax.jit(rows, donate_argnums=(0, 1, 4))


def make_paged_insert_fn(donate: bool = True):
    """``(pool_cache, keys, row_cache, key, slot, page_ids, offset) ->
    (pool_cache, keys)`` — scatter a freshly prefilled single-row
    dense cache (batch 1, max_seq = its length bucket) into physical
    pages of the paged pool, set the slot's offset and PRNG key — one
    dispatch per admission, one compiled program per (bucket,
    pool-geometry).

    A latent pool (`models.kv_cache`: ``vs`` None) has one pool a
    layer to scatter into; the walk is the same.  A recurrent layer's
    state (``states`` / ``convs``) goes whole into the slot's row of
    its pool.

    ``page_ids`` is a (ceil(bucket / page_size),) int32 vector naming
    the physical destination of each LOCAL page of the row cache;
    entries equal to `NULL_PAGE` (0) discard that page's write into
    the reserved trash page — this is how shared prefix pages (owned
    by the radix cache, possibly mapped by other slots) are skipped
    without recompiling.  ``window_ids``: the same for the pools of a
    model's sliding-window layers (`models.kv_cache`), which have pages
    of their own.  The page TABLE is not touched here: it is
    host-managed (`serving.pages.PagedKV`) and re-shipped wholesale
    before the next dispatch.

    The row cache may cover a page-aligned SUFFIX of the prompt (the
    prefix-cache-aware prefill path): local page j then maps to
    logical page ``start_page + j`` — the caller encodes that purely
    in ``page_ids``, so this program is oblivious to sharing.
    """

    def insert(pool, keys, row: KVCache, key, slot, page_ids, offset,
               window_ids=None):
        scatter = functools.partial(_scatter_pages, page_ids,
                                    pool.page_size)
        rep = dict(ks=scatter(pool.ks, row.ks, False),
                   offset=jax.lax.dynamic_update_slice(
                       pool.offset,
                       jnp.reshape(jnp.asarray(offset, jnp.int32), (1,)),
                       (jnp.asarray(slot, jnp.int32),)))
        if pool.vs is not None:         # None: latent rows (K is V)
            rep["vs"] = scatter(pool.vs, row.vs, False)
        if pool.quantized:
            rep["kss"] = scatter(pool.kss, row.kss, True)
            rep["vss"] = scatter(pool.vss, row.vss, True)
        if pool.wks is not None:
            # sliding-window layers: their own pools, their own pages
            rep["wks"] = _scatter_pages(window_ids, pool.page_size,
                                        pool.wks, row.wks, False)
            rep["wvs"] = _scatter_pages(window_ids, pool.page_size,
                                        pool.wvs, row.wvs, False)
        if pool.states is not None:
            # recurrent layers: the row's state, whole, into the slot
            def put(dst_list, src_list):
                return [jax.lax.dynamic_update_slice_in_dim(
                    dst, src.astype(dst.dtype),
                    jnp.asarray(slot, jnp.int32), axis=0)
                    for dst, src in zip(dst_list, src_list)]
            rep["states"] = put(pool.states, row.states)
            rep["convs"] = put(pool.convs, row.convs)
        keys = jax.lax.dynamic_update_slice(
            keys, key.astype(keys.dtype)[None, :],
            (jnp.asarray(slot, jnp.int32), 0))
        return dataclasses.replace(pool, **rep), keys

    if donate:
        return jax.jit(insert, donate_argnums=(0, 1))
    return jax.jit(insert)


# ---------------------------------------------------------------------------
# Prefill bucketing
# ---------------------------------------------------------------------------


def pick_bucket(length: int,
                buckets: Sequence[int]) -> Optional[int]:
    """Smallest bucket >= length, or None when the prompt exceeds all
    buckets (reject upstream)."""
    for b in sorted(buckets):
        if length <= b:
            return int(b)
    return None


def pad_prompt(prompt: Sequence[int], bucket: int,
               pad_id: int = 0) -> Tuple[jnp.ndarray, int]:
    """Right-pad to the bucket length.  Returns ((1, bucket) int32 ids,
    true length).  Right padding is exact here — see the module
    docstring for why the padded tail is never consumed."""
    s = len(prompt)
    assert 0 < s <= bucket, (s, bucket)
    ids = list(prompt) + [pad_id] * (bucket - s)
    return jnp.asarray(ids, jnp.int32)[None, :], s
