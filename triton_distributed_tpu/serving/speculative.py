"""Draft sources for speculative decoding (`SchedulerConfig.spec_k`).

Decode is memory-bound: `kernels.flash_decode` already streams at
~92% of HBM peak, so per-token latency is capped by the hardware and
the remaining raw-speed multiplier is tokens *per step*.  The masked
batched step's speculative verify pass
(`engine_batched.make_spec_verify_fn`) scores K proposed tokens per
slot in one dispatch and commits the accepted prefix plus one bonus
token — on average ``1 + E[accept]`` tokens per target-model step.
This module supplies the proposals, behind one interface:

- :class:`NgramDrafter` — prompt-lookup / n-gram drafting, the
  model-free fallback (and what the CPU-only tier-1 tests exercise):
  the longest recent n-gram suffix of the context is searched for an
  earlier occurrence, and the tokens that followed it last time are
  proposed.  Free to compute, surprisingly effective on repetitive
  continuations (code, RAG quotes, structured output) — and when it
  finds nothing, the scheduler simply takes a plain step.

- :class:`DraftModelDrafter` — a cheap draft model sharing the
  target's tokenizer (e.g. `models.config.ModelConfig.draft_of` — a
  tiny Qwen3 beside a big one; the tests use `serving.toy.ToyModel`
  instances).  The drafter keeps one single-row KV cache per in-flight
  request, greedy-rolls K proposals per round, and reconciles its
  cache with the verified outcome: the accepted prefix's draft KV is
  kept (it was computed with exactly the committed tokens), the
  rejected tail is cursor-rolled-back — the same rollback discipline
  the target engine applies, one model down.

Neither drafter touches the slot PRNG keys: proposals are greedy (or
lookup), and the verify pass itself consumes exactly one key split
per EMITTED token (`make_spec_verify_fn` rolls the chain back), so
`cluster.replica.advance_request_key`'s streamed-token accounting
stays exact through draft/verify rounds, preemption and failover.

Drafter lifecycle, driven by the scheduler: ``start(req, tokens)`` at
admission (and re-admission after preempt/failover — ``tokens`` is
prompt + already-streamed output), ``propose(req, k)`` before each
speculative dispatch, ``commit(req, accepted, committed)`` after the
verify pass for streams that continue, ``stop(req)`` at retirement,
preemption or drain.  Drafters are keyed by ``request_id`` and hold
no slot state, so one drafter instance serves every replica of a
cluster.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from triton_distributed_tpu.serving.engine_batched import (
    pad_prompt,
    pick_bucket,
)


class Drafter:
    """Interface + shared accounting.  Subclasses implement
    `_propose`; the base tracks proposal/acceptance totals (the
    scheduler owns the metrics registry — these are for tests and
    bench introspection).  Denominator note: the drafter counts
    proposals as MADE, while the engine's gauge/counters count the
    drafts actually SCORED (the scheduler trims proposals past a
    request's remaining budget), so `accept_rate` here reads at or
    below the engine's ``serving_spec_accept_rate`` for the same
    run."""

    name = "drafter"

    def __init__(self):
        self.proposed_tokens = 0
        self.accepted_tokens = 0

    @property
    def accept_rate(self) -> float:
        return (self.accepted_tokens / self.proposed_tokens
                if self.proposed_tokens else 0.0)

    # -- lifecycle (scheduler-driven) -----------------------------------

    def start(self, req, tokens: Sequence[int]) -> None:
        """Admission (or resume): ``tokens`` is the full committed
        context — prompt plus any already-streamed output."""

    def propose(self, req, k: int) -> List[int]:
        out = self._propose(req, k)
        self.proposed_tokens += len(out)
        return out

    def commit(self, req, accepted: int,
               committed: Sequence[int]) -> None:
        """The verify outcome for a CONTINUING stream: ``accepted``
        drafts matched and ``committed`` (accepted + 1 tokens, the
        bonus/correction last) were appended to the request."""
        self.accepted_tokens += int(accepted)

    def stop(self, req) -> None:
        """Retirement / preemption / drain: forget the request."""

    # -- subclass seam ---------------------------------------------------

    def _propose(self, req, k: int) -> List[int]:  # pragma: no cover
        raise NotImplementedError


class NgramDrafter(Drafter):
    """Prompt-lookup drafting: propose the continuation that followed
    the most recent earlier occurrence of the context's n-gram suffix.

    For ``n`` from ``max_n`` down to ``min_n``: take the last ``n``
    committed tokens, find their RIGHTMOST earlier occurrence in the
    context, and propose (up to) the ``k`` tokens that followed it.
    Longest n wins (a longer match is stronger evidence); no match at
    any n proposes nothing, and the scheduler falls back to a plain
    masked step for that dispatch.

    Per-request state is a pure ACCELERATION index — one
    ``{n-gram: rightmost end position}`` dict per n, extended
    incrementally as tokens commit — so a proposal costs
    O(max_n + k) instead of re-scanning the context per dispatch
    (no-match is this drafter's common case, and it sits on the host
    hot path between model dispatches).  The index is rebuilt from
    ``req.prompt + req.generated`` whenever it is missing or stale
    (a drafter driven without lifecycle calls, a resumed stream), so
    proposals are always a pure function of the committed context —
    preemption and failover need no reconciliation beyond that.
    """

    name = "ngram"

    def __init__(self, max_n: int = 3, min_n: int = 1):
        super().__init__()
        assert 1 <= min_n <= max_n, (min_n, max_n)
        self.max_n = int(max_n)
        self.min_n = int(min_n)
        #: request_id -> {"ctx", "index" {n: {gram: end}}, "indexed"}
        self._state: Dict[int, dict] = {}

    def _extend(self, st: dict) -> None:
        """Index every n-gram ENDING at a position <= len(ctx) - 2:
        the current suffix itself is never indexed, so a lookup
        always lands strictly earlier (ends are indexed in order, so
        each dict entry is the RIGHTMOST eligible occurrence)."""
        ctx = st["ctx"]
        index = st["index"]
        for end in range(st["indexed"], len(ctx) - 1):
            for n in range(self.min_n, self.max_n + 1):
                if end - n + 1 >= 0:
                    index[n][tuple(ctx[end - n + 1:end + 1])] = end
        st["indexed"] = max(st["indexed"], len(ctx) - 1)

    def _sync(self, req) -> dict:
        st = self._state.get(req.request_id)
        L = len(req.prompt) + len(req.generated)
        if st is None or len(st["ctx"]) != L:
            st = {"ctx": list(req.prompt) + list(req.generated),
                  "index": {n: {} for n in range(self.min_n,
                                                self.max_n + 1)},
                  "indexed": 0}
            self._extend(st)
            self._state[req.request_id] = st
        return st

    def start(self, req, tokens: Sequence[int]) -> None:
        self._state.pop(req.request_id, None)
        self._sync(req)

    def commit(self, req, accepted: int,
               committed: Sequence[int]) -> None:
        super().commit(req, accepted, committed)
        st = self._state.get(req.request_id)
        if st is not None and (len(st["ctx"]) + len(committed)
                               == len(req.prompt)
                               + len(req.generated)):
            st["ctx"].extend(int(t) for t in committed)
            self._extend(st)
        else:
            self._state.pop(req.request_id, None)

    def stop(self, req) -> None:
        self._state.pop(req.request_id, None)

    def _propose(self, req, k: int) -> List[int]:
        st = self._sync(req)
        ctx = st["ctx"]
        L = len(ctx)
        for n in range(min(self.max_n, L - 1), self.min_n - 1, -1):
            end = st["index"][n].get(tuple(ctx[L - n:]))
            if end is not None:
                return ctx[end + 1:end + 1 + k]
        return []


class DraftModelDrafter(Drafter):
    """Draft-model speculation: a small model with the engine contract
    (`create_cache` / `make_prefill_fn` / `make_decode_fn`) proposes K
    greedy tokens per round from its own per-request KV cache.

    Cache discipline mirrors the target engine's: per request, the
    draft cache holds KV for every committed token except the last
    (the *pending* input), so one greedy K-scan from the pending token
    yields the proposals while writing their KV.  After the verify
    pass, positions holding accepted drafts are already correct (the
    committed tokens ARE those drafts); the cursor rolls back over the
    rejected tail, and an all-accepted round teacher-forces the one
    missing token (the last draft) so the bonus token becomes the new
    pending input.  Two compiled programs per prompt bucket cover the
    whole lifecycle: the bucketed prefill and the K-greedy rollout
    (plus a single-token catch-up step).

    Prompts (or resumed contexts) longer than every prefill bucket are
    marked undraftable — `propose` returns [] and the scheduler takes
    plain steps for that request.
    """

    name = "draft_model"

    def __init__(self, model, params, max_seq: Optional[int] = None,
                 prefill_buckets: Sequence[int] = (16, 32, 64, 128)):
        super().__init__()
        self.model = model
        self.params = params
        self.max_seq = int(max_seq or model.config.max_seq_len)
        self.buckets = tuple(sorted(
            int(b) for b in prefill_buckets if b <= self.max_seq))
        self._prefill = jax.jit(model.make_prefill_fn())
        decode_fn = model.make_decode_fn()

        def step(params, tok, cache):
            logits, cache = decode_fn(params, tok, cache)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                    cache)

        self._step = jax.jit(step, donate_argnums=(2,))

        def rollout(params, tok, cache, k):
            def body(carry, _):
                tok, cache = carry
                nxt, cache = step(params, tok, cache)
                return (nxt, cache), nxt

            (_, cache), toks = jax.lax.scan(body, (tok, cache),
                                            length=k)
            return toks[:, 0], cache            # (k,), cache

        import functools
        self._rollouts = {}
        self._make_rollout = lambda k: jax.jit(
            functools.partial(rollout, k=k), donate_argnums=(2,))
        #: request_id -> {"cache", "pending", "written", "k"}
        self._state: Dict[int, dict] = {}

    def _rollout_for(self, k: int):
        fn = self._rollouts.get(k)
        if fn is None:
            fn = self._rollouts[k] = self._make_rollout(k)
        return fn

    # -- lifecycle -------------------------------------------------------

    def start(self, req, tokens: Sequence[int]) -> None:
        tokens = [int(t) for t in tokens]
        cache = self.model.create_cache(1, max_seq=self.max_seq)
        written = len(tokens) - 1
        if written > 0:
            bucket = pick_bucket(written, self.buckets)
            if bucket is None or written > self.max_seq:
                # Undraftable here (context outgrew the draft's
                # buckets): the stream still serves, just unassisted.
                self._state.pop(req.request_id, None)
                return
            ids, _ = pad_prompt(tokens[:-1], bucket)
            _, cache = self._prefill(self.params, ids, cache)
            # prefill set offset to the PADDED length; only `written`
            # positions hold real KV (the pad tail above the cursor is
            # masked, then overwritten as the stream grows)
            cache = cache.set_offset(written)
        self._state[req.request_id] = {
            "cache": cache, "pending": tokens[-1], "written": written,
            "proposal": []}

    def _propose(self, req, k: int) -> List[int]:
        st = self._state.get(req.request_id)
        if st is None:
            return []
        if st["written"] + k + 1 > self.max_seq:
            return []                  # draft cache out of headroom
        toks, cache = self._rollout_for(k)(
            self.params, jnp.asarray([st["pending"]], jnp.int32),
            st["cache"])
        st["cache"] = cache            # offset advanced k (rolled
        proposal = [int(t) for t in jax.device_get(toks)]
        st["proposal"] = proposal      # back at commit)
        return proposal

    def commit(self, req, accepted: int,
               committed: Sequence[int]) -> None:
        super().commit(req, accepted, committed)
        st = self._state.get(req.request_id)
        if st is None:
            return
        a = int(accepted)
        proposal, pending = st["proposal"], st["pending"]
        k = len(proposal)
        assert a <= k and len(committed) == a + 1, (a, k,
                                                   len(committed))
        st["proposal"] = []
        new_written = st["written"] + a + 1
        if new_written >= self.max_seq:
            # Draft cache out of sequence headroom: stop assisting
            # this stream (it keeps serving via plain steps).
            self._state.pop(req.request_id, None)
            return
        # The rollout wrote draft KV at positions written ..
        # written+k-1 for [pending, d_1 .. d_{k-1}]; committed tokens
        # occupy written .. written+a.  For a < k the rollout already
        # covered them (c_j == d_{j+1} on the accepted prefix) and the
        # cursor simply rolls back over the rejected tail.  For a == k
        # one position is missing — the last fed-but-unwritten token
        # (d_k after a full-accept round; the pending token itself
        # when no rollout ran this round, k == 0) — teacher-force it.
        if a == k:
            tok = proposal[-1] if k > 0 else pending
            cache = st["cache"].set_offset(new_written - 1)
            _, cache = self._step(
                self.params, jnp.asarray([int(tok)], jnp.int32),
                cache)
            st["cache"] = cache
        else:
            st["cache"] = st["cache"].set_offset(new_written)
        st["written"] = new_written
        st["pending"] = int(committed[-1])

    def stop(self, req) -> None:
        self._state.pop(req.request_id, None)


class BatchedDraftModelDrafter(Drafter):
    """Draft-model speculation on the MASKED BATCHED machinery: the
    draft engine is a shadow of the target engine — one slot-batched
    KV cache, a single-row bucketed prefill + slot insert per
    admission, and ONE masked greedy K-rollout dispatch proposing for
    every slot at once (`engine_batched.make_masked_block_fn` at
    temperature 0 — the proposal pass IS a block dispatch of the
    draft model).

    This is what makes draft-model speculation a wall-clock win:
    `DraftModelDrafter` pays one rollout dispatch PER SLOT per round
    (fine for a request or two, hopeless at batch 24), while this
    drafter's whole round is three batched draft dispatches —
    rollout, cursor reconcile, one teacher-force step — whatever the
    batch size.  Reconciliation is per-row: accepted prefixes keep
    their rollout KV, rejected tails roll the per-row cursor back,
    and full-accept rows teacher-force the one missing token — the
    same rollback discipline as the target engine, one model down.
    Masked draft rows write garbage at their frozen cursors exactly
    like the target's masked rows; the next rollout overwrites every
    such position before any kept output can attend it.

    Requires ``num_slots`` (the target scheduler's) at construction;
    `start` uses ``req.slot``, so the drafter must be driven by the
    scheduler that owns the slot assignment (a cluster should give
    each replica its OWN batched drafter — slot spaces collide
    otherwise; `make_drafter` treats a factory callable as
    per-scheduler for exactly this reason).
    """

    name = "draft_model_batched"
    batched = True

    def __init__(self, model, params, num_slots: int,
                 max_seq: Optional[int] = None,
                 prefill_buckets: Sequence[int] = (16, 32, 64, 128)):
        super().__init__()
        import numpy as np

        from triton_distributed_tpu.serving.engine_batched import (
            _masked_body,
            make_insert_fn,
            make_masked_block_fn,
        )

        self.model = model
        self.params = params
        self.num_slots = int(num_slots)
        self.max_seq = int(max_seq or model.config.max_seq_len)
        self.buckets = tuple(sorted(
            int(b) for b in prefill_buckets if b <= self.max_seq))
        self.cache = model.create_cache(self.num_slots,
                                        max_seq=self.max_seq)
        #: Dummy per-slot keys: the insert/step programs carry a key
        #: operand, but greedy drafting never consumes randomness.
        self.keys = jnp.zeros((self.num_slots, 2), jnp.uint32)
        self._np = np
        self._prefill = jax.jit(model.make_prefill_fn())
        self._insert = make_insert_fn()
        decode_fn = model.make_decode_fn()
        self._blocks = {}
        self._make_block = lambda k: make_masked_block_fn(
            decode_fn, temperature=0.0, block=k)
        import dataclasses as _dc
        body = _masked_body(decode_fn, 0.0, 0, 1.0, 0)

        # One dispatch reconciles the whole batch: ship the per-row
        # cursors, then one masked step teacher-forcing the
        # full-accept rows (masked rows' writes land at positions the
        # next rollout overwrites before any read — the usual
        # masked-row argument).
        def reconcile(params, tf_tokens, off, cache, keys, tf_mask):
            cache = _dc.replace(cache, offset=off)
            _, cache, keys = body(params, tf_tokens, cache, keys,
                                  tf_mask)
            return cache, keys

        self._reconcile = jax.jit(reconcile, donate_argnums=(3, 4))
        #: Host mirrors, per slot: committed-KV cursor, pending input
        #: token, live proposal LENGTH (values stay on device — see
        #: `propose_batched`), and the offset vector the next cursor
        #: reconcile ships (no device fetch per round).
        #: ``written[s] < 0`` = no draft state.
        self.written = np.full(self.num_slots, -1, np.int64)
        self.pending = np.zeros(self.num_slots, np.int32)
        self.proposal_len = np.zeros(self.num_slots, np.int64)
        self._off = np.zeros(self.num_slots, np.int32)
        #: Reusable per-bucket prefill input rows (the scheduler's
        #: `_row_cache` trick: prefill is functional and the insert
        #: consumes the OUTPUT, so admissions never re-zero HBM).
        self._row_caches: Dict[int, object] = {}

    def _row_cache(self, bucket: int):
        row = self._row_caches.get(bucket)
        if row is None:
            row = self.model.create_cache(1, max_seq=bucket)
            self._row_caches[bucket] = row
        return row

    def _block_for(self, k: int):
        fn = self._blocks.get(k)
        if fn is None:
            fn = self._blocks[k] = self._make_block(k)
        return fn

    # -- lifecycle -------------------------------------------------------

    def start(self, req, tokens: Sequence[int]) -> None:
        slot = req.slot
        assert slot is not None, "batched drafter needs req.slot"
        tokens = [int(t) for t in tokens]
        written = len(tokens) - 1
        self.proposal_len[slot] = 0
        if written > self.max_seq - 1:
            self.written[slot] = -1
            return
        if written == 0:
            # Nothing to prefill: cursor 0, pending = the one token.
            self.cache, self.keys = self._insert(
                self.cache, self.keys, self._row_cache(self.buckets[0]),
                jnp.zeros(2, jnp.uint32), jnp.int32(slot),
                jnp.int32(0))
            self.written[slot] = 0
            self._off[slot] = 0
            self.pending[slot] = tokens[0]
            return
        bucket = pick_bucket(written, self.buckets)
        if bucket is None:
            self.written[slot] = -1      # undraftable: plain steps
            return
        ids, _ = pad_prompt(tokens[:-1], bucket)
        _, row = self._prefill(self.params, ids,
                               self._row_cache(bucket))
        self.cache, self.keys = self._insert(
            self.cache, self.keys, row, jnp.zeros(2, jnp.uint32),
            jnp.int32(slot), jnp.int32(written))
        self.written[slot] = written
        self._off[slot] = written
        self.pending[slot] = tokens[-1]

    def propose_batched(self, by_slot, k: int):
        """One masked greedy K-rollout for every drafted slot.

        Returns ``(drafts, n_draft)`` with ``drafts`` a (B, k) DEVICE
        array — the proposal values never come to host: the verify
        program consumes them where they were produced, and the one
        token reconciliation could need (the last draft of a
        full-accept round) is recovered from the COMMITTED stream
        (``committed[-2]``), so a draft round costs zero extra host
        syncs.  ``n_draft`` is host (B,) int32 — k for drafted rows,
        0 elsewhere.  Returns None when no row can draft."""
        np = self._np
        active = np.zeros(self.num_slots, bool)
        tokens = np.zeros(self.num_slots, np.int32)
        for slot in by_slot:
            if (self.written[slot] >= 0
                    and self.written[slot] + k + 1 <= self.max_seq):
                active[slot] = True
                tokens[slot] = self.pending[slot]
        if not active.any():
            return None
        toks, cache, keys = self._block_for(k)(
            self.params, jnp.asarray(tokens), self.cache, self.keys,
            jnp.asarray(active))
        self.cache, self.keys = cache, keys
        n_draft = np.zeros(self.num_slots, np.int32)
        for slot in by_slot:
            if active[slot]:
                self.proposal_len[slot] = k
                n_draft[slot] = k
                self.proposed_tokens += k
        return toks, n_draft

    def commit_batched(self, outcomes) -> None:
        """Reconcile every continuing row with its verify outcome in
        ONE batched dispatch: ship the per-row cursors (from the host
        mirror — no device fetch) fused with one masked step
        teacher-forcing every full-accept row.  ``outcomes`` is
        ``[(req, accepted, committed), ...]``."""
        np = self._np
        if not outcomes:
            return
        off = self._off
        tf_mask = np.zeros(self.num_slots, bool)
        tf_tokens = np.zeros(self.num_slots, np.int32)
        touched = False
        for req, a, committed in outcomes:
            slot = req.slot
            a = int(a)
            self.accepted_tokens += a
            if self.written[slot] < 0:
                continue
            touched = True
            kk = int(self.proposal_len[slot])
            self.proposal_len[slot] = 0
            new_written = int(self.written[slot]) + a + 1
            if new_written >= self.max_seq:
                self.written[slot] = -1
                continue
            if a == kk:
                # One missing draft-KV position: the last fed-but-
                # unwritten token.  A full-accept round committed
                # [d_1..d_k, bonus], so d_k is committed[-2]; with no
                # rollout this round (kk == 0) it is the pending
                # token itself.
                tf_mask[slot] = True
                tf_tokens[slot] = (int(committed[-2]) if kk
                                   else int(self.pending[slot]))
                off[slot] = new_written - 1
            else:
                off[slot] = new_written
            self.written[slot] = new_written
            self.pending[slot] = int(committed[-1])
        if not touched:
            # Every outcome row is stateless (undraftable prompts,
            # outgrown streams): no cursor moved, nothing to ship —
            # skip the reconcile dispatch entirely.
            return
        # `off` is the persistent host mirror, updated below while the
        # dispatch may still be reading its operands: hand the device
        # a copy (on the CPU backend `asarray` can alias the buffer).
        self.cache, self.keys = self._reconcile(
            self.params, jnp.asarray(tf_tokens), jnp.array(off),
            self.cache, self.keys, jnp.asarray(tf_mask))
        # mirror reflects post-teacher-force cursors for next round
        off[tf_mask] += 1

    def commit(self, req, accepted: int,
               committed: Sequence[int]) -> None:
        self.commit_batched([(req, accepted, committed)])

    def stop(self, req) -> None:
        if req.slot is not None:
            self.written[req.slot] = -1
            self.proposal_len[req.slot] = 0


def make_drafter(spec, scheduler=None) -> Drafter:
    """Resolve a `SchedulerConfig.spec_drafter` value: an existing
    `Drafter` passes through; a callable is a PER-SCHEDULER factory
    (called with the scheduler — how a cluster gives each replica its
    own `BatchedDraftModelDrafter` over that replica's slot space);
    ``"ngram"`` (and None) builds the model-free default."""
    if isinstance(spec, Drafter):
        return spec
    if spec is None or spec == "ngram":
        return NgramDrafter()
    if callable(spec):
        drafter = spec(scheduler)
        if not isinstance(drafter, Drafter):
            raise ValueError(
                f"spec_drafter factory returned {type(drafter)}")
        return drafter
    raise ValueError(f"unknown drafter spec {spec!r}")
