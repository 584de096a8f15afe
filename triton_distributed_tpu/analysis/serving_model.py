"""Serving-state model checker: refcounts, sharing, donation.

PR 6's paged serving layer (`serving.pages`) is host-side refcount
code — exactly the class of logic whose bugs (double-free, leaked
pages, a shared page written by a diverging request, a donated cache
touched after dispatch) survive unit tests and surface as corrupted
KV under production load.  This module checks it the way the comm
sanitizer checks kernels: **small-scope exhaustive exploration**.

The checker drives the *real* `PagePool` / `RadixCache` / `PagedKV`
(via the `insert_fn` injection seam — a recording insert and a stub
cache replace the jitted device path, so every transition is pure
host Python) through every interleaving of
``admit / decode / retire(EOS) / preempt / evict`` reachable within a
small scope — a few requests with shared prefixes, a pool of a few
pages — and audits five invariant families after every transition
(the op set includes ``("spec", a)`` speculative verify dispatches at
both accept extremes, so every rollback interleaves with admission,
eviction and preemption):

- **Refcount conservation** (`refcount_leak`): each page's physical
  refcount must equal its holders — private slot pages + acquired
  radix-path references + the tree's own retention — and every
  refcount-0 page must be on the free list.
- **Double free** (`double_free`): negative refcounts, duplicate
  free-list entries, pages freed while still referenced.
- **Write isolation** (`write_shared_page` / `null_page_write`): every
  KV write (prefill scatter and per-step decode) must land in a page
  the writing slot owns privately (refcount exactly 1) — the
  pages-strictly-below-``s-1`` sharing invariant — and a write below
  the request's horizon must never fall through a NULL table entry.
- **Donation discipline** (`use_after_donate`): the cache/keys handles
  consumed by a dispatch (`engine_batched`'s ``donate_argnums``) must
  never be used again; the stub cache trips on any post-donation use.
- **Speculative rollback** (`spec_rollback`): after a verify dispatch
  (K+1 writes, ``accept`` drafts kept) the slot must map EXACTLY the
  pages a plain engine that decoded only the accepted prefix would
  hold — a rejected tail must leave refcounts, page tables and the
  free list as if it never happened (`PagedKV.rollback`).

Findings reuse `analysis.model.Finding`, the CLI exposes the check as
``python -m triton_distributed_tpu.analysis --check serving``, and the
mutation corpus (`tests/test_resource_mutations.py`) seeds one bug per
class to prove each fires.  The property fuzzer
(`tests/test_serving_fuzz.py`) drives the same harness with random
long sequences and cross-validates that every violation class it can
provoke is also caught here statically.
"""

from __future__ import annotations

import copy
import dataclasses
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from triton_distributed_tpu.analysis.model import Finding, FindingKind

__all__ = [
    "ModelScope",
    "ServingHarness",
    "audit_state",
    "check_serving_model",
    "default_scope",
    "tier_scope",
]


class DonationError(RuntimeError):
    """Raised by the stub cache on any use after donation."""


class _StubPagedCache:
    """Host stand-in for `models.kv_cache.PagedKVCache`: carries only
    the donation flag and the geometry `PagedKV` reads."""

    __slots__ = ("page_size", "donated")

    def __init__(self, page_size: int):
        self.page_size = int(page_size)
        self.donated = False

    def bytes_per_page(self) -> int:
        return 4096  # any constant: admission arithmetic is in pages

    def state_bytes_per_slot(self) -> int:
        return 0     # no recurrent layer: every byte is pages

    def _use(self) -> None:
        if self.donated:
            raise DonationError(
                "donated PagedKVCache handle used after the dispatch "
                "that consumed it")

    def successor(self) -> "_StubPagedCache":
        return _StubPagedCache(self.page_size)

    def with_page_table(self, table) -> "_StubPagedCache":
        self._use()
        return self.successor()

    def reset_slot(self, b) -> "_StubPagedCache":
        self._use()
        return self.successor()


class _StubModel:
    """Model stub satisfying `PagedKV`'s `create_paged_cache` probe."""

    def create_paged_cache(self, num_slots, num_pages, page_size, t):
        del num_slots, num_pages, t
        return _StubPagedCache(page_size)


class _StubRow:
    """Row-cache stand-in: `insert_prefill` reads only
    ``row_cache.ks[0].shape[2]`` (the prefill bucket length)."""

    __slots__ = ("ks",)

    def __init__(self, bucket: int):
        self.ks = [np.zeros((1, 1, int(bucket), 1), np.int8)]


@dataclasses.dataclass(frozen=True)
class _Req:
    rid: int
    prompt: Tuple[int, ...]
    max_new: int


@dataclasses.dataclass(frozen=True)
class ModelScope:
    """The small scope the checker explores exhaustively."""

    requests: Tuple[_Req, ...]
    num_slots: int = 2
    usable_pages: int = 5
    page_size: int = 2
    max_seq: int = 12
    prefix_cache: bool = True
    #: Speculative verify width explored by the ``("spec", a)`` ops
    #: (a ∈ {0, spec_k} — full rejection and full acceptance, the
    #: rollback extremes).  0 disables the spec transitions.
    spec_k: int = 2
    #: Spill-tier capacity (pages parked on demote).  >0 arms the
    #: cross-tier exploration: ``evict`` DEMOTES instead of dropping,
    #: admissions over spilled chains PROMOTE, and the audit checks
    #: the tier ledger — a demoted page's content must survive the
    #: round trip bit-exactly and its parked payload must exist for
    #: as long as a radix node points at it.
    spill_pages: int = 0
    #: Arm the ``("adopt", rid)`` op: a PEER PREFIX SHIPMENT for that
    #: request's prompt lands (`PagedKV.adopt_prefix`) — exercising
    #: refcount conservation across the ship seam (adopted pages are
    #: tree-retained, refs-0, and must never be writable).
    adopt: bool = False


def default_scope() -> ModelScope:
    """Four requests over a pool tight enough to force eviction and
    preemption interleavings.  Request 3's prompt extends request 2's
    by a full page, so the radix cache holds a TWO-page chain whose
    second page ends exactly at another request's position ``s-1`` —
    the configuration where an off-by-one in the sharing cap turns
    into a write to a shared page."""
    return ModelScope(requests=(
        _Req(0, (1, 2, 3), 2),
        _Req(1, (1, 2, 4), 2),
        _Req(2, (1, 2, 3, 5), 3),
        _Req(3, (1, 2, 3, 5, 6), 2),
    ), usable_pages=6)


def tier_scope() -> ModelScope:
    """The cross-tier scope: a pool tight enough that eviction (now a
    DEMOTE) fires, a spill tier small enough that it fills, shared
    prefixes whose chains round-trip through the tier on re-admission,
    and the adopt op so peer-shipped chains interleave with demote/
    promote/preempt.  Three requests keep the product of the extra
    ops explorable in seconds."""
    return ModelScope(requests=(
        _Req(0, (1, 2, 3), 2),
        _Req(1, (1, 2, 4, 5), 3),
        _Req(2, (1, 2, 4, 5, 6), 2),
    ), usable_pages=5, spill_pages=2, adopt=True, spec_k=0)


class ServingHarness:
    """One explorable serving state over the real paged structures.

    Mirrors the scheduler's paged path op-for-op
    (`scheduler.ContinuousBatchingScheduler`): admission via
    `can_admit`/`match_prefix`/`insert_prefill`, per-dispatch
    `_prepare_pages` (ensure + preempt-newest on pool-dry), `flush`,
    the donated dispatch, per-step KV writes at ``offset``, retire via
    `release`.  Subclass-override points (`_release_slot`,
    `_dispatch`, `_match_prefix`, `_record_insert` callees) are where
    the mutation corpus seeds its defects.
    """

    def __init__(self, scope: ModelScope):
        from triton_distributed_tpu.serving.pages import PagedKV

        self.scope = scope
        self.findings: List[Finding] = []
        self.kv = PagedKV(
            _StubModel(), num_slots=scope.num_slots,
            max_seq=scope.max_seq, page_size=scope.page_size,
            num_pages=scope.usable_pages,
            prefix_cache=scope.prefix_cache,
            spill_pages=scope.spill_pages,
            insert_fn=self._record_insert)
        # numpy keys: keeps deepcopy of explored states device-free.
        self.kv.keys = np.zeros((scope.num_slots, 2), np.uint32)
        #: Content ledger for the cross-tier audit: physical page ->
        #: the fingerprint of the chain it holds (pure function of
        #: the node's tree position).  The demote/promote/adopt
        #: content seams move fingerprints instead of device arrays,
        #: so the audit can prove "a demoted page's content survives
        #: promote bit-exactly" without a real cache.
        self._content: Dict[int, int] = {}
        if scope.spill_pages or scope.adopt:
            self.kv._write_page = self._model_write_page
            if self.kv.radix is not None:
                self.kv.radix.read_page = self._model_read_page
        #: rid -> (tokens to (re)prefill, remaining max_new)
        self.queued: Dict[int, Tuple[Tuple[int, ...], int]] = {
            r.rid: (r.prompt, r.max_new) for r in scope.requests}
        #: slot -> [rid, prompt_len_at_admission, gen, remaining,
        #:          horizon, admit_seq]
        self.active: Dict[int, list] = {}
        self.done: List[int] = []
        self._admit_seq = 0

    # -- report helpers --------------------------------------------------

    def _flag(self, kind: FindingKind, message: str) -> None:
        self.findings.append(Finding(kind, message,
                                     kernel="serving.paged"))

    def _req(self, rid: int) -> _Req:
        return next(r for r in self.scope.requests if r.rid == rid)

    def _horizon(self, rid: int) -> int:
        r = self._req(rid)
        return min(len(r.prompt) + r.max_new - 1, self.scope.max_seq)

    # -- recording insert (the injected `PagedKV._insert`) --------------

    def _record_insert(self, cache, keys, row, key, slot, page_ids,
                       offset):
        del row, key, slot, offset
        cache._use()
        cache.donated = True
        ids = np.asarray(page_ids)
        from triton_distributed_tpu.models.kv_cache import NULL_PAGE
        for p in ids:
            p = int(p)
            if p == NULL_PAGE:
                continue
            if int(self.kv.pool.refs[p]) != 1:
                self._flag(
                    FindingKind.WRITE_SHARED_PAGE,
                    f"prefill scatter writes physical page {p} with "
                    f"refcount {int(self.kv.pool.refs[p])} — the page "
                    f"is shared (radix-cached or mapped by another "
                    f"slot)")
        return cache.successor(), keys

    # -- cross-tier content model ----------------------------------------

    @staticmethod
    def chain_fp(chain: Tuple[Tuple[int, ...], ...]) -> int:
        """Deterministic fingerprint of a radix chain (what the page
        holding its last chunk must contain)."""
        import zlib
        return zlib.crc32(repr(tuple(chain)).encode())

    def _node_chain(self, node) -> Tuple:
        chain = []
        while node is not None and node.chunk:
            chain.append(node.chunk)
            node = node.parent
        return tuple(reversed(chain))

    def _model_read_page(self, page: int) -> dict:
        """Demote-time content read (replaces `PagedKV._read_page`):
        park the ledger fingerprint of what the page holds."""
        return {"fp": np.asarray([self._content[int(page)]],
                                 np.uint32)}

    def _model_write_page(self, page: int, payload: dict) -> None:
        """Promote/adopt-time content write: install the payload's
        fingerprint as the page's content."""
        self._content[int(page)] = int(payload["fp"][0])

    def _ledger_slot(self, slot: int, shared) -> None:
        """After an insert: the radix nodes the insert NEWLY
        registered (beyond the matched chain) were just written by
        the prefill — record their content.  Matched/restored nodes
        are deliberately NOT re-stamped: a restore installed whatever
        the tier parked (`_model_write_page`), and overwriting it
        with the expected value would mask a corrupting tier."""
        matched = {id(n) for n in shared}
        for node in self.kv._slot_path[slot]:
            if id(node) not in matched and not node.spilled:
                self._content[int(node.page)] = self.chain_fp(
                    self._node_chain(node))

    # -- ops -------------------------------------------------------------

    def _match_prefix(self, tokens):
        return self.kv.match_prefix(list(tokens))

    def can_admit(self, rid: int) -> bool:
        tokens, remaining = self.queued[rid]
        return (remaining > 0
                and self.kv.feasible(len(tokens), remaining)
                and self.kv.can_admit(list(tokens)))

    def admit(self, rid: int) -> None:
        tokens, remaining = self.queued.pop(rid)
        s = len(tokens)
        shared = self._match_prefix(tokens)
        ps = self.scope.page_size
        bucket = -(-s // ps) * ps
        slot = self.kv.insert_prefill(
            _StubRow(bucket), list(tokens), s,
            np.zeros(2, np.uint32), shared)
        self.active[slot] = [rid, s, 0, remaining,
                             self._horizon(rid), self._admit_seq]
        self._admit_seq += 1
        # Content ledger: the path's NEW pages were just prefilled —
        # each now holds its chain's bytes (restored pages keep what
        # the tier gave back, so corruption there stays visible).
        self._ledger_slot(slot, shared)

    def adopt(self, rid: int) -> None:
        """A peer prefix shipment for ``rid``'s prompt lands: the
        shipped payloads carry exactly the content the chain's pages
        hold on the home replica (same params, same positions — the
        ledger fingerprint), and `PagedKV.adopt_prefix` installs
        them refs-0 / tree-retained."""
        tokens, _ = self.queued[rid]
        ps = self.scope.page_size
        n = (len(tokens) - 1) // ps
        chunks = [tuple(tokens[j * ps:(j + 1) * ps])
                  for j in range(n)]
        payloads = [
            {"fp": np.asarray([self.chain_fp(tuple(chunks[:j + 1]))],
                              np.uint32)}
            for j in range(n)]
        self.kv.adopt_prefix(list(tokens[:n * ps]), payloads)

    def _gen_token(self, rid: int, pos: int) -> int:
        # Deterministic symbolic "model output": exploration needs
        # reproducible tokens, not real logits; collisions across
        # requests are welcome (they exercise radix sharing of
        # generated prefixes after preempt/readmit).
        return 50 + (rid * 17 + pos) % 5

    def _preempt_newest(self) -> None:
        slot = max(self.active,
                   key=lambda sl: self.active[sl][5])
        rid, s, gen, remaining, _, _ = self.active.pop(slot)
        r = self._req(rid)
        done_tokens = tuple(self._gen_token(rid, i) for i in range(
            s + gen - len(r.prompt))) if s + gen > len(r.prompt) else ()
        tokens = r.prompt + done_tokens
        self._release_slot(slot)
        self.queued[rid] = (tokens, remaining - gen)

    def _prepare_pages(self, writes: int = 1) -> bool:
        while True:
            ok = True
            for slot in sorted(self.active):
                rid, s, gen, remaining, horizon, _ = self.active[slot]
                need = min(s + gen + writes - 1, horizon,
                           self.scope.max_seq)
                if not self.kv.ensure(slot, need):
                    ok = False
                    break
            if ok:
                return True
            if len(self.active) <= 1:
                self._flag(
                    FindingKind.REFCOUNT_LEAK,
                    "page pool cannot hold a sole feasible request — "
                    "pages are pinned by nothing reachable "
                    "(admission/eviction accounting broken)")
                return False
            self._preempt_newest()

    def _dispatch(self) -> None:
        """The donated step: consume the cache/keys handles, install
        the successors (what the scheduler's
        ``self.slots.cache = cache`` reassignment does)."""
        cache = self.kv.cache
        cache._use()
        cache.donated = True
        self.kv.cache = cache.successor()

    def _check_write(self, slot: int, pos: int, horizon: int,
                     what: str) -> None:
        """One KV write at absolute position ``pos``: must land in a
        private refcount-1 page, or fall through NULL only at/above
        the horizon."""
        from triton_distributed_tpu.models.kv_cache import NULL_PAGE
        ps = self.scope.page_size
        phys = int(self.kv._table[slot, pos // ps])
        if phys == NULL_PAGE:
            if pos < horizon:
                self._flag(
                    FindingKind.NULL_PAGE_WRITE,
                    f"{what} write at position {pos} (below the "
                    f"request horizon {horizon}) falls through a "
                    f"NULL page-table entry — KV silently dropped")
        else:
            refs = int(self.kv.pool.refs[phys])
            private = phys in self.kv._slot_pages[slot]
            if refs != 1 or not private:
                self._flag(
                    FindingKind.WRITE_SHARED_PAGE,
                    f"{what} write at position {pos} lands in "
                    f"physical page {phys} (refcount {refs}, "
                    f"private={private}) — violates the pages-"
                    f"strictly-below-s-1 sharing invariant")

    def decode(self) -> None:
        if not self._prepare_pages():
            return
        self.kv.flush()
        self._dispatch()
        for slot in sorted(self.active):
            row = self.active[slot]
            rid, s, gen, remaining, horizon, _ = row
            pos = s + gen - 1            # the step's KV write position
            self._check_write(slot, pos, horizon, "decode")
            row[2] += 1
        # auto-retire rows that hit their horizon
        for slot in [sl for sl, r in self.active.items()
                     if r[2] >= r[3]]:
            self.retire(slot)

    def spec_decode(self, accept: int) -> None:
        """One speculative verify dispatch: K proposed tokens + the
        bonus position scored in one program (K+1 writes per active
        row), every row accepting ``accept`` drafts (capped at its
        own remaining budget) and committing ``accept+1`` tokens; the
        rejected tail's pages must roll back
        (`scheduler._spec_outcome` → `PagedKV.rollback`).  Exploring
        accept at both extremes over every interleaving models "any
        draft agreement the drafters could produce"."""
        K = self.scope.spec_k
        if not self._prepare_pages(writes=K + 1):
            return
        self.kv.flush()
        self._dispatch()
        for slot in sorted(self.active):
            row = self.active[slot]
            rid, s, gen, remaining, horizon, _ = row
            for j in range(K + 1):       # the verify pass's writes
                self._check_write(slot, s + gen - 1 + j, horizon,
                                  "spec verify")
            # the scheduler's cap is the REMAINING budget
            # (max_new - generated - 1), so the model never commits
            # past a budget the real engine would have retired at
            a = min(int(accept), remaining - gen - 1, K)
            row[2] += a + 1
            # the scheduler's rollback target: pages covering
            # [0, min(offset', horizon)), offset' = off0 + a + 1
            self._rollback(slot, min(s + row[2] - 1, horizon))
        for slot in [sl for sl, r in self.active.items()
                     if r[2] >= r[3]]:
            self.retire(slot)

    def _rollback(self, slot: int, keep_positions: int) -> None:
        """Mutation seam: the real `PagedKV.rollback`."""
        self.kv.rollback(slot, keep_positions)

    def retire(self, slot: int) -> None:
        rid = self.active[slot][0]
        self.active.pop(slot)
        self._release_slot(slot)
        self.done.append(rid)

    def _release_slot(self, slot: int) -> None:
        self.kv.release(slot)

    def evict_one(self) -> None:
        self.kv.radix.evict(1)

    # -- enabled transitions --------------------------------------------

    def ops(self) -> List[Tuple]:
        out: List[Tuple] = []
        for rid in sorted(self.queued):
            if self.can_admit(rid):
                out.append(("admit", rid))
        if self.active:
            out.append(("decode",))
            K = self.scope.spec_k
            if K and all(
                    self.scope.max_seq - r[1] - r[2] + 1 >= K + 1
                    for r in self.active.values()):
                # Spec is available only with K+1 writes of max_seq
                # headroom on every row (the scheduler's exact
                # near-horizon fallback).  Full rejection and full
                # acceptance — the rollback extremes; intermediates
                # differ only in magnitude.
                out.append(("spec", 0))
                out.append(("spec", K))
            for slot in sorted(self.active):
                if self.active[slot][2] >= 1:
                    out.append(("retire", slot))
        if self.kv.radix is not None and self.kv.radix.cached_pages:
            out.append(("evict",))
        if self.scope.adopt and self.kv.radix is not None:
            ps = self.scope.page_size
            for rid in sorted(self.queued):
                tokens = self.queued[rid][0]
                if (len(tokens) - 1) // ps > 0:
                    out.append(("adopt", rid))
        return out

    def apply(self, op: Tuple) -> None:
        if op[0] == "admit":
            self.admit(op[1])
        elif op[0] == "adopt":
            self.adopt(op[1])
        elif op[0] == "decode":
            self.decode()
        elif op[0] == "spec":
            self.spec_decode(op[1])
        elif op[0] == "retire":
            self.retire(op[1])
        elif op[0] == "evict":
            self.evict_one()
        else:  # pragma: no cover
            raise ValueError(op)

    # -- canonical fingerprint for memoization --------------------------

    def fingerprint(self) -> Tuple:
        kv = self.kv

        def tree(node) -> Tuple:
            # Spill/origin state is behavior-relevant (a spilled node
            # is allocation DEMAND, an adopted node a peer-tier hit):
            # states differing only there must not be conflated.
            return (node.chunk, int(node.page), int(node.refs),
                    node.spilled,
                    (node.spill_key is not None
                     and self.kv.spill is not None
                     and self.kv.spill.has(node.spill_key)),
                    node.origin,
                    tuple(sorted(tree(c)
                                 for c in node.children.values())))

        radix = tree(kv.radix._root) if kv.radix is not None else None
        return (
            tuple(sorted((slot, tuple(r[:5]))
                         for slot, r in self.active.items())),
            # Relative admission order (not the raw counter): it picks
            # the preemption victim, so it is behavior-relevant; the
            # absolute counter is not and would defeat memoization.
            tuple(sorted(self.active,
                         key=lambda sl: self.active[sl][5])),
            tuple(sorted((rid, t) for rid, t in self.queued.items())),
            tuple(int(x) for x in kv.pool.refs),
            tuple(sorted(kv.pool._free)),
            tuple(tuple(int(x) for x in row) for row in kv._table),
            radix,
        )


# ---------------------------------------------------------------------------
# Invariant audit
# ---------------------------------------------------------------------------

def audit_state(harness: ServingHarness) -> List[Finding]:
    """Refcount-conservation / free-list / tree-consistency audit of
    one state (independent of how it was reached)."""
    kv = harness.kv
    pool = kv.pool
    findings: List[Finding] = []

    def flag(kind, msg):
        findings.append(Finding(kind, msg, kernel="serving.paged"))

    expected = np.zeros(pool.num_pages, np.int64)
    for slot in range(kv.num_slots):
        for p in kv._slot_pages[slot]:
            expected[p] += 1
        for node in kv._slot_path[slot]:
            expected[node.page] += 1
    path_refs: Dict[int, int] = {}
    for slot in range(kv.num_slots):
        for node in kv._slot_path[slot]:
            path_refs[id(node)] = path_refs.get(id(node), 0) + 1
    if kv.radix is not None:
        stack = list(kv.radix._root.children.values())
        while stack:
            node = stack.pop()
            expected[node.page] += 1           # tree retention ref
            stack.extend(node.children.values())
            held = path_refs.get(id(node), 0)
            if node.refs != held:
                kind = (FindingKind.DOUBLE_FREE if node.refs < held
                        else FindingKind.REFCOUNT_LEAK)
                flag(kind,
                     f"radix node for page {node.page} counts "
                     f"{node.refs} live request(s) but {held} slot "
                     f"path(s) actually hold it")

    # Cross-tier integrity (the KV hierarchy audit): every spilled
    # node's parked content must EXIST in the tier for as long as the
    # node points at it (a dangling key means the promote on the next
    # prefix hit asserts or installs garbage), survive the round trip
    # bit-exactly (the ledger fingerprint is a pure function of the
    # chain, so drift anywhere across demote → park → promote → adopt
    # shows up here), and the spilled-node counter must agree with
    # the tree.
    if kv.radix is not None and kv.spill is not None:
        content_armed = bool(harness.scope.spill_pages
                             or harness.scope.adopt)
        n_spilled = 0
        stack = [(c, (c.chunk,))
                 for c in kv.radix._root.children.values()]
        while stack:
            node, chain = stack.pop()
            for c in node.children.values():
                stack.append((c, chain + (c.chunk,)))
            if node.spilled:
                n_spilled += 1
                if not kv.spill.has(node.spill_key):
                    flag(FindingKind.TIER_CORRUPT,
                         f"radix node for chain {chain} is marked "
                         f"spilled (key {node.spill_key}) but the "
                         f"tier no longer holds its content — the "
                         f"promote on the next prefix hit is "
                         f"DANGLING (demoted page lost)")
                elif content_armed:
                    payload = kv.spill.load(node.spill_key)
                    fp = int(payload["fp"][0])
                    if fp != harness.chain_fp(chain):
                        flag(FindingKind.TIER_CORRUPT,
                             f"parked content for chain {chain} "
                             f"(key {node.spill_key}) does not match "
                             f"what was demoted — the promote would "
                             f"install wrong KV bytes")
            elif content_armed:
                got = harness._content.get(int(node.page))
                if got != harness.chain_fp(chain):
                    flag(FindingKind.TIER_CORRUPT,
                         f"physical page {node.page} for chain "
                         f"{chain} holds fingerprint {got} — not the "
                         f"chain's content (a promote/adopt wrote "
                         f"the wrong bytes back)")
        if kv.radix.spilled_nodes != n_spilled:
            flag(FindingKind.TIER_CORRUPT,
                 f"spilled-node counter {kv.radix.spilled_nodes} "
                 f"disagrees with the tree ({n_spilled} spilled "
                 f"node(s)) — demote/promote bookkeeping drifted")

    # Mapping-extent invariant (the speculative-rollback audit): an
    # active slot must map exactly the pages a plain engine at its
    # committed position would hold — pages covering
    # [0, min(max(s, s+gen-1), horizon)).  More is a rejected verify
    # tail whose cursor was never rolled back (pages pinned for KV
    # that never happened); less is a mapping hole below the cursor.
    from triton_distributed_tpu.models.kv_cache import pages_for
    for slot, row in harness.active.items():
        rid, s, gen, remaining, horizon, _ = row
        expect = pages_for(min(max(s, s + gen - 1), horizon),
                           harness.scope.page_size)
        mapped = int(kv._mapped[slot])
        if mapped != expect:
            what = ("ahead of" if mapped > expect else "behind")
            flag(FindingKind.SPEC_ROLLBACK,
                 f"slot {slot} (request {rid}) maps {mapped} page(s) "
                 f"but its committed stream (s={s}, gen={gen}) "
                 f"needs exactly {expect} — the page mapping is "
                 f"{what} the committed KV cursor (speculative "
                 f"rollback broken)")

    free = list(pool._free)
    free_set = set(free)
    if len(free) != len(free_set):
        dup = sorted(p for p in free_set if free.count(p) > 1)
        flag(FindingKind.DOUBLE_FREE,
             f"free list holds duplicate page(s) {dup} — the same "
             f"page will be handed to two requests")
    for p in range(1, pool.num_pages):
        refs = int(pool.refs[p])
        if refs < 0:
            flag(FindingKind.DOUBLE_FREE,
                 f"page {p} refcount is negative ({refs})")
            continue
        if refs != int(expected[p]):
            kind = (FindingKind.REFCOUNT_LEAK if refs > expected[p]
                    else FindingKind.DOUBLE_FREE)
            what = ("exceeds" if refs > expected[p] else "is below")
            flag(kind,
                 f"page {p} refcount {refs} {what} its reachable "
                 f"holders ({int(expected[p])}: slot-private + "
                 f"radix-path + tree retention)")
        if refs == 0 and p not in free_set:
            flag(FindingKind.REFCOUNT_LEAK,
                 f"page {p} has refcount 0 but never returned to the "
                 f"free list — pool capacity leaks")
        if refs > 0 and p in free_set:
            flag(FindingKind.DOUBLE_FREE,
                 f"page {p} is on the free list while still "
                 f"referenced ({refs})")
    return findings


# ---------------------------------------------------------------------------
# Exhaustive small-scope exploration
# ---------------------------------------------------------------------------

def check_serving_model(scope: Optional[ModelScope] = None,
                        harness_factory=None,
                        max_states: int = 4000,
                        max_depth: int = 14) -> List[Finding]:
    """Explore every op interleaving reachable within the scope
    (breadth-first, canonical-state memoized) and return the deduped
    findings.  Empty list = the serving layer holds its invariants
    over *every* admit/decode/preempt/retire/evict order the scope
    can express."""
    factory = harness_factory or ServingHarness
    root = factory(scope or default_scope())
    seen = {root.fingerprint()}
    frontier: List[Tuple[ServingHarness, int]] = [(root, 0)]
    findings: Dict[Tuple, Finding] = {}
    states = 0

    def collect(h: ServingHarness, extra: Sequence[Finding] = ()):
        for f in itertools.chain(h.findings, extra):
            findings.setdefault((f.kind, f.message), f)
        h.findings = []

    collect(root, audit_state(root))
    while frontier and states < max_states:
        state, depth = frontier.pop(0)
        if depth >= max_depth:
            continue
        for op in state.ops():
            child = copy.deepcopy(state)
            ok = True
            try:
                child.apply(op)
            except DonationError as e:
                child._flag(FindingKind.USE_AFTER_DONATE, str(e))
                ok = False
            except AssertionError as e:
                child._flag(
                    FindingKind.DOUBLE_FREE,
                    f"serving op {op} tripped an allocator assertion "
                    f"({e!r}) — refcount went negative or a slot was "
                    f"released twice")
                ok = False
            collect(child, audit_state(child) if ok else ())
            states += 1
            if not ok:
                continue
            fp = child.fingerprint()
            if fp not in seen:
                seen.add(fp)
                frontier.append((child, depth + 1))
    return sorted(findings.values(), key=lambda f: (f.kind.value,
                                                    f.message))
