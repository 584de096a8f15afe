"""Paged KV management: page pool, radix prefix cache, slot manager.

The serving-scale replacement for `serving.slots.SlotKV`.  Three
host-side structures cooperate over one donated `PagedKVCache`:

- `PagePool` — the physical allocator: a free list plus per-page
  refcounts over ``num_pages`` fixed-size pages (page 0 reserved as
  the NULL/trash page).  A request pins ``ceil(len / page_size)``
  pages — its TRUE footprint — instead of `SlotKV`'s max-context
  worst case, which is where the 4–8× admitted-concurrency headroom
  on the same HBM budget comes from.

- `RadixCache` — prefix sharing: a radix tree over page-granular
  token chunks.  Full prompt pages are registered at admission;
  later requests whose prompt starts with the same chunks map the
  SAME physical pages (refcounted) instead of re-prefilling and
  re-storing them.  Unreferenced nodes stay cached and are evicted
  LRU, leaves first, when the pool runs dry.  Only pages strictly
  below position ``s-1`` are ever shared: the serving insert
  recomputes position ``s-1`` and decode writes from there on, so
  every page a request can WRITE is private by construction
  (copy-on-extend at page granularity — divergent tails never share).

- `PagedKV` — the slot manager the scheduler drives: per-slot page
  tables (host mirror, re-shipped to the device cache only when an
  allocation changes it), incremental page allocation as sequences
  grow (`ensure`), page-based admission/feasibility arithmetic, and
  the jitted paged insert.  API mirrors `SlotKV` where the scheduler
  needs it (`can_admit` / `insert_prefill` / `release` /
  `active_mask` / occupancy properties).  A model with recurrent
  layers also holds a fixed-size state a SLOT (`models.kv_cache`):
  that pool is sized by slots, paid for out of the byte budget before
  any page, written whole by the insert and zeroed by `release`; pages,
  prefix sharing and spill concern its attention layers alone.
  PAGES BY LAYER KIND: a model with sliding-window layers
  (``model.window`` tokens) keeps THEIR K/V in a second pool behind a
  second page table (`models.kv_cache`), sized by slots x (window + a
  page) and paid for out of the byte budget like the state pool.  A
  row holds there only the pages its next query can still see: what
  lies behind the window goes back to that pool as the row grows — at
  every decode dispatch (`ensure`) and between the pieces of a
  prefill (`insert_rows`) — so that pool never runs dry.  Window pages
  are private, always: the radix tree, spill and peer shipping know
  the full layers' pages alone, and a prefix hit on such a model
  shares those for storage while its prefill covers the prompt from
  position 0 (`serving.scheduler`).

- `SpillPool` — graceful degradation under KV pressure: when the
  radix cache must evict a refcount-0 prefix page, its CONTENT is
  first parked in host memory (device HBM is the scarce resource;
  host DRAM is not).  The node stays in the tree marked spilled, so
  a later prefix hit restores it — a fresh physical page is
  allocated and the parked bytes written back, bit-exactly (numpy
  round-trip of the stored dtypes) — instead of silently losing the
  prefix.  This is what keeps *prefix-dependent admission* alive
  under pressure: a prompt longer than every prefill bucket is only
  servable through a cached prefix + suffix-only prefill, and
  without spill one eviction turns it from servable into a load
  shed.  Spill is opt-in (``spill_pages``/`SchedulerConfig.
  spill_pages` > 0); with it off, eviction behaves exactly as
  before.  Counters: ``serving_kv_spill_out_pages_total`` /
  ``serving_kv_spill_in_pages_total``.

Invariant that makes mid-stream allocation safe: a request was only
admitted if its WORST-CASE total pages fit the usable pool, and
everything not referenced by a live request is evictable — so after
evicting the radix cache and preempting down to one request, that
request can always grow to its horizon.  The scheduler preempts
newest-first when `ensure` fails (see `scheduler.ContinuousBatching
Scheduler._preempt`).
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from triton_distributed_tpu.models.kv_cache import (
    NULL_PAGE,
    PagedKVCache,
    pages_for,
    zero_state_rows,
)
from triton_distributed_tpu.serving.engine_batched import (
    make_paged_insert_fn,
    make_paged_rows_fn,
)


class PagePool:
    """Free list + refcounts over physical pages 1..num_pages-1
    (page `NULL_PAGE` is reserved and never allocated)."""

    def __init__(self, num_pages: int):
        assert num_pages >= 2, num_pages
        self.num_pages = num_pages
        self._free: List[int] = list(range(1, num_pages))
        self.refs = np.zeros(num_pages, np.int32)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def usable_pages(self) -> int:
        return self.num_pages - 1

    @property
    def used_pages(self) -> int:
        return self.usable_pages - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n pages with refcount 1, or None (caller evicts/preempts)."""
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        self.refs[ids] = 1
        return ids

    def incref(self, ids: Sequence[int]) -> None:
        for i in ids:
            self.refs[i] += 1

    def decref(self, ids: Sequence[int]) -> None:
        """Drop one reference; pages hitting refcount 0 return to the
        free list.  (Radix-cached pages are kept alive by the tree's
        OWN reference — eviction drops it.)"""
        for i in ids:
            self.refs[i] -= 1
            assert self.refs[i] >= 0, (i, self.refs[i])
            if self.refs[i] == 0:
                self._free.append(i)


def _count_metric(name: str, n: int = 1, **labels) -> None:
    from triton_distributed_tpu.observability.metrics import (
        count_metric)
    count_metric(name, n, **labels)


_next_spill_key = itertools.count(1)


class SpillPool:
    """Host-memory parking lot for spilled KV pages.

    ``put`` parks one page's content (a dict of numpy arrays, one
    k/v [+scale] entry per layer) under a unique key; ``take``
    retrieves-and-forgets it on restore.  Bounded in PAGES
    (``max_pages``): a full pool refuses the spill and the caller
    degrades to plain eviction — best-effort preservation, never
    unbounded host growth.
    """

    def __init__(self, max_pages: int):
        assert max_pages >= 1, max_pages
        self.max_pages = int(max_pages)
        self._store: Dict[int, dict] = {}
        self.spilled_out = 0
        self.spilled_in = 0
        self.rejected = 0

    @property
    def pages(self) -> int:
        return len(self._store)

    @property
    def bytes(self) -> int:
        return sum(a.nbytes for p in self._store.values()
                   for a in p.values())

    def can_accept(self) -> bool:
        """May one more page be parked right now?  (`RadixCache.evict`
        checks this BEFORE the device->host page read, and
        `serving.kvtier.KVTier` chains it: a full host pool demotes
        onward to disk instead of refusing.)"""
        return len(self._store) < self.max_pages

    def has(self, key: int) -> bool:
        return key in self._store

    def load(self, key: int) -> Optional[dict]:
        """Non-destructive read (the tier-integrity probe; host
        memory never corrupts, so None here means a DANGLING key —
        the parked content is gone while the radix node still points
        at it)."""
        return self._store.get(key)

    def oldest_key(self) -> Optional[int]:
        """Least-recently-parked key (dict insertion order) — the
        write-back victim `KVTier` demotes to disk on host overflow.
        """
        return next(iter(self._store), None)

    def take_silent(self, key: int) -> Optional[dict]:
        """Remove without touching the spill-in counters: a
        host→disk demotion is a migration, not a promote."""
        return self._store.pop(key, None)

    def put(self, key: int, payload: dict) -> bool:
        """Park one page; False = pool full (caller evicts plainly)."""
        if len(self._store) >= self.max_pages:
            self.rejected += 1
            return False
        self._store[key] = payload
        self.spilled_out += 1
        _count_metric("serving_kv_spill_out_pages_total")
        return True

    def take(self, key: int) -> Optional[dict]:
        payload = self._store.pop(key, None)
        if payload is not None:
            self.spilled_in += 1
            _count_metric("serving_kv_spill_in_pages_total")
        return payload

    def drop(self, key: int) -> None:
        self._store.pop(key, None)


class _RadixNode:
    __slots__ = ("children", "parent", "chunk", "page", "refs",
                 "last_use", "spill_key", "origin")

    def __init__(self, parent, chunk: Tuple[int, ...], page: int):
        self.children: Dict[Tuple[int, ...], "_RadixNode"] = {}
        self.parent = parent
        self.chunk = chunk
        self.page = page
        #: Live requests currently mapping this page (the tree's own
        #: retention is NOT counted here — refs 0 means evictable).
        self.refs = 0
        self.last_use = 0
        #: SpillPool key when this node's page content is parked in
        #: host memory (``page`` is then NULL_PAGE); None = physical.
        self.spill_key: Optional[int] = None
        #: Which cache tier this page's content arrived from when it
        #: is not yet consumed locally: "peer" for a chain adopted
        #: from a peer replica's shipment (`PagedKV.adopt_prefix`).
        #: The FIRST admission that consumes it counts a peer-tier
        #: hit and clears the tag (after that it is device-resident
        #: like any cached page).
        self.origin: Optional[str] = None

    @property
    def spilled(self) -> bool:
        return self.spill_key is not None


class RadixCache:
    """Page-granular radix tree: node = one full page of prompt
    tokens, keyed by that page's token tuple under its parent.  The
    tree holds one pool reference per cached page; live requests add
    theirs via `acquire`.  `evict` frees LRU refcount-0 leaves."""

    def __init__(self, pool: PagePool, page_size: int,
                 spill: Optional[SpillPool] = None,
                 read_page=None):
        self.pool = pool
        self.page_size = page_size
        self._root = _RadixNode(None, (), NULL_PAGE)
        self._clock = 0
        self.cached_pages = 0   # PHYSICAL pages the tree retains
        #: Pages at refcount 0 (evictable) — maintained incrementally
        #: so the admission path never walks the tree.
        self._idle_pages = 0
        self.hit_tokens = 0
        self.miss_tokens = 0
        self.evicted_pages = 0
        #: `evict` calls, and the pages they freed (destroyed OR
        #: spilled: what `evicted_pages` leaves out) — mirrored as
        #: ``serving_kv_evict_calls_total`` /
        #: ``serving_kv_evicted_pages_total``.
        self.evict_calls = 0
        self.freed_pages = 0
        #: Spill-before-evict (optional): the host pool and the
        #: ``read_page(page) -> payload`` content reader (the owning
        #: `PagedKV` wires both when spill is enabled).
        self.spill = spill
        self.read_page = read_page
        self.spilled_nodes = 0

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def match(self, tokens: Sequence[int]) -> List[_RadixNode]:
        """Longest chain of cached full pages prefixing ``tokens``."""
        ps = self.page_size
        node, path = self._root, []
        j = 0
        while True:
            chunk = tuple(tokens[j * ps:(j + 1) * ps])
            if len(chunk) < ps:
                break
            child = node.children.get(chunk)
            if child is None:
                break
            path.append(child)
            node = child
            j += 1
        return path

    def acquire(self, path: Sequence[_RadixNode]) -> None:
        """Pin ``path`` for one request.  Spilled nodes are pinned
        too (their refs keep them from being pruned) but hold no
        pool reference until the caller restores them
        (`PagedKV.insert_prefill`'s restore pass adds both the
        tree's and the request's pool refs)."""
        t = self._tick()
        for n in path:
            if n.refs == 0 and not n.spilled:
                self._idle_pages -= 1
            n.refs += 1
            n.last_use = t
            if not n.spilled:
                self.pool.incref([n.page])

    def release(self, path: Sequence[_RadixNode]) -> None:
        t = self._tick()
        for n in path:
            assert not n.spilled, "released node was never restored"
            n.refs -= 1
            assert n.refs >= 0
            if n.refs == 0:
                self._idle_pages += 1
            n.last_use = t
            self.pool.decref([n.page])

    def restore(self, node: _RadixNode, page: int) -> None:
        """Re-materialize a spilled node onto freshly allocated
        physical ``page`` (the caller has already written the parked
        content back and holds the allocation's refcount-1, which
        becomes the TREE's retention ref)."""
        assert node.spilled and node.page == NULL_PAGE
        node.spill_key = None
        node.page = int(page)
        self.cached_pages += 1
        self.spilled_nodes -= 1

    def extend(self, parent_path: Sequence[_RadixNode],
               tokens: Sequence[int], first_page: int,
               page_ids: Sequence[int]) -> List[_RadixNode]:
        """Register pages ``first_page .. first_page+len(page_ids)-1``
        of ``tokens`` (already written, ownership transferred from the
        caller's private allocation — the tree adds its own pool ref).
        Returns the new nodes, ACQUIRED for the calling request (the
        caller's original allocation ref becomes the request's)."""
        ps = self.page_size
        node = parent_path[-1] if parent_path else self._root
        t = self._tick()
        out = []
        for i, page in enumerate(page_ids):
            j = first_page + i
            chunk = tuple(tokens[j * ps:(j + 1) * ps])
            assert len(chunk) == ps, (j, len(chunk))
            assert chunk not in node.children, "duplicate radix chain"
            child = _RadixNode(node, chunk, page)
            child.refs = 1            # the inserting request
            child.last_use = t
            node.children[chunk] = child
            # tree retention ref (beyond the request's)
            self.pool.incref([page])
            self.cached_pages += 1
            node = child
            out.append(child)
        return out

    def adopt(self, parent_path: Sequence[_RadixNode],
              chunk: Tuple[int, ...], page: int) -> _RadixNode:
        """Register one PEER-SHIPPED page under ``parent_path``: the
        content was written into freshly allocated physical ``page``
        by the caller (`PagedKV.adopt_prefix`), whose allocation ref
        BECOMES the tree's retention ref (no incref here).  Unlike
        `extend`, the node starts at refs 0 — no live request holds
        it yet; it is immediately evictable, exactly like a cached
        prefix left behind by a retired request — tagged
        ``origin="peer"`` so the first local consumption counts a
        peer-tier hit."""
        node = parent_path[-1] if parent_path else self._root
        chunk = tuple(chunk)
        assert chunk not in node.children, "adopt over an existing chain"
        child = _RadixNode(node, chunk, int(page))
        child.last_use = self._tick()
        child.origin = "peer"
        node.children[chunk] = child
        self.cached_pages += 1
        self._idle_pages += 1
        return child

    def drop_subtree(self, node: _RadixNode) -> None:
        """Remove an UNHELD spilled node (and its necessarily-spilled
        subtree) whose parked content failed its integrity probe —
        the tier-degradation path: the chain below it recomputes.
        """
        assert node.spilled and node.refs == 0, (node.refs,
                                                node.spill_key)
        self._prune(node)
        self.evicted_pages += 1

    def evictable_pages(self) -> int:
        """Pages the tree could free right now (refcount-0 nodes —
        ancestors of a refs>0 node are themselves refs>0, so every
        refs-0 subtree is fully evictable).  O(1): the counter is
        maintained by acquire/release/evict, keeping the per-step
        admission check off the tree."""
        return self._idle_pages

    def _frontier_leaf(self, node: _RadixNode) -> bool:
        """May ``node``'s physical page be freed right now?  Unheld,
        physical, and every child already spilled (spill keeps the
        node in the tree, so "leaf" means no *physical* subtree; with
        spill disabled no node is ever spilled and this is exactly
        the old childless test)."""
        return (node.refs == 0 and not node.spilled
                and all(c.spilled for c in node.children.values()))

    def _prune(self, node: _RadixNode) -> None:
        """Remove a spilled-or-evicted node AND its (necessarily
        spilled) subtree from the tree, dropping parked content."""
        stack = [node]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            n.children.clear()
            if n.spilled:
                if self.spill is not None:
                    self.spill.drop(n.spill_key)
                n.spill_key = None
                self.spilled_nodes -= 1
        del node.parent.children[node.chunk]

    def evict(self, need: int) -> int:
        """Free up to ``need`` pages, LRU leaves first.  Returns how
        many were freed.  One tree walk collects the evictable-leaf
        frontier; freeing a leaf promotes its parent into the
        frontier when it becomes an evictable leaf itself.

        With a `SpillPool` wired, each victim's content is parked in
        host memory first and the node stays in the tree (spilled, a
        later prefix hit restores it); a full spill pool degrades to
        plain eviction — the page is freed either way, which is what
        the caller needs."""
        frontier = []                      # (last_use, id, node)
        stack = list(self._root.children.values())
        while stack:
            node = stack.pop()
            if self._frontier_leaf(node):
                heapq.heappush(frontier,
                               (node.last_use, id(node), node))
            stack.extend(node.children.values())
        freed = 0
        while freed < need and frontier:
            _, _, victim = heapq.heappop(frontier)
            parent = victim.parent
            spilled = False
            if self.spill is not None and self.read_page is not None:
                # Capacity check BEFORE the device->host page copy:
                # a full pool (its steady state under sustained
                # pressure) must not pay a discarded read per victim.
                # (`KVTier.can_accept` extends this down the chain: a
                # full host pool still accepts by demoting to disk.)
                if self.spill.can_accept():
                    key = next(_next_spill_key)
                    spilled = self.spill.put(
                        key, self.read_page(victim.page))
                    if spilled:
                        victim.spill_key = key
                        self.spilled_nodes += 1
                else:
                    self.spill.rejected += 1
            self.pool.decref([victim.page])
            if spilled:
                victim.page = NULL_PAGE
            else:
                self._prune(victim)
                self.evicted_pages += 1
            self.cached_pages -= 1
            self._idle_pages -= 1
            freed += 1
            if (parent is not self._root
                    and self._frontier_leaf(parent)):
                heapq.heappush(frontier,
                               (parent.last_use, id(parent), parent))
        self.evict_calls += 1
        self.freed_pages += freed
        _count_metric("serving_kv_evict_calls_total")
        if freed:
            _count_metric("serving_kv_evicted_pages_total", freed)
        return freed


class PagedKV:
    """Paged slot manager with radix prefix reuse — the `SlotKV`
    analogue the scheduler drives in ``kv_layout="paged"`` mode.

    ``num_pages`` counts USABLE pages (the reserved null page is added
    internally).  When ``kv_budget_bytes`` is given instead, the pool
    is sized to ``budget // bytes_per_page`` — admission arithmetic is
    then in actual pages, so a rejection reason reflects what the
    allocator can truly hold, not a max-context estimate.
    """

    def __init__(self, model, num_slots: int, max_seq: int,
                 page_size: int = 16,
                 num_pages: Optional[int] = None,
                 kv_budget_bytes: Optional[int] = None,
                 prefix_cache: bool = True,
                 spill_pages: int = 0,
                 spill_disk_dir: Optional[str] = None,
                 spill_disk_pages: int = 0,
                 insert_fn=None):
        self.page_size = ps = int(page_size)
        #: > 1: the model generates by blocks of this many positions
        #: (`models.sdar_moe`).  A request then has a BLOCK IN FLIGHT:
        #: the pages mapped at and past its cursor hold provisional
        #: rows that every pass of the block overwrites, final only
        #: once the pass that carries the block's commit (the next
        #: block's first) has written them — they are private
        #: (`ensure` allocates them to the slot alone; no radix node
        #: ever covers a position at or past the cursor:
        #: `insert_prefill`) and go back to the pool at `release`.
        self.block = int(getattr(model, "block_length", 0) or 0)
        assert self.block <= 1 or ps % self.block == 0, (
            "a block must not straddle a page", ps, self.block)
        #: > 0: the model's sliding-window layers see that many tokens
        #: back (module docstring: pages by layer kind).
        self.window = int(getattr(model, "window", 0) or 0)
        assert not (self.window and self.block > 1)
        self.max_seq = int(max_seq)
        self.pages_per_seq = t = pages_for(self.max_seq, ps)
        self.num_slots = int(num_slots)
        # Size the pool: explicit pages > byte budget > slot-engine
        # parity (every slot can reach max_seq simultaneously).
        probe = model.create_paged_cache(1, 2, ps, 1)
        self.bytes_per_page = probe.bytes_per_page()
        #: The window layers' pool: what a page of it pins across those
        #: layers, and the most pages a slot ever holds there — the
        #: window's and the one its edge crosses.  Sized by slots: no
        #: allocation from it can fail.
        self.window_bytes_per_page = (probe.window_bytes_per_page()
                                      if self.window else 0)
        self.window_pages_per_slot = (
            min(pages_for(self.window, ps) + 1, t) if self.window else 0)
        self.window_usable_pages = (self.num_slots
                                    * self.window_pages_per_slot)
        window_pool = (self.window_usable_pages
                       * self.window_bytes_per_page)
        #: What a slot's recurrent layers hold whatever its length
        #: (0: the model has none).  That pool is sized by slots, not
        #: pages, and a byte budget pays for it first.
        self.state_bytes_per_slot = probe.state_bytes_per_slot()
        del probe
        state_pool = (self.num_slots * self.state_bytes_per_slot
                      + window_pool)
        if num_pages is None:
            if kv_budget_bytes:
                num_pages = int((kv_budget_bytes - state_pool)
                                // self.bytes_per_page)
            else:
                num_pages = self.num_slots * t
        self.usable_pages = int(num_pages)
        if self.usable_pages < 1:
            raise ValueError(
                f"kv budget holds {self.usable_pages} pages — nothing "
                f"is ever admittable")
        self.kv_budget_bytes = (self.usable_pages * self.bytes_per_page
                                + state_pool)
        self.cache: PagedKVCache = model.create_paged_cache(
            self.num_slots, 1 + self.usable_pages, ps, t,
            **(dict(window_pages=1 + self.window_usable_pages)
               if self.window else {}))
        self.keys = jnp.zeros((self.num_slots, 2), jnp.uint32)
        self.pool = PagePool(1 + self.usable_pages)
        self.radix = (RadixCache(self.pool, ps) if prefix_cache
                      else None)
        #: Host-memory spill (opt-in, ``spill_pages`` > 0): evicted
        #: refcount-0 prefix pages park their content here and
        #: restore bit-exactly on the next prefix hit.  With
        #: ``spill_disk_dir`` + ``spill_disk_pages`` also set, the
        #: host pool chains onto a CRC-verified `kvtier.DiskTier`:
        #: host overflow demotes the coldest parked page to a disk
        #: segment instead of dropping it, and a corrupt/lost segment
        #: degrades that chain to recompute at the match-time probe.
        self.spill: Optional[SpillPool] = None
        if spill_pages and self.radix is not None:
            self.spill = SpillPool(spill_pages)
            if spill_disk_dir and spill_disk_pages:
                from triton_distributed_tpu.serving.kvtier import (
                    DiskTier, KVTier)
                self.spill = KVTier(
                    self.spill, DiskTier(spill_disk_dir,
                                         spill_disk_pages))
            self.radix.spill = self.spill
            self.radix.read_page = self._read_page
        #: Per-tier admission accounting (pages resolved per tier /
        #: missed everywhere / tier reads degraded to recompute) —
        #: mirrored as ``serving_kvtier_*`` gauges onto heartbeats
        #: and as labeled ``serving_kvtier_{hit,miss}_total``
        #: counters (docs/serving.md "Cache hierarchy").
        self.tier_stats: Dict[str, int] = {
            "hit_device": 0, "hit_host": 0, "hit_peer": 0,
            "hit_disk": 0, "miss": 0, "fallbacks": 0}
        self._free: List[int] = list(range(self.num_slots))
        self._active = np.zeros(self.num_slots, bool)
        #: Host mirror of the device page table — single source of
        #: truth; `flush` re-ships it before a dispatch when dirty.
        self._table = np.zeros((self.num_slots, t), np.int32)
        self._dirty = True
        #: Per-slot private page ids (allocation order = logical
        #: order) and acquired radix path.
        self._slot_pages: List[List[int]] = [[] for _ in
                                             range(self.num_slots)]
        self._slot_path: List[List[_RadixNode]] = [[] for _ in
                                                   range(self.num_slots)]
        #: Logical pages currently mapped per slot.
        self._mapped = np.zeros(self.num_slots, np.int64)
        #: slot -> (page-table row to be, prompt length) of the
        #: prefills under way (`begin_prefill` .. `finish_prefill`).
        self._prefilling: Dict[int, tuple] = {}
        #: The window layers' side (``window`` > 0): their allocator;
        #: the host mirror of their page table; slot -> its row-to-be
        #: while its prefill is under way; and of each slot's logical
        #: pages the first still held and the first not mapped yet
        #: (everything between is mapped, everything below went back).
        self.wpool = (PagePool(1 + self.window_usable_pages)
                      if self.window else None)
        self._wtable = np.zeros((self.num_slots, t), np.int32)
        self._wprefilling: Dict[int, np.ndarray] = {}
        self._wfirst = np.zeros(self.num_slots, np.int64)
        self._wnext = np.zeros(self.num_slots, np.int64)
        #: Window pages given back since the start
        #: (``serving_window_pages_released_total``).
        self.window_released = 0
        #: Work at the KV boundary since the start: pages `ensure`
        #: mapped, page-table rows `flush` uploaded — mirrored as
        #: ``serving_kv_pages_mapped_total`` /
        #: ``serving_kv_table_rows_flushed_total``.
        self.mapped_pages = 0
        self.flushed_rows = 0
        # `insert_fn` is an injection seam for the serving-state model
        # checker / fuzz harness (`analysis.serving_model`): the real
        # host-side page accounting runs against a recording insert
        # and a stub cache, no jit, no device arrays.
        self._insert = insert_fn or make_paged_insert_fn()
        #: The scatter alone, for the chunks of a prefill under way
        #: (`insert_rows`).
        self._put_rows = make_paged_rows_fn()
        #: Slots whose state was zeroed since the start
        #: (``serving_state_resets_total``), and the program that does
        #: it (`_reset_state`), built at the first release.
        self.state_resets = 0
        self._reset = None

    # -- occupancy / accounting -----------------------------------------

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def active_slots(self) -> int:
        return self.num_slots - len(self._free)

    @property
    def occupancy(self) -> float:
        return self.active_slots / self.num_slots

    @property
    def free_pages(self) -> int:
        return self.pool.free_pages

    @property
    def used_pages(self) -> int:
        return self.pool.used_pages

    @property
    def live_pages(self) -> int:
        """Pages LIVE requests hold (private, or shared prefix pages
        some request maps) — `used_pages` less the prefix pages the
        radix cache merely retains, which the next allocation may
        evict: memory in use, against memory reserved."""
        return self.pool.used_pages - (
            self.radix.evictable_pages() if self.radix else 0)

    @property
    def page_occupancy(self) -> float:
        return self.used_pages / self.usable_pages

    @property
    def cached_prefix_pages(self) -> int:
        return self.radix.cached_pages if self.radix else 0

    @property
    def bytes_in_use(self) -> int:
        """TRUE bytes pinned (pages actually allocated, and the
        recurrent state of the slots in use) — not the max-context
        estimate `SlotKV` reports."""
        return (self.used_pages * self.bytes_per_page
                + self.active_slots * self.state_bytes_per_slot
                + self.window_pages_live * self.window_bytes_per_page)

    @property
    def window_pages_live(self) -> int:
        """Pages of the window layers' pool that rows hold."""
        return self.wpool.used_pages if self.wpool else 0

    # -- the window layers' pages (module docstring) ----------------------

    def _window_row(self, slot: int) -> np.ndarray:
        """``slot``'s row of the window table: the one to be, while its
        prefill is under way."""
        row = self._wprefilling.get(slot)
        return self._wtable[slot] if row is None else row

    def _window_advance(self, slot: int, first: int, end: int) -> None:
        """``slot`` holds window pages for exactly the logical pages
        ``[first, end)`` from here on: those below ``first`` go back to
        their pool (no query of the row sees a token of them again),
        those up to ``end`` are mapped (never below ``first``: rows
        nobody will read are not kept)."""
        row = self._window_row(slot)
        lo, nxt = int(self._wfirst[slot]), int(self._wnext[slot])
        if first <= lo and end <= nxt:
            return
        if first > lo:
            gone = [int(p) for p in row[lo:min(first, nxt)]]
            self.wpool.decref(gone)
            row[lo:min(first, nxt)] = NULL_PAGE
            self._wfirst[slot] = first
            self.window_released += len(gone)
            if gone:
                _count_metric("serving_window_pages_released_total",
                              len(gone))
        nxt = max(nxt, first)
        if end > nxt:
            ids = self.wpool.alloc(end - nxt)
            assert ids is not None, "the window pool is sized by slots"
            row[nxt:end] = ids
            nxt = end
        self._wnext[slot] = nxt
        if slot not in self._wprefilling:
            self._dirty = True

    def prefill_window_pages(self, slot: int) -> np.ndarray:
        """`prefill_pages` of the window layers' table: what a chunk's
        program reads ITS predecessors' window rows through."""
        return self._wprefilling[slot]

    def _reclaimable(self) -> int:
        return self.pool.free_pages + (
            self.radix.evictable_pages() if self.radix else 0)

    def feasible(self, prompt_len: int, max_new: int) -> bool:
        """Could this request EVER run alone on an empty pool?  The
        last generated token needs no KV write, so the horizon is
        ``prompt_len + max_new - 1`` positions — for a model that
        generates by blocks, the end of the block that holds the last
        token (the block runs whole)."""
        horizon = prompt_len + max_new - 1
        if self.block > 1:
            horizon = -(-(prompt_len + max_new) // self.block) * self.block
        return (horizon <= self.max_seq
                and pages_for(horizon, self.page_size)
                <= self.usable_pages)

    def can_admit(self, tokens: Optional[Sequence[int]] = None) -> bool:
        """A slot is free and the pool (after evicting unreferenced
        prefix pages) covers the request's PREFILL pages — growth is
        incremental (`ensure`), with preemption as the safety valve.

        Matched-chain pages at refcount 0 are NOT counted as
        evictable: `insert_prefill` acquires the chain before
        allocating, which pins exactly those pages — counting them
        both as "shared, not needed" and "evictable headroom" would
        admit a request the allocator then cannot serve.  Spilled
        chain nodes count as DEMAND, not supply: their restore
        allocates a fresh physical page each."""
        if not self._free:
            return False
        if tokens is None:
            return self._reclaimable() >= 1
        path = self.match_prefix(tokens)
        spilled = sum(1 for n in path if n.spilled)
        need = (pages_for(len(tokens), self.page_size) - len(path)
                + spilled)
        reclaim = self.pool.free_pages
        if self.radix is not None:
            on_path_idle = sum(1 for n in path
                               if n.refs == 0 and not n.spilled)
            reclaim += self.radix.evictable_pages() - on_path_idle
        return reclaim >= need

    # -- prefix cache ----------------------------------------------------

    def match_prefix(self, tokens: Sequence[int]) -> List[_RadixNode]:
        """Cached full pages prefixing ``tokens``, capped so every
        page containing positions >= len(tokens)-1 stays private
        (those get written: s-1 is recomputed at insert, generation
        writes from s on).

        Spilled chain nodes are integrity-probed HERE (a
        non-destructive CRC-verified `load`; host memory always
        passes, disk segments can be corrupt or lost): a node whose
        parked content cannot be read back is pruned and the chain
        truncates at it — admission then recomputes the tail instead
        of committing to a restore that would fail.  Never wrong
        bytes, at worst a re-prefill (`serving_kvtier_fallbacks_total`
        counts each degradation)."""
        if self.radix is None:
            return []
        path = self.radix.match(tokens)
        cap = (len(tokens) - 1) // self.page_size
        path = path[:cap]
        if self.spill is not None:
            for i, node in enumerate(path):
                if not node.spilled:
                    continue
                if self.spill.load(node.spill_key) is None:
                    # Count the degradation ONCE, when the node is
                    # actually dropped — the probe also runs from
                    # router scoring and peer extraction, and a
                    # counter incremented per probe would inflate
                    # "tier reads fell back to recompute" with
                    # re-observations of one lost page.  (Pruning
                    # itself is always correct on detection: the
                    # content is gone whoever asked.)
                    if node.refs == 0:
                        self.radix.drop_subtree(node)
                        self.tier_stats["fallbacks"] += 1
                        _count_metric("serving_kvtier_fallbacks_total")
                    return path[:i]
        return path

    def _tier_account(self, tier: Optional[str], n: int = 1) -> None:
        """Per-page hit/miss bookkeeping along the tier ladder: a
        page resolved at tier X is a hit there and a miss at every
        cheaper tier; a page resolved nowhere (fresh prefill) misses
        all four."""
        if n <= 0:
            return
        from triton_distributed_tpu.serving.kvtier import TIERS
        missed = TIERS if tier is None else TIERS[:TIERS.index(tier)]
        if tier is not None:
            self.tier_stats[f"hit_{tier}"] += n
            _count_metric("serving_kvtier_hit_total", n, tier=tier)
        else:
            self.tier_stats["miss"] += n
        for t in missed:
            _count_metric("serving_kvtier_miss_total", n, tier=t)

    # -- allocation ------------------------------------------------------

    def _alloc(self, n: int) -> Optional[List[int]]:
        if n == 0:
            return []
        ids = self.pool.alloc(n)
        if ids is None and self.radix is not None:
            # `evict` walks the whole tree of retained prefixes for its
            # LRU frontier, whatever it is asked for: ask for a 64th of
            # the pool at a time, so that a full pool pays one walk for
            # many allocations and not one for each (14 ms a decode
            # step at 10k pages — PERF.md, PR 28).  The order of
            # eviction is the same; small pools evict what they need.
            self.radix.evict(max(n - self.pool.free_pages,
                                 self.usable_pages // 64))
            ids = self.pool.alloc(n)
        return ids

    def ensure(self, slot: int, need_positions: int) -> bool:
        """Grow slot ``slot``'s mapping to cover KV positions
        ``[0, need_positions)`` — called before every dispatch so the
        decode write at ``offset`` always lands in a mapped private
        page.  False = pool dry even after eviction (caller preempts).
        """
        need = min(pages_for(need_positions, self.page_size),
                   self.pages_per_seq)
        mapped = 0
        while self._mapped[slot] < need:
            ids = self._alloc(1)
            if not ids:
                break
            j = int(self._mapped[slot])
            self._table[slot, j] = ids[0]
            self._slot_pages[slot].append(ids[0])
            self._mapped[slot] = j + 1
            self._dirty = True
            mapped += 1
        if mapped:
            self.mapped_pages += mapped
            _count_metric("serving_kv_pages_mapped_total", mapped)
        if self.window and self._mapped[slot] >= need:
            # the dispatch's query stands at ``need_positions - 1``
            self._window_advance(
                slot, max(need_positions - self.window, 0)
                // self.page_size, need)
        return bool(self._mapped[slot] >= need)

    def rollback(self, slot: int, keep_positions: int) -> None:
        """Shrink slot ``slot``'s mapping to cover exactly KV
        positions ``[0, keep_positions)`` — the speculative-rollback
        path: a verify dispatch mapped (and wrote) pages for K+1
        positions, but only the accepted prefix happened, so the
        pages the rejected tail reached must unmap and free.  After
        this, refcounts, the page table and the free list are exactly
        what a plain engine that decoded only the accepted prefix
        would hold (`analysis.serving_model` proves the invariant;
        `FindingKind.SPEC_ROLLBACK` is the violation).

        Only PRIVATE pages can ever be unmapped here: generation
        positions lie beyond the prompt, so ``keep_positions >=
        prompt_len`` keeps every shared/radix-registered page (and
        the whole prompt mapping) untouched.  The freed pages hold
        garbage KV from the rejected writes — never read: a future
        owner's attention masks ``>= offset`` and its own writes
        precede its reads, the same argument that makes `release`'s
        data-left-in-place free."""
        assert not self.window, "a rollback over pages given back"
        keep = pages_for(keep_positions, self.page_size)
        assert keep >= len(self._slot_path[slot]), (
            keep, len(self._slot_path[slot]))
        while self._mapped[slot] > keep:
            j = int(self._mapped[slot]) - 1
            p = int(self._table[slot, j])
            assert p != NULL_PAGE, (slot, j)
            assert (self._slot_pages[slot]
                    and self._slot_pages[slot][-1] == p), (
                "rollback reached a non-private page")
            self._slot_pages[slot].pop()
            self.pool.decref([p])
            self._table[slot, j] = NULL_PAGE
            self._mapped[slot] = j
            self._dirty = True

    def flush(self) -> None:
        """Re-ship the host page table to the device cache if any
        allocation/release changed it since the last dispatch."""
        if self._dirty:
            # a copy: the step that gets this table may still be
            # running when the host next edits its mirror
            self.cache = self.cache.with_page_table(
                self._table.copy(),
                *((self._wtable.copy(),) if self.window else ()))
            self._dirty = False
            self.flushed_rows += self.num_slots
            _count_metric("serving_kv_table_rows_flushed_total",
                          self.num_slots)

    # -- lifecycle -------------------------------------------------------

    def insert_prefill(self, row_cache, tokens: Sequence[int],
                       prompt_len: int, key,
                       shared_path: List[_RadixNode],
                       row_start: int = 0,
                       offset: Optional[int] = None) -> int:
        """Claim a slot, map shared prefix pages + freshly allocated
        private pages, scatter the prefilled row cache into the
        private pages, set offset to ``prompt_len - 1`` (``offset``
        where given: a block-generating model's cursor, the end of the
        prompt's whole blocks) and the slot PRNG key.  ``row_cache`` covers prompt positions
        ``[row_start, prompt_len)`` (``row_start = 0`` for a full
        prefill, or the page-aligned shared-prefix length for the
        suffix path).  Full prompt pages are registered into the
        radix cache so later arrivals share them.  Returns the slot.

        A prefill in ONE piece: `begin_prefill`, one `insert_rows`,
        `finish_prefill`.  One carried out in chunks calls the three
        itself, an insert a chunk."""
        assert row_start <= len(shared_path) * self.page_size
        slot = self.begin_prefill(prompt_len, shared_path)
        self.insert_rows(slot, row_cache, row_start, key, offset)
        self.finish_prefill(slot, tokens, offset)
        return slot

    def begin_prefill(self, prompt_len: int,
                      shared_path: List[_RadixNode]) -> int:
        """Claim a slot and every page of a prompt of ``prompt_len``
        tokens: the shared chain acquired (spilled nodes restored),
        the rest allocated.  The slot's row of the page TABLE stays
        NULL until `finish_prefill` — a decode step that runs while
        the prompt is still being prefilled masks the slot, and a
        masked row's write must land in the trash page — so the pages
        are kept beside it (`prefill_pages`).  Returns the slot."""
        s = int(prompt_len)
        ps = self.page_size
        assert self._free, "insert_prefill without can_admit()"
        c_pages = len(shared_path)
        total_pages = pages_for(s, ps)
        # Acquire the shared chain BEFORE allocating: _alloc may evict
        # refcount-0 radix pages, and the matched chain must not be
        # among them.
        if shared_path and self.radix is not None:
            self.radix.acquire(shared_path)
            # Restore any spilled chain node: a fresh physical page
            # (the allocation ref becomes the tree's retention ref),
            # the parked content written back bit-exactly, plus this
            # request's own pool ref (acquire skipped it while the
            # node was spilled).  can_admit budgeted these pages, and
            # the match-time probe verified each parked payload
            # reads back intact.
            for node in shared_path:
                if not node.spilled:
                    # Device-resident page; a peer-adopted chain's
                    # first local consumption counts as a peer-tier
                    # hit (it was shipped, not prefilled here).
                    self._tier_account(node.origin or "device")
                    node.origin = None
                    continue
                tier = (self.spill.tier_of(node.spill_key)
                        if hasattr(self.spill, "tier_of") else "host")
                ids = self._alloc(1)
                assert ids is not None, \
                    "insert_prefill without can_admit()"
                payload = self.spill.take(node.spill_key)
                assert payload is not None, node.spill_key
                self._write_page(ids[0], payload)
                self.radix.restore(node, ids[0])
                self.pool.incref([ids[0]])
                self._tier_account(tier or "host")
        priv = self._alloc(total_pages - c_pages)
        assert priv is not None, "insert_prefill without can_admit()"
        slot = self._free.pop(0)
        # the slot's row-to-be: shared chain, then private pages, then
        # NULL
        row = np.full(self.pages_per_seq, NULL_PAGE, np.int32)
        for j, node in enumerate(shared_path):
            row[j] = node.page
        for i, p in enumerate(priv):
            row[c_pages + i] = p
        self._prefilling[slot] = (row, s)
        if self.window:
            # mapped piece by piece, as the rows go in (`insert_rows`)
            self._wprefilling[slot] = np.full(self.pages_per_seq,
                                              NULL_PAGE, np.int32)
        self._slot_pages[slot] = list(priv)
        self._slot_path[slot] = list(shared_path)
        return slot

    def prefill_pages(self, slot: int) -> np.ndarray:
        """The pages of a slot whose prefill is under way, in logical
        order (NULL past the prompt): what a chunk's program reads the
        rows of its predecessors through."""
        return self._prefilling[slot][0]

    def insert_rows(self, slot: int, row_cache, row_start: int,
                    key=None, offset: Optional[int] = None) -> None:
        """Scatter ``row_cache`` — positions ``[row_start, row_start +
        its length)`` of the prompt begun in ``slot`` — into the
        slot's private pages.  With ``key`` (the prompt's LAST rows)
        the same dispatch sets the slot's PRNG key and its offset —
        ``offset``, or the prompt's last position: the insert proper.
        Without it the rows are written and nothing else — the slot of
        a prefill under way stays as its release left it, offset 0."""
        row, s = self._prefilling[slot]
        ps = self.page_size
        assert row_start % ps == 0, row_start
        c_pages = len(self._slot_path[slot])
        total_pages = pages_for(s, ps)
        # physical destination of each LOCAL row page (NULL = discard:
        # shared pages the row may not overwrite, pad-tail overflow)
        bucket = int(row_cache.ks[0].shape[2])
        n_row_pages = pages_for(bucket, ps)
        page_ids = np.full(n_row_pages, NULL_PAGE, np.int32)
        for j in range(n_row_pages):
            g = row_start // ps + j
            if c_pages <= g < total_pages:
                page_ids[j] = row[g]
        window = ()
        if self.window:
            # The window layers keep of these rows what the row's NEXT
            # query still sees — the next piece's first, or the first
            # decode step's at ``s - 1`` — and give back what lies
            # behind that.  (The piece that read those pages is
            # enqueued already; their next owner's rows come later.)
            end = min(row_start + bucket, s)
            nxt = end if key is None else s - 1
            first = max(nxt - self.window + 1, 0) // ps
            self._window_advance(slot, first, pages_for(end, ps))
            wrow = self._wprefilling[slot]
            window_ids = np.full(n_row_pages, NULL_PAGE, np.int32)
            g0 = row_start // ps
            hi = min(n_row_pages, pages_for(end, ps) - g0)
            window_ids[:hi] = wrow[g0:g0 + hi]
            window = (jnp.asarray(window_ids),)
        if key is None:
            c = self.cache
            (ks, vs, kss, vss), offset, *wpools = self._put_rows(
                (c.ks, c.vs, c.kss, c.vss), c.offset, row_cache,
                jnp.asarray(page_ids),
                *(((c.wks, c.wvs),) + window if window else ()))
            rep = dict(ks=ks, vs=vs, kss=kss, vss=vss, offset=offset)
            if wpools:
                rep["wks"], rep["wvs"] = wpools[0]
            self.cache = dataclasses.replace(c, **rep)
            return
        self.cache, self.keys = self._insert(
            self.cache, self.keys, row_cache, key,
            jnp.int32(slot), jnp.asarray(page_ids),
            jnp.int32(s - 1 if offset is None else offset), *window)

    def finish_prefill(self, slot: int, tokens: Sequence[int],
                       offset: Optional[int] = None) -> None:
        """The last rows of the prompt begun in ``slot`` are in: map
        its pages, and register its full pages into the radix cache so
        that later arrivals share them (``offset``: a block-generating
        model's cursor, below which alone pages are shared)."""
        row, s = self._prefilling.pop(slot)
        ps = self.page_size
        shared_path = self._slot_path[slot]
        priv = self._slot_pages[slot]
        c_pages = len(shared_path)
        self._table[slot] = row
        if self.window:
            self._wtable[slot] = self._wprefilling.pop(slot)
        self._mapped[slot] = pages_for(s, ps)
        self._dirty = True
        self._active[slot] = True
        # Register newly written FULL prompt pages (strictly below
        # position s-1) so the next same-prefix arrival shares them.
        if self.radix is not None:
            sharable = (s - 1) // ps          # pages 0..sharable-1
            # no shared page holds a position at or past the cursor
            assert offset is None or sharable * ps <= offset, (s, offset)
            n_new = sharable - c_pages
            if n_new > 0:
                new_pages = [row[c_pages + i] for i in range(n_new)]
                nodes = self.radix.extend(shared_path, tokens, c_pages,
                                          new_pages)
                # ownership moved: the request now holds these via its
                # radix path, not as private pages
                self._slot_pages[slot] = list(priv[n_new:])
                self._slot_path[slot] = list(shared_path) + nodes
            self.radix.hit_tokens += c_pages * ps
            self.radix.miss_tokens += s - c_pages * ps
            # Sharable pages the hierarchy did NOT hold anywhere
            # (freshly prefilled; the never-sharable tail page is
            # not a cache miss).
            self._tier_account(None, max(sharable - c_pages, 0))

    def adopt_prefix(self, tokens: Sequence[int],
                     payloads: Sequence[dict]) -> int:
        """Install a PEER-SHIPPED prefix chain into this pool's radix
        cache: page ``j`` of ``tokens`` gets ``payloads[j]`` (the
        per-layer content `_read_page` produced on the home replica —
        numpy round-trip is exact, and replicas share params, so the
        bytes are identical to a local prefill's).

        Pages this cache already holds are skipped; adoption stops at
        the first locally-SPILLED chain node (restoring it locally is
        the cheaper path, and extending physical pages under a
        spilled parent would break the all-spilled-subtree pruning
        invariant).  New pages allocate from the pool (evicting idle
        prefix pages if needed — an adopted hot prefix is worth a
        cold one) and register refs-0 / tree-retained, tagged
        ``origin="peer"``, so the NEXT admission's `match_prefix`
        consumes them like any cached prefix: suffix-only prefill,
        zero prompt FLOPs for the shipped pages.  Returns the number
        of pages adopted (0 = nothing fit / radix off) — a partial
        or failed adoption is never an error, merely less reuse."""
        if self.radix is None:
            return 0
        ps = self.page_size
        n_pages = min(len(payloads), len(tokens) // ps)
        path = self.radix.match(tokens)[:n_pages]
        adopted = 0
        # Pin the chain against the eviction _alloc may trigger: a
        # freshly adopted node is an LRU-frontier LEAF, and demoting
        # it mid-adoption would hang the next page under a spilled
        # parent (breaking the all-spilled-subtree prune invariant).
        # Same move insert_prefill makes before ITS allocations.
        pinned = [n for n in path if not n.spilled]
        if pinned:
            self.radix.acquire(pinned)
        try:
            for j in range(len(path), n_pages):
                if path and path[-1].spilled:
                    break
                chunk = tuple(tokens[j * ps:(j + 1) * ps])
                ids = self._alloc(1)
                if ids is None:
                    break          # pool dry even after eviction
                self._write_page(ids[0], payloads[j])
                node = self.radix.adopt(path, chunk, ids[0])
                self.radix.acquire([node])
                pinned.append(node)
                path.append(node)
                adopted += 1
        finally:
            if pinned:
                self.radix.release(pinned)
        if adopted:
            _count_metric("serving_kvtier_adopted_pages_total",
                          adopted)
        return adopted

    def release(self, slot: int) -> None:
        """Retire a slot: drop its radix references (pages stay cached
        for future prefix hits), free its private pages, reset its
        offset AND its page-table row to NULL — a masked row keeps
        issuing (frozen-offset) writes, which must land in the trash
        page, never in a page someone else may get."""
        assert 0 <= slot < self.num_slots and slot not in self._free
        # (a prefill under way is given up with its slot: the pages it
        # holds are the slot's, mapped or not)
        self._prefilling.pop(slot, None)
        if self.window:
            # everything the slot still holds of the window pool
            self.wpool.decref([int(p) for p in self._window_row(slot)[
                self._wfirst[slot]:self._wnext[slot]]])
            self._wprefilling.pop(slot, None)
            self._wtable[slot] = NULL_PAGE
            self._wfirst[slot] = self._wnext[slot] = 0
        if self._slot_path[slot] and self.radix is not None:
            self.radix.release(self._slot_path[slot])
        self.pool.decref(self._slot_pages[slot])
        self._slot_pages[slot] = []
        self._slot_path[slot] = []
        self._table[slot] = NULL_PAGE
        self._mapped[slot] = 0
        self._dirty = True
        self.cache = self.cache.reset_slot(slot)
        if self.state_bytes_per_slot:
            self._reset_state(slot)
        self._active[slot] = False
        self._free.append(slot)

    def _reset_state(self, slot: int) -> None:
        """Zero slot ``slot``'s recurrent state where it lies.  The
        pools are donated (an eager `.at[].set` would copy them whole)
        and they alone go through the program: the page table, the
        pages and the counters stay the arrays they were, as for any
        other model.  The program hands back arrays PLACED AS IT GOT
        THEM (a plain `jit` would spell a replicated result its own
        way, and the insert and the step after a release would meet
        their argument as a new kind: a compilation in the window)."""
        c = self.cache
        if self._reset is None:
            placed = lambda pools: [x.sharding for x in pools]  # noqa: E731
            self._reset = jax.jit(
                zero_state_rows, donate_argnums=(0, 1),
                out_shardings=(placed(c.states), placed(c.convs)))
        states, convs = self._reset(c.states, c.convs, np.int32(slot))
        self.cache = dataclasses.replace(c, states=states, convs=convs)
        self.state_resets += 1
        _count_metric("serving_state_resets_total")

    # -- spill content I/O (admission path, not the decode hot path) ----

    def _read_page(self, page: int) -> dict:
        """One physical page's content across all layers, as host
        numpy (the SpillPool payload).  Numpy round-trip of the
        stored dtypes (float32 / int8 + float32 scales) is exact, so
        restore-on-hit is bit-exact."""
        c = self.cache
        out: Dict[str, np.ndarray] = {}
        for layer in range(len(c.ks)):
            out[f"k{layer}"] = np.asarray(c.ks[layer][page])
            if c.vs is not None:        # None: latent rows (K is V)
                out[f"v{layer}"] = np.asarray(c.vs[layer][page])
            if c.quantized:
                out[f"ks{layer}"] = np.asarray(c.kss[layer][page])
                out[f"vs{layer}"] = np.asarray(c.vss[layer][page])
        return out

    def _write_page(self, page: int, payload: dict) -> None:
        """Write parked content back into physical ``page`` (restore;
        functional `.at[].set` updates, rebound like the insert)."""
        c = self.cache
        ks = [k.at[page].set(jnp.asarray(payload[f"k{i}"]))
              for i, k in enumerate(c.ks)]
        rep = dict(ks=ks)
        if c.vs is not None:
            rep["vs"] = [v.at[page].set(jnp.asarray(payload[f"v{i}"]))
                         for i, v in enumerate(c.vs)]
        if c.quantized:
            rep["kss"] = [x.at[page].set(
                jnp.asarray(payload[f"ks{i}"]))
                for i, x in enumerate(c.kss)]
            rep["vss"] = [x.at[page].set(
                jnp.asarray(payload[f"vs{i}"]))
                for i, x in enumerate(c.vss)]
        self.cache = dataclasses.replace(c, **rep)

    def active_mask(self) -> jnp.ndarray:
        return jnp.asarray(self._active)

    def snapshot_key(self, slot: int) -> np.ndarray:
        """Device fetch of a slot's current PRNG key (preemption path
        — the resumed request must continue its exact key chain)."""
        return np.asarray(self.keys[slot]).copy()
