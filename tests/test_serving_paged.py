"""Paged KV cache + radix prefix reuse tests — CPU-only,
deterministic.  The toy model implements the paged engine contract
with the same page-table addressing `flash_decode_paged` uses on TPU,
so the allocator, radix cache, preemption and the scheduler's paged
admission are exercised token-for-token against the slot engine here;
the Pallas kernel itself is covered in test_flash_decode.py.
All tier-1 (`not slow`)."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.models.kv_cache import (
    NULL_PAGE,
    PagedKVCache,
    pages_for,
)
from triton_distributed_tpu.serving import (
    ContinuousBatchingScheduler,
    FinishReason,
    PagedKV,
    PagePool,
    RadixCache,
    RejectReason,
    Request,
    SchedulerConfig,
    ToyConfig,
    ToyModel,
    pad_prompt,
    request_key,
)


class Clock:
    def __init__(self):
        self.t = 0.0

    def now(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture(scope="module")
def toy():
    model = ToyModel(ToyConfig(vocab_size=61, hidden=16,
                               max_seq_len=64))
    params = model.init_params(jax.random.key(0))
    return model, params


@pytest.fixture(scope="module")
def toy_int8():
    model = ToyModel(ToyConfig(vocab_size=61, hidden=16,
                               max_seq_len=64, quantize_kv_cache=True))
    params = model.init_params(jax.random.key(0))
    return model, params


def make_sched(model, params, layout, clock=None, **cfg_kw):
    cfg_kw.setdefault("num_slots", 3)
    cfg_kw.setdefault("prefill_buckets", (8, 16, 32, 64))
    cfg_kw.setdefault("page_size", 16)
    ck = clock or Clock()
    return ContinuousBatchingScheduler(
        model, params, SchedulerConfig(kv_layout=layout, **cfg_kw),
        clock=ck.now, clock_advance=ck.advance), ck


def rand_prompts(n, vocab=61, seed=0, lo=3, hi=20):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, vocab, rng.integers(lo, hi)))
            for _ in range(n)]


def run_layout(model, params, layout, reqs_factory, **cfg_kw):
    sched, _ = make_sched(model, params, layout, **cfg_kw)
    done = sched.run(reqs_factory())
    return (sched, [r.generated for r in
                    sorted(done, key=lambda r: r.request_id)])


# ---------------------------------------------------------------------------
# unit: PagedKVCache, PagePool, RadixCache
# ---------------------------------------------------------------------------


def test_pages_for():
    assert pages_for(0, 16) == 0
    assert pages_for(1, 16) == 1
    assert pages_for(16, 16) == 1
    assert pages_for(17, 16) == 2


def test_paged_cache_create_and_bytes():
    c = PagedKVCache.create(num_layers=3, num_pages=9, batch=4,
                            num_kv_heads=2, page_size=8, head_dim=16,
                            max_pages_per_seq=4, dtype=jnp.bfloat16)
    assert c.num_pages == 9 and c.pages_per_seq == 4
    assert c.page_size == 8 and c.max_seq == 32
    assert c.page_table.shape == (4, 4)
    assert (np.asarray(c.page_table) == NULL_PAGE).all()
    # 3 layers x (K+V) x 2 heads x 8 rows x 16 dim x 2 bytes
    assert c.bytes_per_page() == 3 * 2 * 2 * 8 * 16 * 2
    q = PagedKVCache.create(num_layers=3, num_pages=9, batch=4,
                            num_kv_heads=2, page_size=8, head_dim=16,
                            max_pages_per_seq=4, quantized=True)
    assert q.quantized
    assert q.bytes_per_page() == (3 * 2 * 2 * 8 * 16 * 1
                                  + 3 * 2 * 2 * 8 * 4)


def test_paged_cache_page_cheaper_than_slot(toy):
    """The budget-arithmetic fix: a short request's true page cost is
    far below the max-context bytes `KVCache.bytes_per_slot` charges."""
    model, _ = toy
    dense = model.create_cache(1, max_seq=64).bytes_per_slot()
    paged = model.create_paged_cache(1, 2, 16, 4).bytes_per_page()
    # an 8-token prompt pins ONE page, not 64 rows
    assert pages_for(8, 16) * paged * 4 == dense
    assert pages_for(8, 16) * paged < dense


def test_page_pool_alloc_free_refcount():
    pool = PagePool(6)                 # pages 1..5 usable
    assert pool.usable_pages == 5 and pool.free_pages == 5
    ids = pool.alloc(3)
    assert len(ids) == 3 and NULL_PAGE not in ids
    assert pool.free_pages == 2 and pool.used_pages == 3
    assert pool.alloc(3) is None       # only 2 left
    pool.incref([ids[0]])
    pool.decref([ids[0]])              # still held once
    assert pool.free_pages == 2
    pool.decref(ids)
    assert pool.free_pages == 5


def test_radix_match_insert_evict_lru():
    pool = PagePool(10)
    radix = RadixCache(pool, page_size=4)
    toks_a = list(range(1, 13))        # 3 full pages
    pages = pool.alloc(3)
    nodes = radix.extend([], toks_a, 0, pages)
    assert len(nodes) == 3 and radix.cached_pages == 3
    # chain is matched page-granularly; divergent tail isn't
    assert len(radix.match(toks_a)) == 3
    assert len(radix.match(toks_a[:8] + [99, 99, 99, 99])) == 2
    assert len(radix.match([99] + toks_a[1:])) == 0
    # Release the inserting request (extend transferred its alloc ref
    # into the chain — `release` is the only decref the caller owes):
    # nodes stay cached at refs 0.
    radix.release(nodes)
    assert radix.evictable_pages() == 3
    assert pool.free_pages == 10 - 1 - 3   # tree still retains them
    # LRU eviction frees leaves first (deepest page evicted first)
    freed = radix.evict(1)
    assert freed == 1 and radix.cached_pages == 2
    assert len(radix.match(toks_a)) == 2
    radix.evict(10)
    assert radix.cached_pages == 0 and pool.free_pages == 9


def test_radix_refs_block_eviction():
    pool = PagePool(4)
    radix = RadixCache(pool, page_size=2)
    pages = pool.alloc(2)
    nodes = radix.extend([], [1, 2, 3, 4], 0, pages)
    # the inserting request still holds the chain: nothing evictable
    assert radix.evictable_pages() == 0
    assert radix.evict(2) == 0
    radix.release(nodes)
    assert radix.evict(2) == 2


def test_pagedkv_insert_release_and_table(toy):
    model, params = toy
    kv = PagedKV(model, 2, max_seq=64, page_size=16)
    assert kv.usable_pages == 2 * 4
    prefill = jax.jit(model.make_prefill_fn())
    prompt = list(range(1, 21))        # 20 tokens -> 2 pages
    ids, s = pad_prompt(prompt, 32)
    row = model.create_cache(1, max_seq=32)
    _, row = prefill(params, ids, row)
    shared = kv.match_prefix(prompt)
    assert shared == []
    slot = kv.insert_prefill(row, prompt, s, request_key(3), shared)
    assert kv.used_pages == 2 and kv.free_pages == 6
    assert int(kv.cache.offset[slot]) == s - 1
    # table row maps 2 real pages then NULL
    trow = kv._table[slot]
    assert (trow[:2] != NULL_PAGE).all() and (trow[2:] == NULL_PAGE).all()
    # the prefilled KV is readable back through the table
    kv.flush()
    k_log, _ = kv.cache.gather_logical(0)
    np.testing.assert_allclose(np.asarray(k_log[slot, :, :s]),
                               np.asarray(row.ks[0][0, :, :s]))
    # full prompt page below s-1 was donated to the radix cache
    assert kv.cached_prefix_pages == 1
    kv.release(slot)
    # private pages freed, radix page retained (refs 0, evictable)
    assert kv.free_pages == 7 and kv.cached_prefix_pages == 1
    assert (kv._table[slot] == NULL_PAGE).all()


def test_can_admit_does_not_double_count_matched_chain(toy):
    """Regression: matched-chain pages at refcount 0 are BOTH the
    shared pages the request won't allocate AND (naively) evictable
    headroom — counting them twice admitted requests the allocator
    could not serve (insert acquires the chain first, pinning them).
    Pool of 6: A caches a 1-page chain and retires; B pins 3 pages;
    C needs 3 fresh pages beyond its 1-page hit but only 2 are free
    and the single "evictable" page IS the matched chain."""
    model, params = toy
    kv = PagedKV(model, 3, max_seq=64, page_size=16, num_pages=6)
    prefill = jax.jit(model.make_prefill_fn())

    def admit(tokens, bucket):
        ids, s = pad_prompt(tokens, bucket)
        row = model.create_cache(1, max_seq=bucket)
        _, row = prefill(params, ids, row)
        shared = kv.match_prefix(tokens)
        return kv.insert_prefill(row, tokens, s, request_key(0),
                                 shared)

    chain = list(range(1, 18))             # 17 tokens: 1 full page
    slot_a = admit(chain, 32)
    kv.release(slot_a)                     # chain cached, refs 0
    assert kv.cached_prefix_pages == 1
    slot_b = admit([40 + i % 20 for i in range(33)], 64)  # 3 pages
    assert kv.free_pages == 2
    big = chain[:16] + [50 + i % 10 for i in range(44)]   # 60 tokens
    # need 4 total - 1 matched = 3 fresh; only 2 free and the one
    # "evictable" page IS the matched chain
    assert not kv.can_admit(big)
    kv.release(slot_b)                     # now 5 free: admissible
    assert kv.can_admit(big)
    slot_c = admit(big, 64)
    assert slot_c is not None


def test_pagedkv_feasible_truthful_pages(toy):
    """Satellite fix: admission arithmetic counts PAGES, so the
    rejection boundary is the allocator's true capacity."""
    model, _ = toy
    kv = PagedKV(model, 2, max_seq=64, page_size=16, num_pages=3)
    assert kv.feasible(8, 41)          # horizon 48 = 3 pages
    assert not kv.feasible(8, 42)      # horizon 49 = 4 pages > 3
    assert not kv.feasible(60, 10)     # horizon 69 > max_seq


def test_pagedkv_budget_bytes_sizes_pool(toy):
    model, _ = toy
    bpp = model.create_paged_cache(1, 2, 16, 4).bytes_per_page()
    kv = PagedKV(model, 4, max_seq=64, page_size=16,
                 kv_budget_bytes=5 * bpp + bpp // 2)
    assert kv.usable_pages == 5
    assert kv.kv_budget_bytes == 5 * bpp
    with pytest.raises(ValueError):
        PagedKV(model, 4, max_seq=64, page_size=16,
                kv_budget_bytes=bpp // 2)


# ---------------------------------------------------------------------------
# end-to-end: paged engine token-for-token vs the slot engine
# ---------------------------------------------------------------------------


def test_paged_matches_slots_greedy(toy):
    """The equivalence satellite: same requests, same tokens, whatever
    the KV layout — with mid-decode joins forcing real insertion into
    a running paged batch."""
    model, params = toy
    prompts = rand_prompts(7, seed=1)
    gens = [3, 7, 4, 6, 2, 5, 8]

    def reqs():
        return [Request(prompt=p, max_new_tokens=g)
                for p, g in zip(prompts, gens)]

    _, a = run_layout(model, params, "slots", reqs)
    _, b = run_layout(model, params, "paged", reqs)
    assert a == b


def test_paged_matches_slots_sampled(toy):
    model, params = toy
    prompts = rand_prompts(6, seed=5)

    def reqs():
        return [Request(prompt=p, max_new_tokens=5, seed=100 + i)
                for i, p in enumerate(prompts)]

    _, a = run_layout(model, params, "slots", reqs, temperature=1.0)
    _, b = run_layout(model, params, "paged", reqs, temperature=1.0)
    assert a == b


def test_paged_matches_slots_int8(toy_int8):
    model, params = toy_int8
    prompts = rand_prompts(5, seed=9)

    def reqs():
        return [Request(prompt=p, max_new_tokens=6, seed=7 + i)
                for i, p in enumerate(prompts)]

    for temp in (0.0, 1.0):
        _, a = run_layout(model, params, "slots", reqs,
                          temperature=temp)
        _, b = run_layout(model, params, "paged", reqs,
                          temperature=temp)
        assert a == b, temp


def test_mid_stream_page_allocation_boundary(toy):
    """A generation crossing a page boundary mid-stream allocates a
    fresh page incrementally and stays token-exact: prompt 14 + 10
    new tokens crosses 16 with page_size 16 (and crosses twice with
    page_size 8)."""
    model, params = toy
    prompt = rand_prompts(1, seed=11, lo=14, hi=15)[0]

    def reqs():
        return [Request(prompt=prompt, max_new_tokens=10)]

    _, want = run_layout(model, params, "slots", reqs)
    for ps in (8, 16):
        sched, got = run_layout(model, params, "paged", reqs,
                                page_size=ps)
        assert got == want, ps
        # pages grew past the prefill allocation: 14+10-1 positions
        assert sched.slots.pool.refs.sum() >= 0  # bookkeeping intact
    # the smallest page: a boundary every other dispatch, each mapped
    # while the step before it is still unread
    _, got = run_layout(model, params, "paged", reqs, page_size=2)
    assert got == want


# ---------------------------------------------------------------------------
# prefix sharing
# ---------------------------------------------------------------------------


def shared_prefix_reqs(vocab=61, n=4, sys_len=24, max_new=3, seed=21):
    rng = np.random.default_rng(seed)
    sysp = list(rng.integers(1, vocab, sys_len))
    return lambda: [Request(prompt=sysp + [1 + i, 2 + i],
                            max_new_tokens=max_new)
                    for i in range(n)]


def test_prefix_sharing_exact_and_counted(toy):
    from triton_distributed_tpu.observability import get_registry
    model, params = toy
    reqs = shared_prefix_reqs()
    get_registry().clear()
    sched, shared_out = run_layout(model, params, "paged", reqs)
    _, slot_out = run_layout(model, params, "slots", reqs)
    _, unshared_out = run_layout(model, params, "paged", reqs,
                                 prefix_cache=False)
    assert shared_out == slot_out == unshared_out
    # the first request misses; the other three each hit one full page
    assert sched.slots.radix.hit_tokens == 3 * 16
    snap = get_registry().snapshot()
    assert snap["counters"][
        "serving_prefix_cache_hit_tokens_total"] == 3 * 16
    assert snap["counters"][
        "serving_prefix_cache_miss_tokens_total"] > 0
    for g in ("serving_kv_pages_free", "serving_kv_pages_used",
              "serving_kv_page_occupancy", "serving_prefix_cache_pages"):
        assert g in snap["gauges"], g


def test_prefix_sharing_shares_pages_not_copies(toy):
    """Concurrent same-prefix requests map the SAME physical page."""
    model, params = toy
    sched, _ = make_sched(model, params, "paged", num_slots=4)
    rng = np.random.default_rng(3)
    sysp = list(rng.integers(1, 61, 16))      # exactly one full page
    reqs = [Request(prompt=sysp + [10 + i, 20 + i], max_new_tokens=8,
                    arrival_time=0.0)
            for i in range(4)]
    for r in reqs:
        assert sched.submit(r)
    sched.step()                                # admit all four
    table = sched.slots._table
    live = [r.slot for r in reqs if r.slot is not None]
    assert len(live) == 4
    first_pages = {table[s, 0] for s in live}
    assert len(first_pages) == 1                # one shared page
    page = first_pages.pop()
    assert sched.slots.pool.refs[page] >= 4     # 4 requests + cache
    sched.drain()
    # retired: requests' refs dropped, the cache still retains it
    assert sched.slots.pool.refs[page] == 1
    assert sched.slots.cached_prefix_pages >= 1


def test_prefix_cache_survives_retirement_and_lru_evicts(toy):
    """A later arrival hits pages cached by an already-finished
    request; pool pressure evicts the least recently used chain."""
    model, params = toy
    sched, _ = make_sched(model, params, "paged", num_slots=2,
                          num_pages=8)
    rng = np.random.default_rng(5)
    a = list(rng.integers(1, 61, 16))
    b = list(rng.integers(1, 61, 16))
    done = sched.run([Request(prompt=a + [1], max_new_tokens=2)])
    assert len(done) == 1
    assert sched.slots.cached_prefix_pages == 1
    # same prefix again: hit
    h0 = sched.slots.radix.hit_tokens
    sched.run([Request(prompt=a + [2], max_new_tokens=2)])
    assert sched.slots.radix.hit_tokens - h0 == 16
    # a different prefix caches a second chain
    sched.run([Request(prompt=b + [3], max_new_tokens=2)])
    assert sched.slots.cached_prefix_pages == 2
    # now exhaust the pool: big requests force LRU eviction
    evicted0 = sched.slots.radix.evicted_pages
    sched.run([Request(prompt=list(rng.integers(1, 61, 30)),
                       max_new_tokens=34) for _ in range(2)])
    assert sched.slots.radix.evicted_pages > evicted0


# ---------------------------------------------------------------------------
# preemption: pool pressure evicts newest, resumes exactly
# ---------------------------------------------------------------------------


def test_preemption_resumes_token_exact(toy):
    from triton_distributed_tpu.observability import get_registry
    model, params = toy

    def reqs():
        return [Request(prompt=[1 + i] * 10, max_new_tokens=30,
                        seed=i, eos_token_ids=())
                for i in range(3)]

    get_registry().clear()
    # 6 usable pages cannot hold 3 x 39-position horizons (3 pages
    # each): the newest gets preempted and resumed.
    sched, got = run_layout(model, params, "paged", reqs, num_pages=6,
                            temperature=1.0)
    _, want = run_layout(model, params, "slots", reqs, temperature=1.0)
    assert got == want
    preempted = [r for r in sched.finished if r.preemptions]
    assert preempted, "pool pressure should have preempted someone"
    snap = get_registry().snapshot()
    assert snap["counters"]["serving_preemptions_total"] >= 1


def test_paged_rejects_infeasible_request(toy):
    model, params = toy
    sched, _ = make_sched(model, params, "paged", num_pages=2)
    req = Request(prompt=[1] * 8, max_new_tokens=40)  # 3 pages > 2
    assert not sched.submit(req)
    assert req.reject_reason == RejectReason.EXCEEDS_KV_CAPACITY
    ok = Request(prompt=[1] * 8, max_new_tokens=24)   # 31 pos = 2 pages
    assert sched.submit(ok)
    sched.drain()
    assert ok.finish_reason == FinishReason.LENGTH
    assert len(ok.generated) == 24


def test_paged_capacity_boundary_full_length(toy):
    """Same boundary semantics as the slot engine: prompt + max_new ==
    max_seq + 1 delivers every token (the final token needs no KV
    write)."""
    model, params = toy
    for ps in (4, 16):
        sched, _ = make_sched(model, params, "paged", max_seq=16,
                              prefill_buckets=(8, 16), page_size=ps)
        req = Request(prompt=[1, 2, 3, 4], max_new_tokens=13)
        assert sched.submit(req), req.reject_reason
        sched.drain()
        assert req.finish_reason == FinishReason.LENGTH, (
            ps, req.finish_reason)
        assert len(req.generated) == 13
        over = Request(prompt=[1, 2, 3, 4], max_new_tokens=14)
        assert not sched.submit(over)
        assert over.reject_reason == RejectReason.EXCEEDS_KV_CAPACITY


def test_paged_admission_beats_slot_admission_same_budget(toy):
    """The tentpole claim, in miniature: on the SAME KV byte budget,
    page-based admission sustains >= 4x the slot engine's concurrency
    for short requests (slot admission prices every request at
    max-context)."""
    model, params = toy
    budget = 4 * model.create_cache(1, max_seq=64).bytes_per_slot()

    def reqs():
        return [Request(prompt=[1 + i, 2, 3], max_new_tokens=4,
                        arrival_time=0.0)
                for i in range(32)]

    peak = {}
    for layout in ("slots", "paged"):
        sched, _ = make_sched(model, params, layout, num_slots=32,
                              kv_budget_bytes=budget)
        for r in reqs():
            assert sched.submit(r), r.reject_reason
        m = 0
        while sched.has_work():
            sched.step()
            m = max(m, sched.slots.active_slots)
        assert len(sched.finished) == 32
        peak[layout] = m
    assert peak["slots"] == 4
    assert peak["paged"] >= 4 * peak["slots"]


def test_observability_disabled_paged_still_serves(toy, monkeypatch):
    monkeypatch.setenv("TDT_OBSERVABILITY", "0")
    model, params = toy
    sched, _ = make_sched(model, params, "paged")
    done = sched.run([Request(prompt=[1, 2, 3], max_new_tokens=2)])
    assert len(done) == 1 and len(done[0].generated) == 2


def test_paged_requires_contract():
    class NoPaged:
        class config:
            max_seq_len = 32

    with pytest.raises(ValueError, match="paged engine contract"):
        ContinuousBatchingScheduler(
            NoPaged(), {}, SchedulerConfig(kv_layout="paged"))


# ---------------------------------------------------------------------------
# step-phase spans and KV-boundary counters (ISSUE 24)
# ---------------------------------------------------------------------------

STEP_PHASES = ("serving.admit", "serving.pages", "serving.dispatch",
               "serving.sync", "serving.commit", "serving.gauges")


@pytest.fixture
def tracer():
    from triton_distributed_tpu.observability import get_tracer
    tr = get_tracer()
    tr.clear()
    yield tr
    tr.clear()


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


@pytest.mark.parametrize("layout", ["paged", "slots"])
def test_one_step_leaves_the_phase_spans(toy, tracer, layout):
    """The first iteration admits and dispatches; the second
    dispatches the next step and THEN reads and commits the first."""
    model, params = toy
    sched, _ = make_sched(model, params, layout)
    prompts = rand_prompts(2, seed=3)
    reqs = [Request(prompt=p, max_new_tokens=4) for p in prompts]
    for r in reqs:
        assert sched.submit(r)
    out = sched.step()
    assert out == {"admitted": 2, "active": 2, "retired": 0}
    spans = _by_name(tracer.finished())
    (step,) = spans["serving.step"]
    assert step.parent is None
    assert step.attrs == {"step": 1, "admitted": 2, "active": 2,
                          "retired": 0}
    # nothing was in flight: nothing to read or commit yet
    first = [p for p in STEP_PHASES
             if p not in ("serving.sync", "serving.commit")
             and (layout == "paged" or p != "serving.pages")]
    assert {s.name for s in tracer.finished()
            if s.parent == step.id} == set(first)
    for name in first:
        (sp,) = spans[name]
        assert sp.parent == step.id, name
    (admit,) = spans["serving.admit"]
    assert admit.attrs == {"queued": 2}
    # an admission's two halves — the prefill's enqueue, then the
    # insert's dispatch — side by side under the admission loop; the
    # host waits for neither prefill (no `serving.prefill.block`), and
    # with nothing in flight there was no step to read between them
    ones = spans["serving.admit.request"]
    fronts = spans["serving.admit.prefill"]
    assert "serving.prefill.block" not in spans
    assert [s.parent for s in ones] == [admit.id] * 2
    assert [s.parent for s in fronts] == [admit.id] * 2
    for one, front, req in zip(ones, fronts, reqs):
        assert one.attrs["request_id"] == req.request_id
        # the first enqueue of an idle server says so; `starved` is the
        # device's word (asked, not waited for)
        idle = {"idle": 1} if req is reqs[0] else {}
        assert front.attrs == dict(
            idle, request_id=req.request_id, flight="none",
            bucket=req.bucket, queue_wait_ms=0.0,
            # where the piece starts, and the prompt's tokens in it
            start=0, tokens=req.prompt_len,
            starved=front.attrs["starved"])
        assert front.attrs["starved"] in (0, 1) and (
            front.attrs["starved"] or not idle)
        assert front.t0 + front.dur <= one.t0
        assert one.attrs["read_flight"] == 0
        assert one.attrs["prompt_len"] == req.prompt_len
        assert one.attrs["bucket"] == req.bucket
        assert one.attrs["mode"] == "local"
        assert one.attrs["cached_tokens"] == 0
    # the request's lifetime: open, caused by its admission, sharing
    # its request_id
    lives = [s for s in tracer.open_spans()
             if s.name == "serving.request"]
    assert [s.parent for s in lives] == [s.id for s in ones]
    assert [s.attrs["request_id"] for s in lives] == [
        r.request_id for r in reqs]
    (dispatch,) = spans["serving.dispatch"]
    assert dispatch.attrs == {"k": 1, "spec": False, "inflight": 0,
                              "starved": dispatch.attrs["starved"]}
    if layout == "paged":
        (pages,) = spans["serving.pages"]
        assert pages.attrs["preempted"] == 0
        assert pages.attrs["flushed_rows"] == sched.config.num_slots
        assert pages.attrs["live_pages"] == sched.slots.live_pages > 0
    assert all(r.generated == [] for r in reqs) and sched.has_work()
    # the second iteration: no admit span; dispatch, then the read
    tracer.clear()
    out = sched.step()
    assert out == {"admitted": 0, "active": 2, "retired": 0}
    spans = _by_name(tracer.finished())
    (step,) = spans["serving.step"]
    assert "serving.admit" not in spans
    order = [s.name for s in sorted(tracer.finished(),
                                    key=lambda s: s.t0)
             if s.parent == step.id]
    assert order == [p for p in STEP_PHASES[1:-1]
                     if layout == "paged" or p != "serving.pages"]
    (dispatch,) = spans["serving.dispatch"]
    assert dispatch.attrs == {"k": 1, "spec": False, "inflight": 1,
                              "starved": dispatch.attrs["starved"]}
    # the read's record: a process's first read closes no interval,
    # stood behind both prefills and delivered two first tokens
    (sync,) = spans["serving.sync"]
    assert sync.attrs == {
        "rows": 2, "first_tokens": 2, "prefills": 2,
        "prefill_tokens": sum(r.bucket for r in reqs), "early": 0,
        "landed": sync.attrs["landed"],
        "prefill_request_ids": [r.request_id for r in reqs]}
    (commit,) = spans["serving.commit"]
    assert commit.attrs == {"tokens": 2, "retired": 0, "discarded": 0,
                            "deliver_ms": commit.attrs["deliver_ms"],
                            "retire_ms": 0.0}
    assert all(len(r.generated) == 1 for r in reqs)
    sched.drain()


@pytest.mark.parametrize("layout", ["paged", "slots"])
def test_the_commit_span_splits_into_delivery_and_retirement(
        toy, tracer, layout):
    """`deliver_ms` runs from the span's start until the last row of
    the read has its token, `retire_ms` is the rest: they add up to
    the span (less the attributes' own writing, on the span's clock),
    a commit that retires nothing is all delivery, and one that
    retires spends its `slots.release` calls in `retire_ms`."""
    model, params = toy
    sched, _ = make_sched(model, params, layout)
    release = sched.slots.release
    took = []

    def releasing(slot):
        t0 = time.perf_counter()
        release(slot)
        took.append((time.perf_counter() - t0) * 1e3)
    sched.slots.release = releasing
    sched.run([Request(prompt=p, max_new_tokens=n)
               for p, n in zip(rand_prompts(4, seed=6), (2, 5, 5, 3))])
    commits = [s for s in tracer.finished() if s.name == "serving.commit"]
    assert sum(c.attrs["retired"] for c in commits) == 4
    assert {c.attrs["retired"] for c in commits} >= {0, 1}
    for c in commits:
        deliver, retire = c.attrs["deliver_ms"], c.attrs["retire_ms"]
        assert deliver > 0.0 and retire >= 0.0
        assert (retire > 0.0) == (c.attrs["retired"] > 0)
        # the span closes a few microseconds after its attributes
        assert 0.0 <= c.dur * 1e3 - (deliver + retire) < 1.0
    assert sum(c.attrs["retire_ms"] for c in commits) >= sum(took) > 0.0


def test_step_self_time_is_duration_less_its_phases(toy, tracer):
    model, params = toy
    sched, _ = make_sched(model, params, "paged")
    sched.run([Request(prompt=p, max_new_tokens=5)
               for p in rand_prompts(4, seed=5)])
    spans = tracer.finished()
    steps = [s for s in spans if s.name == "serving.step"]
    assert len(steps) >= 5
    for step in steps:
        kids = [s for s in spans if s.parent == step.id
                and not s.detached]
        assert {k.name for k in kids} <= set(STEP_PHASES)
        inside = sum(k.dur for k in kids)
        assert 0.0 <= step.dur - inside <= step.dur
        # phases do not overlap: in order on the step's own clock
        kids.sort(key=lambda s: s.t0)
        for a, b in zip(kids, kids[1:]):
            assert a.t0 + a.dur <= b.t0
        assert step.t0 <= kids[0].t0
        assert kids[-1].t0 + kids[-1].dur <= step.t0 + step.dur


def test_request_span_survives_out_of_order_retirement(toy, tracer,
                                                       monkeypatch):
    """`serving.request` opens at admission and closes at retirement,
    whatever the order, and never reaches the profiler."""
    from triton_distributed_tpu.observability import tracing
    entered = []

    class Ann:
        def __init__(self, name):
            entered.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracing, "_TraceAnnotation", Ann)
    model, params = toy
    sched, _ = make_sched(model, params, "paged")
    prompts = rand_prompts(3, seed=11)
    # admitted together; the LAST admitted retires first
    reqs = [Request(prompt=p, max_new_tokens=n)
            for p, n in zip(prompts, (6, 4, 2))]
    for r in reqs:
        sched.submit(r)
    sched.step()
    open_ids = lambda: [s.attrs["request_id"]          # noqa: E731
                        for s in tracer.open_spans()
                        if s.name == "serving.request"]
    assert open_ids() == [r.request_id for r in reqs]
    sched.step()        # dispatches step 2, reads step 1
    assert open_ids() == [r.request_id for r in reqs]
    sched.step()        # reads step 2: the shortest has its two tokens
    assert open_ids() == [reqs[0].request_id, reqs[1].request_id]
    sched.drain()
    assert tracer.open_spans() == []
    done = [s for s in tracer.finished() if s.name == "serving.request"]
    assert [s.attrs["request_id"] for s in done] == [
        r.request_id for r in reversed(reqs)]
    assert "serving.request" not in entered
    assert "serving.step" in entered and "serving.sync" in entered


def test_dropped_scheduler_closes_its_request_spans(toy, tracer):
    import gc
    model, params = toy
    sched, _ = make_sched(model, params, "paged")
    sched.submit(Request(prompt=rand_prompts(1)[0], max_new_tokens=8))
    sched.step()
    assert [s.name for s in tracer.open_spans()] == ["serving.request"]
    del sched
    gc.collect()
    assert tracer.open_spans() == []
    assert [s.name for s in tracer.finished()
            if s.detached] == ["serving.request"]


def test_disabled_observability_allocates_no_span(toy, tracer,
                                                  monkeypatch):
    from triton_distributed_tpu.observability import tracing
    model, params = toy
    sched, _ = make_sched(model, params, "paged")
    monkeypatch.setenv("TDT_OBSERVABILITY", "0")

    def boom(*a, **kw):
        raise AssertionError("a span was allocated")

    monkeypatch.setattr(tracing.Span, "__init__", boom)
    done = sched.run([Request(prompt=p, max_new_tokens=3)
                      for p in rand_prompts(3, seed=2)])
    assert len(done) == 3
    assert len(tracer) == 0 and tracer.open_spans() == []


def test_kv_counters_count_an_eviction_and_a_flush(toy, tracer):
    """A pool too small to retain a finished prompt's pages: the next
    admission's allocation evicts them, and every changed table is
    re-shipped once."""
    from triton_distributed_tpu.observability import get_registry
    reg = get_registry()
    reg.clear()
    model, params = toy
    sched, _ = make_sched(model, params, "paged", num_slots=1,
                          page_size=8, num_pages=4)
    rng = np.random.default_rng(0)
    first = Request(prompt=list(rng.integers(1, 61, 20)),
                    max_new_tokens=6)      # grows into a fourth page
    sched.run([first])
    kv = sched.slots
    assert kv.mapped_pages == 1
    # two full prompt pages stay cached; nobody holds them
    assert kv.used_pages == 2 and kv.live_pages == 0
    flushed = kv.flushed_rows
    second = Request(prompt=list(rng.integers(1, 61, 26)),
                     max_new_tokens=2)
    tracer.clear()
    sched.run([second])
    snap = reg.snapshot()["counters"]
    assert kv.radix.evict_calls >= 1 and kv.radix.freed_pages == 2
    assert snap["serving_kv_evict_calls_total"] == kv.radix.evict_calls
    assert snap["serving_kv_evicted_pages_total"] == 2
    assert snap["serving_kv_pages_mapped_total"] == kv.mapped_pages
    assert snap["serving_kv_table_rows_flushed_total"] == kv.flushed_rows
    assert kv.flushed_rows > flushed
    # the spans saw the same work, step by step
    pages = [s for s in tracer.finished() if s.name == "serving.pages"]
    assert sum(s.attrs["mapped"] for s in pages) <= kv.mapped_pages
    assert max(s.attrs["live_pages"] for s in pages) == 4
    assert sum(s.attrs["flushed_rows"] for s in pages) > 0
    assert reg.snapshot()["gauges"]["serving_kv_pages_live"] == 0


# ---------------------------------------------------------------------------
# a long prompt is prefilled in chunks, at most one a decode step
# ---------------------------------------------------------------------------

CHUNK = 16


@pytest.fixture(scope="module")
def toys():
    """(chunking model, the same model never chunking, params)."""
    kw = dict(vocab_size=61, hidden=16, max_seq_len=128)
    model = ToyModel(ToyConfig(prefill_chunk=CHUNK, **kw))
    return (model, ToyModel(ToyConfig(**kw)),
            model.init_params(jax.random.key(0)))


def chunk_sched(model, params, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_buckets", (8, 16, 32, 64))
    return make_sched(model, params, "paged", **kw)[0]


def long_prompts(lengths, seed=0, vocab=61):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, vocab, n))) for n in lengths]


@pytest.fixture
def metrics():
    from triton_distributed_tpu.observability import get_registry
    reg = get_registry()
    reg.clear()
    yield reg
    reg.clear()


class Watch:
    """Every enqueue of the scheduler's programs, in order: `log` holds
    ("chunk", request's first token, start) for the chunk program,
    ("prefill",) for a whole prefill and ("step", rows) for a decode
    dispatch.  At every chunk the rows below its start are READ BACK
    through the page ids it was given and held against the toy's own
    K for those tokens: chunk k finds what its predecessors (and a
    prefix hit) left in the pool."""

    def __init__(self, sched, params):
        self.log, self.sched = [], sched
        suffix, prefill, step = (sched._prefill_suffix, sched._prefill,
                                 sched._step)
        wk, pe, embed = (np.asarray(params[k])
                         for k in ("wk", "pe", "embed"))

        def chunk(p, ids, start, row, pools, pages):
            adm = sched._underway or self.planned
            start = int(start)
            assert ids.shape == (1, adm.pieces[adm.done][1])
            if start:
                ps = sched.slots.page_size
                pool = np.asarray(pools[0][0])
                toks = np.asarray(adm.tokens[:start])
                want = (embed[toks] + pe[:start]) @ wk
                got = np.stack([pool[pages[i // ps], 0, i % ps]
                                for i in range(start)])
                np.testing.assert_allclose(got, want, rtol=1e-5,
                                           atol=1e-6)
            self.log.append(("chunk", adm.tokens[0], start))
            return suffix(p, ids, start, row, pools, pages)

        def whole(*a):
            self.log.append(("prefill",))
            return prefill(*a)

        def stepping(p, tokens, cache, keys, active):
            self.log.append(("step", int(np.sum(active))))
            return step(p, tokens, cache, keys, active)

        plan = sched._plan

        def planning(*a):
            self.planned = plan(*a)
            return self.planned
        sched._plan = planning
        sched._prefill_suffix, sched._prefill = chunk, whole
        sched._step = stepping

    def between_steps(self):
        """Prefill enqueues between consecutive decode dispatches that
        had a row running."""
        out, n = [], 0
        for ev in self.log:
            if ev[0] == "step":
                out.append(n)
                n = 0
            else:
                n += 1
        return out


def test_chunked_prefill_serves_the_unchunked_streams(toys):
    """2, 3 and 4 chunks (a last one padded), one exactly a chunk long
    (a whole prefill, as ever) and short ones, admitted beside one
    another: token for token what the never-chunking scheduler
    serves, and every chunk found its predecessors' rows."""
    model, plain, params = toys
    prompts = long_prompts([50, 40, 17, 16, 5, 64, 33], seed=1)
    mk = lambda: [Request(prompt=p, max_new_tokens=6 + i, seed=i)  # noqa: E731
                  for i, p in enumerate(prompts)]
    want = chunk_sched(plain, params).run(mk())
    sched = chunk_sched(model, params)
    watch = Watch(sched, params)
    got = sched.run(mk())
    by_id = lambda rs: [r.generated for r in  # noqa: E731
                        sorted(rs, key=lambda r: r.prompt)]
    assert by_id(got) == by_id(want)
    chunks = [ev for ev in watch.log if ev[0] == "chunk"]
    starts = {}
    for _, first, start in chunks:
        starts.setdefault(first, []).append(start)
    assert sorted(starts.values()) == sorted(
        list(range(0, n, CHUNK)) for n in (50, 40, 17, 64, 33))
    # 16 and 5 tokens: today's whole prefill
    assert sum(ev == ("prefill",) for ev in watch.log) == 2
    assert sched.slots.used_pages == sched.slots.cached_prefix_pages
    assert sched.slots.free_slots == 4 and not sched.has_work()


def test_never_two_chunks_between_two_decode_dispatches(toys, metrics):
    """Three long admissions queued behind a running row: one enqueue
    — a chunk, or a short prompt's whole prefill — a decode dispatch,
    first come first, and the running row gets its token every call
    while its neighbours are in mid-prefill."""
    model, _, params = toys
    sched = chunk_sched(model, params)
    runner = Request(prompt=[3, 1, 4], max_new_tokens=40)
    sched.submit(runner)
    sched.step()
    sched.step()
    watch = Watch(sched, params)
    longs = [Request(prompt=p, max_new_tokens=3)
             for p in long_prompts([60, 9, 41, 50], seed=2)]
    for r in longs:
        sched.submit(r)
    mid = 0
    while any(r.finish_reason is None for r in longs):
        before = len(runner.generated)
        sched.step()
        assert len(runner.generated) == before + 1
        mid += sched._underway is not None
    assert mid >= 6             # 4 + 3 + 4 chunks: calls in mid-prefill
    assert max(watch.between_steps()) == 1
    order = [ev for ev in watch.log if ev[0] != "step"]
    firsts = [r.prompt[0] for r in longs]
    assert order == (
        [("chunk", firsts[0], at) for at in (0, 16, 32, 48)]
        + [("prefill",)]
        + [("chunk", firsts[2], at) for at in (0, 16, 32)]
        + [("chunk", firsts[3], at) for at in (0, 16, 32, 48)])
    # a slot in mid-prefill is masked: the dispatches between a
    # request's first and last chunk ran the rows before it alone
    steps = [ev[1] for ev in watch.log if ev[0] == "step"]
    assert steps[:4] == [1, 1, 1, 2]
    # every enqueue a prefill, the multi-piece ones chunks; each timed
    # by its own read or counted unobserved
    snap = metrics.snapshot()
    count = lambda name: sum(  # noqa: E731
        v for k, v in snap["counters"].items()
        if k.split("{")[0] == name)
    assert count("serving_prefill_chunks_total") == 11
    assert count("serving_prefills_total") == 13     # + runner, + short
    assert (snap["histograms"]["serving_prefill_ms"]["count"]
            + count("serving_prefill_unobserved_total")) == 13
    assert snap["counters"]['serving_prefills_total{bucket="16"}'] == 12


def test_with_nothing_running_the_chunks_go_in_one_call(toys):
    """No row waits for a token: the whole prompt is enqueued at once,
    and the request runs from that call's dispatch on."""
    model, _, params = toys
    sched = chunk_sched(model, params)
    watch = Watch(sched, params)
    req = Request(prompt=long_prompts([50], seed=3)[0], max_new_tokens=2)
    sched.submit(req)
    assert sched.step()["admitted"] == 1
    assert [ev[0] for ev in watch.log] == ["chunk"] * 4 + ["step"]
    assert len(sched._flight.prefills) == 4 and req.slot is not None


def test_the_radix_tree_learns_a_prompt_after_its_last_chunk(toys):
    model, _, params = toys
    sched = chunk_sched(model, params)
    runner = Request(prompt=[2, 7, 1], max_new_tokens=30)
    sched.submit(runner)
    sched.step()
    prompt = long_prompts([45], seed=4)[0]
    req = Request(prompt=prompt, max_new_tokens=2)
    sched.submit(req)
    seen = []
    while req.t_admitted is None:
        sched.step()
        seen.append((sched._underway is not None,
                     len(sched.slots.match_prefix(prompt))))
    # in mid-prefill the tree holds nothing of it; its slot is
    # claimed, masked, and its row of the page table still NULL
    assert seen == [(True, 0), (True, 0), (False, 5)]
    assert (sched.slots._table[req.slot][:6] != NULL_PAGE).all()


def test_the_page_table_row_stays_null_until_the_last_chunk(toys):
    model, _, params = toys
    sched = chunk_sched(model, params)
    runner = Request(prompt=[2, 7, 1], max_new_tokens=30)
    sched.submit(runner)
    sched.step()
    req = Request(prompt=long_prompts([45], seed=4)[0], max_new_tokens=2)
    sched.submit(req)
    sched.step()
    slot = sched._underway.slot
    assert slot is not None and slot != runner.slot
    assert (sched.slots._table[slot] == NULL_PAGE).all()
    assert (np.asarray(sched.slots.cache.page_table)[slot]
            == NULL_PAGE).all()
    assert int(np.asarray(sched.slots.cache.offset)[slot]) == 0
    assert sched.slots.free_slots == 2      # claimed all the same
    sched.drain()
    assert req.finish_reason == FinishReason.LENGTH


def test_a_prefix_hit_starts_the_first_chunk_at_the_matched_length(toys):
    model, plain, params = toys
    shared = long_prompts([24], seed=5)[0]
    tails = long_prompts([40, 30], seed=6)
    prompts = [shared + t for t in tails]

    def serve(m, watch=False):
        sched = chunk_sched(m, params)
        w = Watch(sched, params) if watch else None
        out = []
        for p in prompts:           # one after the other: a hit
            r = Request(prompt=p, max_new_tokens=5)
            sched.run([r])
            out.append(r.generated)
        return out, w
    want, _ = serve(plain)
    got, watch = serve(model, watch=True)
    assert got == want
    starts = [ev[2] for ev in watch.log if ev[0] == "chunk"]
    # 64 tokens cold: 0..48; then 54 tokens with 24 matched (3 pages)
    assert starts == [0, 16, 32, 48, 24, 40]


#: (tokens behind a 24-token prefix another request left in the tree,
#: the plan of the second request): more than a chunk is left behind
#: the three matched pages / at most a chunk is / the prefix is no
#: other request's (a miss).
PLANS = {"chunks_behind_the_match": (30, "chunk", [(24, 16), (40, 16)]),
         "suffix_behind_the_match": (9, "suffix", [(24, 16)]),
         "a_miss_in_chunks": (None, "chunk", [(0, 16), (16, 16),
                                              (32, 16)])}


@pytest.mark.parametrize("case", sorted(PLANS))
def test_a_model_without_a_state_plans_from_the_match_and_carries_nothing(
        toys, case):
    """What PR 43 put behind `_stateful` (a model with a recurrent
    state plans its chunks from position 0 and hands the state from
    chunk to chunk) leaves a model without one where it was: its
    pieces begin at the matched length, a hit that leaves at most a
    chunk is a suffix prefill, no admission carries anything, nothing
    counts as recomputed, and the streams are the unchunked model's."""
    model, plain, params = toys
    tail, mode, pieces = PLANS[case]
    shared = long_prompts([24], seed=11)[0]
    first = shared + long_prompts([20], seed=12)[0]
    second = (shared + long_prompts([tail], seed=13)[0] if tail
              else long_prompts([40], seed=14)[0])

    def serve(m, plans=None):
        sched = chunk_sched(m, params)
        assert not sched._stateful
        if plans is not None:
            plan = sched._plan
            sched._plan = lambda *a: plans.append(plan(*a)) or plans[-1]
        out = []
        for p in (first, second):       # one after the other: a hit
            r = Request(prompt=p, max_new_tokens=5)
            sched.submit(r)
            while sched.has_work():
                sched.step()
                adm = sched._underway
                assert adm is None or adm.carry is None
            out.append(r.generated)
        assert sched._state_recomputed == 0
        return out
    plans = []
    assert serve(model, plans) == serve(plain)
    assert (plans[1].mode, plans[1].pieces) == (mode, pieces)
    assert all(adm.carry is None for adm in plans)


@pytest.mark.parametrize("how", ["stop", "pool_dry"])
def test_giving_up_in_mid_prefill_returns_every_page_and_the_slot(
        toys, how):
    model, plain, params = toys
    kw = dict(prefix_cache=False, num_pages=7 if how == "pool_dry" else 40)
    sched = chunk_sched(model, params, **kw)
    runner = Request(prompt=long_prompts([15], seed=8)[0],
                     max_new_tokens=30)
    long = Request(prompt=long_prompts([40], seed=7)[0], max_new_tokens=4)
    sched.submit(runner)
    sched.step()
    sched.submit(long)
    sched.step()
    assert sched._underway is not None and sched._underway.req is long
    assert sched.slots.used_pages == 2 + 5 and sched.slots.free_slots == 2
    if how == "stop":
        sched.stop()
        assert sched._underway is None
        assert sched.slots.used_pages == 0 and sched.slots.free_slots == 4
        assert long.reject_reason == RejectReason.STOPPED
        assert runner.finish_reason == FinishReason.STOPPED
        return
    # 7 pages, all claimed: the runner's next page meets the long
    # prompt's claim with two of its three chunks in.  The admission
    # under way is given up — the newest claim — and starts over once
    # the pool takes it
    gave_up = []
    give_up = sched._give_up_underway
    sched._give_up_underway = lambda: gave_up.append(
        sched._underway.done) or give_up()
    sched.drain()
    assert long.finish_reason == runner.finish_reason == FinishReason.LENGTH
    assert sched.slots.used_pages == 0 and sched.slots.free_slots == 4
    assert gave_up == [2] and long.preemptions == 0
    want = [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens)
            for r in (runner, long)]
    chunk_sched(plain, params, prefix_cache=False).run(want)
    assert [r.generated for r in want] == [runner.generated,
                                           long.generated]


def test_a_chunks_scatter_hands_back_the_offset_it_was_given(toys):
    """`_starved` asks whether the program enqueued last has finished
    by asking the cache's offset: the scatter of a chunk's rows, which
    changes no offset, still has to return it — a new handle, the old
    one donated — or every dispatch behind a chunk would read the chip
    as idle."""
    model, _, params = toys
    slots = PagedKV(model, 2, max_seq=64, page_size=8)
    prompt = long_prompts([40], seed=9)[0]
    slot = slots.begin_prefill(len(prompt), [])
    ids, _ = pad_prompt(prompt[:CHUNK], CHUNK)
    row = jax.jit(model.make_prefill_suffix_fn())(
        params, ids, jnp.int32(0), model.create_cache(1, CHUNK),
        (slots.cache.ks, slots.cache.vs), slots.prefill_pages(slot))
    before, table = slots.cache.offset, slots.cache.page_table
    slots.insert_rows(slot, row, 0)
    assert slots.cache.offset is not before and before.is_deleted()
    assert slots.cache.page_table is table
    assert (np.asarray(slots.cache.offset) == 0).all()
    pages = slots.prefill_pages(slot)[:2]
    np.testing.assert_array_equal(
        np.asarray(slots.cache.ks[0][pages, 0]).reshape(CHUNK, -1),
        np.asarray(row.ks[0][0, 0]))
    slots.release(slot)
    assert slots.used_pages == 0 and slots.free_slots == 2
