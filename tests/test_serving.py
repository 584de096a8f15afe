"""Continuous-batching serving runtime tests — CPU-only, deterministic
(virtual clock, seeded prompts; the toy model exercises the real
machinery: bucketed prefill, slot insert, masked step, retirement).
All tier-1 (`not slow`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.models.kv_cache import KVCache
from triton_distributed_tpu.serving import (
    ContinuousBatchingScheduler,
    FinishReason,
    RejectReason,
    Request,
    RequestState,
    SchedulerConfig,
    SlotKV,
    ToyConfig,
    ToyModel,
    masked_sample,
    pad_prompt,
    pick_bucket,
    request_key,
)


class Clock:
    """Deterministic virtual clock: advances only when asked."""

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


def make_sched(model, params, clock=None, **cfg_kw):
    cfg_kw.setdefault("num_slots", 3)
    cfg_kw.setdefault("prefill_buckets", (8, 16, 32))
    ck = clock or Clock()
    return ContinuousBatchingScheduler(
        model, params, SchedulerConfig(**cfg_kw),
        clock=ck.now, clock_advance=ck.advance), ck


def serial_reference(model, params, prompt, n, key=None,
                     temperature=0.0):
    """Exact-length prefill + per-step batch-1 decode — the ground
    truth the continuous path must reproduce token-for-token."""
    from triton_distributed_tpu.models.utils import sample_token
    prefill = jax.jit(model.make_prefill_fn())
    decode = jax.jit(model.make_decode_fn())
    ids = jnp.asarray(prompt, jnp.int32)[None]
    cache = model.create_cache(1)
    logits, cache = prefill(params, ids, cache)
    toks = []
    kc = key
    for _ in range(n):
        if temperature > 0:
            kc, sub = jax.random.split(kc)
            cur = sample_token(logits, sub, temperature)
        else:
            cur = jnp.argmax(logits, -1).astype(jnp.int32)
        toks.append(int(cur[0]))
        logits, cache = decode(params, cur, cache)
    return toks


def rand_prompts(n, vocab=61, seed=0, lo=3, hi=20):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, vocab, rng.integers(lo, hi)))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# unit: buckets, padding, masked sampling, KV-cache helpers
# ---------------------------------------------------------------------------


def test_pick_bucket():
    assert pick_bucket(1, (8, 16, 32)) == 8
    assert pick_bucket(8, (8, 16, 32)) == 8
    assert pick_bucket(9, (8, 16, 32)) == 16
    assert pick_bucket(32, (8, 16, 32)) == 32
    assert pick_bucket(33, (8, 16, 32)) is None
    assert pick_bucket(5, (32, 8, 16)) == 8  # order-insensitive


def test_pad_prompt():
    ids, s = pad_prompt([5, 6, 7], 8, pad_id=0)
    assert ids.shape == (1, 8) and s == 3
    assert ids[0, :3].tolist() == [5, 6, 7]
    assert ids[0, 3:].tolist() == [0] * 5


def test_masked_sample_returns_pad_id_deterministically():
    """Satellite: masked rows must yield the EOS/pad id, never a
    sample from (stale) logits — even at temperature > 0."""
    b, v, pad = 8, 16, 13
    # stale logits hugely favour token 1 everywhere
    logits = jnp.zeros((b, v)).at[:, 1].set(100.0)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(b))
    active = jnp.asarray([i % 2 == 0 for i in range(b)])
    for temperature in (0.0, 1.0, 5.0):
        out = np.asarray(masked_sample(logits, keys, active, pad,
                                       temperature=temperature))
        assert (out[1::2] == pad).all(), (temperature, out)
        assert (out[::2] != pad).all(), (temperature, out)


def test_kv_cache_bytes_per_slot():
    cache = KVCache.create(num_layers=3, batch=4, num_kv_heads=2,
                           max_seq=32, head_dim=8, dtype=jnp.bfloat16)
    # 3 layers x (K+V) x 2 heads x 32 seq x 8 dim x 2 bytes
    assert cache.bytes_per_slot() == 3 * 2 * 2 * 32 * 8 * 2
    q = KVCache.create(num_layers=3, batch=4, num_kv_heads=2,
                       max_seq=32, head_dim=8, quantized=True)
    # int8 K+V (1 byte) + f32 per-token scales for each of K and V
    assert q.bytes_per_slot() == (3 * 2 * 2 * 32 * 8 * 1
                                  + 3 * 2 * 2 * 32 * 4)


def test_kv_cache_reset_slot():
    cache = KVCache.create(num_layers=1, batch=3, num_kv_heads=1,
                           max_seq=8, head_dim=4)
    cache = cache.set_offset(5)
    cache = cache.reset_slot(1)
    assert cache.offset.tolist() == [5, 0, 5]


def test_slotkv_insert_and_release(toy):
    model, params = toy
    slots = SlotKV(model.create_cache(3, max_seq=64))
    prefill = jax.jit(model.make_prefill_fn())
    ids, s = pad_prompt([4, 5, 6, 7, 8], 8)
    row = model.create_cache(1, max_seq=8)
    _, row = prefill(params, ids, row)
    slot = slots.insert_prefill(row, s, request_key(7))
    assert slots.active_slots == 1
    assert bool(slots.active_mask()[slot])
    # offset = prompt_len - 1: the masked step recomputes position s-1
    assert int(slots.cache.offset[slot]) == s - 1
    assert np.asarray(slots.keys[slot]).tolist() == np.asarray(
        jax.random.PRNGKey(7)).tolist()
    # row cache KV landed in the slot
    got = np.asarray(slots.cache.ks[0][slot, :, :s])
    want = np.asarray(row.ks[0][0, :, :s])
    np.testing.assert_allclose(got, want)
    slots.release(slot)
    assert slots.active_slots == 0
    assert int(slots.cache.offset[slot]) == 0
    assert not bool(slots.active_mask()[slot])


# ---------------------------------------------------------------------------
# scheduler logic: admission, backpressure, retirement, reuse
# ---------------------------------------------------------------------------


def test_admission_fifo_order(toy):
    model, params = toy
    sched, ck = make_sched(model, params, num_slots=2)
    reqs = [Request(prompt=[1 + i, 2, 3], max_new_tokens=3)
            for i in range(5)]
    for r in reqs:
        assert sched.submit(r)
    sched.step()
    # only the first two fit; FIFO order
    assert reqs[0].state == RequestState.RUNNING
    assert reqs[1].state == RequestState.RUNNING
    assert all(r.state == RequestState.QUEUED for r in reqs[2:])
    done = sched.drain()
    assert len(done) == 5
    # admission (hence first-token) times follow submission order
    admits = [r.t_admitted for r in reqs]
    assert admits == sorted(admits)


def test_arrival_times_gate_admission(toy):
    model, params = toy
    sched, ck = make_sched(model, params, num_slots=4)
    early = Request(prompt=[1, 2, 3], max_new_tokens=2,
                    arrival_time=0.0)
    late = Request(prompt=[4, 5, 6], max_new_tokens=2,
                   arrival_time=10.0)
    sched.submit(early)
    sched.submit(late)
    sched.step()
    assert early.state == RequestState.RUNNING
    assert late.state == RequestState.QUEUED
    sched.drain()   # advances the virtual clock to 10.0 when idle
    assert late.state == RequestState.FINISHED
    assert late.t_admitted >= 10.0


def test_backpressure_queue_full(toy):
    model, params = toy
    sched, _ = make_sched(model, params, max_queue=2)
    r1, r2, r3 = (Request(prompt=[1, 2], max_new_tokens=1)
                  for _ in range(3))
    assert sched.submit(r1) and sched.submit(r2)
    assert not sched.submit(r3)
    assert r3.state == RequestState.REJECTED
    assert r3.reject_reason == RejectReason.QUEUE_FULL


def test_reject_prompt_too_long_and_kv_capacity(toy):
    model, params = toy
    sched, _ = make_sched(model, params)   # buckets (8,16,32), max 64
    too_long = Request(prompt=list(range(1, 40)), max_new_tokens=1)
    assert not sched.submit(too_long)
    assert too_long.reject_reason == RejectReason.PROMPT_TOO_LONG
    too_much = Request(prompt=[1] * 30, max_new_tokens=40)
    assert not sched.submit(too_much)
    assert too_much.reject_reason == RejectReason.EXCEEDS_KV_CAPACITY
    ok = Request(prompt=[1] * 30, max_new_tokens=30)
    assert sched.submit(ok)


def test_capacity_boundary_request_gets_full_length(toy):
    """A request sized exactly to the KV horizon (prompt + max_new ==
    max_seq + 1: the final token needs no KV write) must deliver every
    promised token and finish LENGTH, not KV_CAPACITY: its last step
    is known to be its last before it is read, and no step is
    dispatched past the horizon."""
    model, params = toy
    sched, _ = make_sched(model, params, max_seq=16,
                          prefill_buckets=(8,))
    req = Request(prompt=[1, 2, 3, 4], max_new_tokens=13)
    assert sched.submit(req), req.reject_reason
    sched.drain()
    assert req.finish_reason == FinishReason.LENGTH, (
        req.finish_reason, len(req.generated))
    assert len(req.generated) == 13
    over = Request(prompt=[1, 2, 3, 4], max_new_tokens=14)
    assert not sched.submit(over)
    assert over.reject_reason == RejectReason.EXCEEDS_KV_CAPACITY


def test_eos_retirement(toy):
    model, params = toy
    prompt = [7, 8, 9, 10]
    first = serial_reference(model, params, prompt, 1)[0]
    sched, _ = make_sched(model, params)
    req = Request(prompt=prompt, max_new_tokens=10,
                  eos_token_ids=(first,))
    sched.submit(req)
    sched.drain()
    assert req.state == RequestState.FINISHED
    assert req.finish_reason == FinishReason.EOS
    assert req.generated == [first]   # EOS included, then stop


def test_length_retirement_and_slot_reuse(toy):
    model, params = toy
    sched, _ = make_sched(model, params, num_slots=2)
    reqs = [Request(prompt=p, max_new_tokens=4)
            for p in rand_prompts(6, seed=3)]
    done = sched.run(reqs)
    assert len(done) == 6
    assert all(r.finish_reason == FinishReason.LENGTH for r in done)
    assert all(len(r.generated) == 4 for r in done)
    # 6 requests through 2 slots: slots were reused
    slots_used = [r.slot for r in done]
    assert set(slots_used) == {0, 1}
    assert sched.slots.active_slots == 0
    assert sched.slots.cache.offset.tolist() == [0, 0]


def test_kv_budget_caps_concurrency(toy):
    model, params = toy
    per_slot = model.create_cache(1, max_seq=64).bytes_per_slot()
    sched, _ = make_sched(model, params, num_slots=4,
                          kv_budget_bytes=2 * per_slot)
    for p in rand_prompts(6, seed=4):
        sched.submit(Request(prompt=p, max_new_tokens=3))
    max_active = 0
    while sched.has_work():
        sched.step()
        max_active = max(max_active, sched.slots.active_slots)
    assert max_active == 2          # budget, not slot count, bound it
    assert len(sched.finished) == 6


def test_infeasible_kv_budget_rejects_instead_of_spinning(toy):
    """A budget below one slot's bytes can never admit: submit must
    reject (typed) rather than queue work drain() would spin on."""
    model, params = toy
    sched, _ = make_sched(model, params, kv_budget_bytes=1)
    req = Request(prompt=[1, 2, 3], max_new_tokens=2)
    assert not sched.submit(req)
    assert req.reject_reason == RejectReason.EXCEEDS_KV_CAPACITY
    assert not sched.has_work()


def test_stop_aborts(toy):
    from triton_distributed_tpu.observability import get_registry
    model, params = toy
    sched, _ = make_sched(model, params, num_slots=2)
    reqs = [Request(prompt=[1, 2, 3], max_new_tokens=50)
            for _ in range(4)]
    for r in reqs:
        sched.submit(r)
    sched.step()
    rejected = get_registry().counter(
        "serving_requests_rejected_total", reason="stopped")
    before = rejected.value
    sched.stop()
    # queued requests count as rejects, same as the submit() path
    assert rejected.value - before == 2
    assert not sched.has_work()
    states = sorted(r.state.value for r in reqs)
    assert states == ["finished", "finished", "rejected", "rejected"]
    assert all(r.finish_reason == FinishReason.STOPPED
               for r in reqs if r.state == RequestState.FINISHED)
    late = Request(prompt=[1], max_new_tokens=1)
    assert not sched.submit(late)
    assert late.reject_reason == RejectReason.STOPPED


# ---------------------------------------------------------------------------
# end-to-end correctness: continuous == serial, token for token
# ---------------------------------------------------------------------------


def test_continuous_matches_serial_greedy(toy):
    """Mid-decode joiners must not perturb anyone's tokens: bucketed
    prefill + slot insert + masked step reproduce the serial engine
    exactly.

    Heterogeneous max_new with everyone eligible at once forces REAL
    mid-decode insertion: rows retire at different steps, so each
    joiner is inserted while its neighbors are mid-stream.  (A
    staggered ``arrival_time`` schedule would NOT test this under the
    virtual clock — time only advances while the batch is idle, which
    serializes the requests.)"""
    model, params = toy
    prompts = rand_prompts(7, seed=1)
    gens = [3, 7, 4, 6, 2, 5, 8]
    want = [serial_reference(model, params, p, g)
            for p, g in zip(prompts, gens)]
    sched, _ = make_sched(model, params, num_slots=3)
    for p, g in zip(prompts, gens):
        sched.submit(Request(prompt=p, max_new_tokens=g))
    saw_mid_decode_join = False
    while sched.has_work():
        stats = sched.step()
        # a join is mid-decode when rows beyond the joiners were
        # already active in the same iteration
        if stats["admitted"] and stats["active"] > stats["admitted"]:
            saw_mid_decode_join = True
    assert saw_mid_decode_join
    done = sched.finished
    assert len(done) == 7
    for r, w in zip(sorted(done, key=lambda r: r.request_id), want):
        assert r.generated == w, (r.request_id, r.generated, w)


def test_sampling_independent_of_batch_composition(toy):
    """Per-request RNG keys: a request's sampled stream is a function
    of (prompt, seed), not of who shares the batch — the serial
    1-slot schedule and a packed 4-slot schedule agree."""
    model, params = toy
    prompts = rand_prompts(6, seed=5)
    outs = {}
    for slots in (1, 4):
        sched, _ = make_sched(model, params, num_slots=slots,
                              temperature=1.0)
        reqs = [Request(prompt=p, max_new_tokens=4, seed=100 + i)
                for i, p in enumerate(prompts)]
        done = sched.run(reqs)
        outs[slots] = [r.generated for r in
                       sorted(done, key=lambda r: r.request_id)]
    assert outs[1] == outs[4]


def test_engine_serve_cache_reuse(toy):
    """Satellite: Engine.serve accepts a caller-provided cache, reuses
    it across calls (returning the donated-through cache), and the
    tokens match the fresh-cache path."""
    from triton_distributed_tpu.models.engine import Engine
    model, params = toy
    eng = Engine(model, temperature=0.0, scan_decode=True)
    ids = jnp.asarray(rand_prompts(1, seed=6, lo=8, hi=9)[0],
                      jnp.int32)[None]
    fresh = eng.serve(params, ids, 5)
    cache = model.create_cache(1)
    out1, cache = eng.serve(params, ids, 5, cache=cache)
    out2, cache = eng.serve(params, ids, 5, cache=cache)
    assert (np.asarray(fresh) == np.asarray(out1)).all()
    assert (np.asarray(out1) == np.asarray(out2)).all()


# ---------------------------------------------------------------------------
# observability: SLO metrics + per-request spans in the timeline
# ---------------------------------------------------------------------------


def test_serving_metrics_and_spans(toy, tmp_path, monkeypatch):
    from triton_distributed_tpu.observability import (
        get_registry, get_tracer, prometheus_text)
    from triton_distributed_tpu.observability.timeline import (
        merge_directory)
    model, params = toy
    reg = get_registry()
    reg.clear()
    tracer = get_tracer()
    tracer.clear()

    sched, _ = make_sched(model, params, num_slots=2)
    reqs = [Request(prompt=p, max_new_tokens=3,
                    arrival_time=i * 0.01)
            for i, p in enumerate(rand_prompts(4, seed=7))]
    done = sched.run(reqs)
    assert len(done) == 4

    snap = reg.snapshot()
    assert snap["counters"]["serving_requests_submitted_total"] == 4
    assert snap["counters"][
        'serving_requests_completed_total{reason="length"}'] == 4
    assert snap["counters"]["serving_tokens_generated_total"] == 12
    for h in ("serving_ttft_ms", "serving_tbt_ms",
              "serving_queue_wait_ms", "serving_decode_step_ms",
              "serving_prefill_ms", "serving_request_latency_ms"):
        assert snap["histograms"][h]["count"] > 0, h
    assert snap["histograms"]["serving_ttft_ms"]["count"] == 4
    assert snap["gauges"]["serving_active_slots"] == 0
    assert snap["gauges"]["serving_slot_occupancy"] == 0.0
    assert snap["gauges"]["serving_kv_budget_bytes"] > 0

    # Prometheus export carries the SLO metrics
    text = prometheus_text()
    assert "serving_ttft_ms_bucket" in text
    assert "serving_queue_depth" in text

    # one span per request, landing in the merged cross-rank timeline
    req_spans = [s for s in tracer.finished()
                 if s.name == "serving.request"]
    assert len(req_spans) == 4
    assert {s.attrs["request_id"] for s in req_spans} == {
        r.request_id for r in done}
    import json
    for rank in (0, 1):   # two synthetic ranks so the merge has work
        monkeypatch.setenv("TDT_PROCESS_ID", str(rank))
        tracer.export_chrome_trace(
            str(tmp_path / f"trace-rank-{rank}.json"))
    report = merge_directory(str(tmp_path))
    assert "serving.request" in report["spans"]
    assert report["spans"]["serving.request"]["occurrences"] == 4
    merged = json.load(open(tmp_path / "merged_trace.json"))
    assert sum(e.get("name") == "serving.request" and e.get("pid") == 0
               for e in merged["traceEvents"]) == 4


def test_observability_disabled_still_serves(toy, monkeypatch):
    monkeypatch.setenv("TDT_OBSERVABILITY", "0")
    model, params = toy
    sched, _ = make_sched(model, params)
    done = sched.run([Request(prompt=[1, 2, 3], max_new_tokens=2)])
    assert len(done) == 1 and len(done[0].generated) == 2


def test_the_slot_layout_never_chunks_and_serves_the_same_streams(toy):
    """A model that names a chunk length prefills in chunks over the
    PAGED layout alone (the chunks attend the page pool): the slot
    layout admits its whole prompt through its bucket, and both serve
    the streams of the model that never chunks."""
    _, params = toy
    kw = dict(vocab_size=61, hidden=16, max_seq_len=64)
    chunking = ToyModel(ToyConfig(prefill_chunk=8, **kw))
    plain = ToyModel(ToyConfig(**kw))
    rng = np.random.default_rng(12)
    prompts = [list(map(int, rng.integers(1, 61, n)))
               for n in (30, 7, 19, 8, 25)]

    def serve(model, **cfg):
        sched, _ = make_sched(model, params, **cfg)
        reqs = [Request(prompt=p, max_new_tokens=5) for p in prompts]
        sched.run(reqs)
        return sched, [r.generated for r in reqs]
    want = serve(plain)[1]
    slotted, got = serve(chunking)
    assert slotted._chunk == 0 and got == want
    paged, got = serve(chunking, kv_layout="paged", page_size=8)
    assert paged._chunk == 8 and got == want
