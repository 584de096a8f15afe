"""Speculative decoding tests — CPU-only, deterministic, tier-1.

The load-bearing claim: speculative output is TOKEN-FOR-TOKEN
identical to the non-speculative engine — greedy AND sampled (the
accept rule is exact-match verification: every emitted token is the
target's own sample under its true context and key chain, so
rejection changes how many tokens a dispatch commits, never which) —
across both KV layouts, page-boundary rollbacks, preempt-and-resume
and cluster failover.  Plus drafter units, the key-advance
accounting failover depends on, the accept-collapse throttle, and
the observability surfaces (metrics / lineage / heartbeat / doctor).
"""

import jax
import pytest

from triton_distributed_tpu.models.kv_cache import NULL_PAGE, pages_for
from triton_distributed_tpu.serving import (
    BatchedDraftModelDrafter,
    ContinuousBatchingScheduler,
    DraftModelDrafter,
    NgramDrafter,
    Request,
    SchedulerConfig,
    ToyConfig,
    ToyModel,
)
from triton_distributed_tpu.serving.cluster.replica import (
    advance_request_key,
)


@pytest.fixture(autouse=True)
def _fresh_observability_state():
    """Spec rounds record DecisionEvents (throttle), lineage hops and
    flight-ring entries; clear the process-global rings so later test
    files' capacity asserts see their own traffic only (the
    test_cluster idiom).  The tracer too: a killed replica's corpse
    keeps its in-flight `serving.request` spans open by design
    (nothing is salvaged from it), and test_tracing's heartbeat
    forensics assert on the CURRENT open-span stack."""
    from triton_distributed_tpu.observability import (
        feedback,
        get_tracer,
    )
    from triton_distributed_tpu.observability.lineage import (
        get_lineage_recorder,
    )
    from triton_distributed_tpu.observability.recorder import (
        get_flight_recorder,
    )
    yield
    feedback.clear_recent_decisions()
    get_lineage_recorder().clear()
    get_flight_recorder().clear()
    get_tracer().clear()


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
                               max_seq_len=96))
    params = model.init_params(jax.random.key(0))
    return model, params


def _sched(model, params, clock=None, **kw):
    clock = clock or Clock()
    cfg = dict(num_slots=3, prefill_buckets=(8, 16), page_size=8)
    cfg.update(kw)
    return ContinuousBatchingScheduler(
        model, params, SchedulerConfig(**cfg),
        clock=clock.now, clock_advance=clock.advance)


def _reqs(n=6, max_new=20, eos=(), stagger=True):
    return [Request(prompt=[1 + i, 2, 3, 4],
                    max_new_tokens=max_new + (i % 5), seed=i,
                    eos_token_ids=eos,
                    arrival_time=(i % 2) * 0.01 if stagger else None)
            for i in range(n)]


def _streams(done):
    return [r.generated for r in
            sorted(done, key=lambda r: r.request_id)]


def _batched_factory(model, params, buckets=(8, 16)):
    return lambda s: BatchedDraftModelDrafter(
        model, params, num_slots=s.config.num_slots,
        max_seq=s.max_seq, prefill_buckets=buckets)


# ---------------------------------------------------------------------------
# Drafter units
# ---------------------------------------------------------------------------


def test_ngram_drafter_proposes_continuation():
    d = NgramDrafter(max_n=3, min_n=1)
    req = Request(prompt=[5, 6, 7, 8, 9, 5, 6, 7], max_new_tokens=4)
    # suffix (5, 6, 7) occurred at position 0; continuation 8, 9, 5
    assert d.propose(req, 3) == [8, 9, 5]
    assert d.propose(req, 2) == [8, 9]


def test_ngram_drafter_prefers_longest_match():
    d = NgramDrafter(max_n=3, min_n=1)
    # last trigram (2, 3, 4) matches at 1 (-> 9); the last unigram 4
    # also occurs at 4 (-> 5) — the trigram evidence must win.
    req = Request(prompt=[1, 2, 3, 4, 9, 4, 5, 2, 3, 4],
                  max_new_tokens=4)
    assert d.propose(req, 1) == [9]


def test_ngram_drafter_no_match_is_empty():
    d = NgramDrafter()
    req = Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=4)
    assert d.propose(req, 4) == []
    # accounting: nothing proposed
    assert d.proposed_tokens == 0


def test_ngram_drafter_reads_generated_tail():
    d = NgramDrafter()
    req = Request(prompt=[9, 9], max_new_tokens=8)
    req.generated = [4, 5, 6, 4, 5]
    assert d.propose(req, 2) == [6, 4]


def test_draft_model_self_draft_matches_greedy(toy):
    """The per-request draft state machine stays coherent through
    propose/commit rounds: a self-draft (same model, same params)
    must keep proposing the target's exact greedy continuation —
    i.e. accept every draft — for a whole stream."""
    model, params = toy
    drafter = DraftModelDrafter(model, params, max_seq=96,
                                prefill_buckets=(8, 16))
    sched = _sched(model, params, spec_k=3, spec_drafter=drafter)
    done = sched.run(_reqs(n=4))
    assert all(r.spec_proposed > 0 for r in done)
    # every draft the verify pass actually scored was accepted (the
    # drafter's own rate counts pre-cap proposals: the scheduler
    # trims drafts past a request's remaining budget, so it sits
    # slightly below 1.0 by construction)
    assert all(r.spec_accepted == r.spec_proposed for r in done)
    assert drafter.accept_rate > 0.8


def test_batched_drafter_self_draft_full_accept(toy):
    model, params = toy
    sched = _sched(model, params, spec_k=4,
                   spec_drafter=_batched_factory(model, params))
    done = sched.run(_reqs(n=6))
    assert all(r.spec_accepted == r.spec_proposed > 0 for r in done)


# ---------------------------------------------------------------------------
# Exactness: greedy and sampled, both layouts, both drafters
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["slots", "paged"])
@pytest.mark.parametrize("spec_k", [2, 4])
def test_greedy_exact_ngram(toy, layout, spec_k):
    model, params = toy
    ref = _streams(_sched(model, params, kv_layout=layout).run(
        _reqs()))
    spec = _sched(model, params, kv_layout=layout, spec_k=spec_k)
    out = _streams(spec.run(_reqs()))
    assert out == ref


@pytest.mark.parametrize("layout", ["slots", "paged"])
def test_greedy_exact_draft_model(toy, layout):
    model, params = toy
    ref = _streams(_sched(model, params, kv_layout=layout).run(
        _reqs()))
    spec = _sched(model, params, kv_layout=layout, spec_k=4,
                  spec_drafter=_batched_factory(model, params))
    out = _streams(spec.run(_reqs()))
    assert out == ref


@pytest.mark.parametrize("layout", ["slots", "paged"])
def test_sampled_exact(toy, layout):
    """The accept rule keeps SAMPLED streams bit-exact too: each
    verify position samples with the row's own key chain, and the
    in-program key rollback leaves exactly one split per emitted
    token — so composition with temperature/top-k is unchanged."""
    model, params = toy
    kw = dict(kv_layout=layout, temperature=1.0, top_k=8)
    ref = _streams(_sched(model, params, **kw).run(_reqs()))
    out = _streams(_sched(model, params, spec_k=3, **kw).run(_reqs()))
    assert out == ref


def test_greedy_exact_with_eos(toy):
    """EOS lands mid-verify-round: tokens past it are discarded
    (bounded over-generation, as in block mode) and the stream is
    still identical to the per-token-sync engine's."""
    model, params = toy
    # find an eos id that actually occurs in the reference streams
    ref_done = _sched(model, params).run(_reqs())
    tok = ref_done[0].generated[2]
    ref = _streams(_sched(model, params).run(_reqs(eos=(tok,))))
    out = _streams(_sched(model, params, spec_k=4).run(
        _reqs(eos=(tok,))))
    assert out == ref
    assert any(len(s) < 20 for s in ref)   # EOS really fired


# ---------------------------------------------------------------------------
# Rollback: cursor, pages, page boundaries
# ---------------------------------------------------------------------------


def test_paged_rollback_unit(toy):
    """Direct `PagedKV.rollback`: unmap exactly the pages above the
    keep point — refcounts, table and free list exactly as if the
    rejected tail never happened."""
    model, params = toy
    sched = _sched(model, params, kv_layout="paged")
    req = Request(prompt=list(range(1, 10)), max_new_tokens=30)
    sched.submit(req)
    sched.step()
    kv = sched.slots
    slot = req.slot
    free0 = kv.pool.free_pages
    mapped0 = int(kv._mapped[slot])
    table0 = kv._table[slot].copy()
    # grow far past the current stream, as a verify dispatch would
    need = req.prompt_len + len(req.generated) + 16
    assert kv.ensure(slot, need)
    assert int(kv._mapped[slot]) == pages_for(need, kv.page_size)
    assert kv.pool.free_pages < free0
    # reject everything: roll back to the pre-grow state
    kv.rollback(slot, mapped0 * kv.page_size)
    assert int(kv._mapped[slot]) == mapped0
    assert kv.pool.free_pages == free0
    assert (kv._table[slot] == table0).all()
    assert (kv._table[slot][mapped0:] == NULL_PAGE).all()


@pytest.mark.parametrize("page_size", [4, 8])
def test_rollback_at_page_boundary(toy, page_size):
    """spec_k chosen so rejected tails repeatedly straddle page
    boundaries; streams stay exact and the pool balances after
    drain (every non-radix page freed)."""
    model, params = toy
    kw = dict(kv_layout="paged", page_size=page_size,
              prefill_buckets=(8, 16))
    ref = _streams(_sched(model, params, **kw).run(_reqs()))
    spec = _sched(model, params, spec_k=page_size - 1, **kw)
    out = _streams(spec.run(_reqs()))
    assert out == ref
    kv = spec.slots
    assert kv.pool.used_pages == kv.radix.cached_pages
    assert not any(kv._slot_pages[s] for s in range(kv.num_slots))


def test_preempt_resume_mid_speculation(toy):
    """A pool tight enough to force preemption while speculation is
    active: the victim resumes bit-exactly (key chain and KV cursor
    were rolled back to committed state before the snapshot)."""
    model, params = toy
    # bucket 32 keeps every resume (prompt + generated <= 28)
    # re-admittable, so preemption is always followed by an exact
    # resume rather than the bucket-outgrown truncation (whose
    # trigger point legitimately depends on dispatch grouping,
    # exactly as in block mode)
    kw = dict(kv_layout="paged", page_size=8, num_pages=11,
              prefill_buckets=(8, 32), temperature=1.0)
    reqs = lambda: [Request(prompt=[1 + i, 2, 3, 4],  # noqa: E731
                            max_new_tokens=24, seed=i)
                    for i in range(3)]
    ref_s = _sched(model, params, **kw)
    ref_done = ref_s.run(reqs())
    spec_s = _sched(model, params, spec_k=3, **kw)
    done = spec_s.run(reqs())
    assert _streams(done) == _streams(ref_done)
    assert sum(r.preemptions for r in done) > 0, (
        "pool was not tight enough to exercise preemption")


def test_key_advance_accounting(toy):
    """The failover contract: after ``g`` streamed tokens a slot's
    key equals ``split^g(PRNGKey(seed))[0]`` — the verify pass
    consumed exactly one split per EMITTED token (rolling back the
    rejected tail's splits), so `advance_request_key` stays exact
    under speculation, on both layouts."""
    model, params = toy
    for layout in ("slots", "paged"):
        sched = _sched(model, params, kv_layout=layout, spec_k=3,
                       temperature=1.0)
        req = Request(prompt=[7, 2, 3, 4], max_new_tokens=24, seed=5)
        sched.submit(req)
        for _ in range(3):
            sched.step()
        assert req.state.value == "running"
        assert len(req.generated) > 0
        got = sched.slots.snapshot_key(req.slot)
        want = advance_request_key(req.seed, len(req.generated))
        assert (got == want).all(), (layout, len(req.generated))
        sched.stop()


def test_cluster_failover_of_inflight_spec_request(toy):
    """Kill a replica while speculative requests are mid-stream: the
    survivors' resumed streams stay token-for-token identical to the
    non-speculative single-engine reference."""
    from triton_distributed_tpu.serving import (
        ClusterConfig,
        ServingCluster,
    )
    from triton_distributed_tpu.serving.cluster import RouterConfig

    model, params = toy
    trace = [dict(prompt=[1 + i, 2, 3], max_new_tokens=10 + (i % 3),
                  seed=i, arrival_time=0.002 * i) for i in range(6)]
    ref_sched = _sched(model, params, temperature=0.8, top_k=8)
    ref = _streams(ref_sched.run(
        [Request(**t) for t in trace]))

    sc = SchedulerConfig(num_slots=3, prefill_buckets=(8, 16),
                         temperature=0.8, top_k=8, spec_k=3)
    cluster = ServingCluster(model, params, ClusterConfig(
        n_replicas=2, scheduler=sc,
        router=RouterConfig(dead_after_s=0.005, dead_checks=2)))
    recs = [cluster.submit(**t) for t in trace]
    for _ in range(4):
        cluster.step()
    assert any(r.tokens for r in recs), "nothing in flight yet"
    cluster.kill_replica(0)
    done = cluster.drain()
    assert len(done) == len(trace), [r.state for r in recs]
    assert cluster.router.failovers, "no failover happened"
    toks = [list(r.tokens) for r in
            sorted(done, key=lambda r: r.record_id)]
    assert toks == ref
    # speculation really ran on the cluster's replicas
    assert any(rep.scheduler._spec_proposed > 0
               for rep in cluster.replicas)


# ---------------------------------------------------------------------------
# Throttle
# ---------------------------------------------------------------------------


class _JunkDrafter(NgramDrafter):
    """Always proposes tokens the target will reject."""

    name = "junk"

    def _propose(self, req, k):
        return [60] * k        # valid vocab id; never the argmax here


def test_accept_collapse_throttle(toy):
    from triton_distributed_tpu.observability import (
        feedback,
        get_registry,
    )

    model, params = toy
    get_registry().clear()
    feedback.clear_recent_decisions()
    ref = _streams(_sched(model, params).run(_reqs()))
    sched = _sched(model, params, spec_k=4,
                   spec_drafter=_JunkDrafter(),
                   spec_min_accept=0.3, spec_probe_tokens=16)
    out = _streams(sched.run(_reqs()))
    assert out == ref                       # fallback is bit-exact
    assert sched._spec_throttled
    assert sched._spec_accepted == 0
    snap = get_registry().snapshot()
    assert snap["counters"]["serving_spec_throttled_total"] == 1
    rows = [d for d in feedback.recent_decisions()
            if d.consumer == "serving.speculative"]
    assert len(rows) == 1 and rows[0].choice == "throttle"
    assert rows[0].inputs["accept_rate"] == 0.0


# ---------------------------------------------------------------------------
# Observability surfaces
# ---------------------------------------------------------------------------


def test_spec_metrics_and_lineage(toy):
    from triton_distributed_tpu.observability import get_registry
    from triton_distributed_tpu.observability.lineage import (
        get_lineage_recorder,
    )

    model, params = toy
    get_registry().clear()
    get_lineage_recorder().clear()
    sched = _sched(model, params, spec_k=3,
                   spec_drafter=_batched_factory(model, params))
    done = sched.run(_reqs(n=4))
    snap = get_registry().snapshot()
    c = snap["counters"]
    proposed = sum(r.spec_proposed for r in done)
    accepted = sum(r.spec_accepted for r in done)
    assert c["serving_spec_proposed_tokens_total"] == proposed > 0
    assert c["serving_spec_accepted_tokens_total"] == accepted
    assert (c["serving_spec_rejected_tokens_total"]
            == proposed - accepted)
    hist = snap["histograms"]["serving_spec_accept_tokens"]
    assert hist["count"] > 0
    assert snap["gauges"]["serving_spec_accept_rate"] == (
        pytest.approx(accepted / proposed))
    # one spec_verify lineage hop per verify round per request, with
    # the proposed/accepted detail TBT attribution needs
    rec = get_lineage_recorder()
    hops = [e for rid in rec.request_ids()
            for e in rec.events_for(rid) if e.hop == "spec_verify"]
    assert hops and all("proposed" in h.detail
                        and "accepted" in h.detail for h in hops)
    # request summaries carry the outcome
    d = done[0].to_dict()
    assert d["spec_proposed"] == done[0].spec_proposed
    assert d["spec_accepted"] == done[0].spec_accepted


def test_tbt_attribution_names_verify_cost():
    """A TBT spike with a spec_verify hop inside it (and no lifecycle
    stall) is attributed to the verify round; a preempt in the same
    gap still wins (verify hops are second-tier — every spec dispatch
    records one)."""
    from triton_distributed_tpu.observability.lineage import (
        LineageEvent,
        attribute_tbt,
    )

    times = [0.0, 0.01, 0.02, 0.2, 0.21]
    verify = LineageEvent(request_id=1, hop="spec_verify", ts=0.1)
    out = attribute_tbt([verify], times)
    assert out["spikes"] == [{"token": 3, "gap_ms": 180.0,
                              "cause": "spec_verify"}]
    preempt = LineageEvent(request_id=1, hop="preempt", ts=0.05)
    out = attribute_tbt([verify, preempt], times)
    assert out["spikes"][0]["cause"] == "preempt"


def test_spec_accept_rate_rides_heartbeat(toy):
    from triton_distributed_tpu.observability import get_registry
    from triton_distributed_tpu.observability.exporter import (
        heartbeat_payload,
    )

    model, params = toy
    get_registry().clear()
    body = heartbeat_payload()
    assert "serving_spec_accept_rate" not in body.get("serving", {})
    sched = _sched(model, params, spec_k=3)
    sched.run(_reqs(n=4))
    rate = heartbeat_payload()["serving"][
        "serving_spec_accept_rate"]
    assert rate == pytest.approx(
        sched._spec_accepted / sched._spec_proposed)


def test_doctor_notes_accept_collapse(tmp_path):
    import json

    from triton_distributed_tpu.observability.doctor import (
        diagnose,
        render_markdown,
    )

    def beat(rate):
        d = tmp_path / f"r{rate}"
        d.mkdir()
        with open(d / "heartbeat-rank-0.json", "w") as f:
            json.dump({"schema": 1, "rank": 0, "pid": 1,
                       "unix_time": 100.0, "step": 3,
                       "last_span": None, "open_spans": [],
                       "serving": {"serving_spec_accept_rate": rate}},
                      f)
        return diagnose([str(d)])

    bad = beat(0.12)
    assert bad["spec"] == [{"rank": 0, "accept_rate": 0.12,
                            "collapsed": True}]
    md = render_markdown(bad)
    assert "## Speculative decoding" in md and "COLLAPSED" in md
    assert "accept rate collapsed" in bad["verdict"]

    ok = beat(0.85)
    assert ok["spec"][0]["collapsed"] is False
    assert "collapsed" not in ok["verdict"]


def test_doctor_report_without_spec_gauge_unchanged(tmp_path):
    """Golden discipline: no gauge -> no section key."""
    import json

    from triton_distributed_tpu.observability.doctor import diagnose

    with open(tmp_path / "heartbeat-rank-0.json", "w") as f:
        json.dump({"schema": 1, "rank": 0, "pid": 1,
                   "unix_time": 100.0, "step": 3,
                   "last_span": None, "open_spans": []}, f)
    report = diagnose([str(tmp_path)])
    assert "spec" not in report


# ---------------------------------------------------------------------------
# Serving-model checker: the rollback invariant
# ---------------------------------------------------------------------------


def test_serving_model_spec_ops_clean():
    from triton_distributed_tpu.analysis import serving_model as SM

    assert SM.check_serving_model() == []


def test_serving_model_catches_missing_rollback():
    from triton_distributed_tpu.analysis import serving_model as SM
    from triton_distributed_tpu.analysis.model import FindingKind

    class NoRollback(SM.ServingHarness):
        def _rollback(self, slot, keep_positions):
            pass

    findings = SM.check_serving_model(harness_factory=NoRollback)
    assert findings
    assert {f.kind for f in findings} == {FindingKind.SPEC_ROLLBACK}
    assert "rollback" in findings[0].message
