"""Disaggregated serving cluster (`serving/cluster/`): router +
replica + prefill-worker correctness on CPU.

The load-bearing assertions:

- **Token parity.**  A seeded multi-request trace served through
  router + N replicas (with and without dedicated prefill workers,
  slots and paged layouts, greedy and sampled) is token-for-token
  identical to the single-engine scheduler — routing, shipping and
  failure handling may change WHERE work runs, never a token.
- **Degradation.**  Signal-aware placement with absent or stale
  replica signals routes bit-identically to round-robin.
- **Chaos.**  Kill one replica and straggle another mid-trace on the
  virtual clock: every request finishes token-for-token exact on the
  survivors, and the doctor's report names the failed replicas.
"""

import json
import os
import subprocess
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.serving import (
    ClusterConfig,
    ContinuousBatchingScheduler,
    Request,
    SchedulerConfig,
    ServingCluster,
    ToyConfig,
    ToyModel,
)
from triton_distributed_tpu.serving.cluster import (
    KVShipment,
    RouterConfig,
    VirtualTransport,
    advance_request_key,
    role_from_env,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_decision_state():
    """Routing records a DecisionEvent per request into the
    process-global recent ring AND the flight recorder's bounded
    ring; left behind, a cluster test module's worth of decisions
    fills the flight ring to capacity and breaks later test files
    that assert on its length (test_observability's emit test)."""
    from triton_distributed_tpu.observability import feedback
    from triton_distributed_tpu.observability.lineage import (
        get_lineage_recorder)
    from triton_distributed_tpu.observability.recorder import (
        get_flight_recorder)
    feedback.clear_recent_decisions()
    yield
    feedback.clear_recent_decisions()
    get_flight_recorder().clear()
    get_lineage_recorder().clear()


@pytest.fixture(scope="module")
def toy():
    model = ToyModel(ToyConfig(vocab_size=61, hidden=16,
                               max_seq_len=64))
    params = model.init_params(jax.random.key(0))
    return model, params


@pytest.fixture(scope="module")
def toy_q():
    model = ToyModel(ToyConfig(vocab_size=61, hidden=16,
                               max_seq_len=64, quantize_kv_cache=True))
    params = model.init_params(jax.random.key(0))
    return model, params


def _trace(n=8):
    """Deterministic request trace: varied prompts, budgets, seeds."""
    gens = [6, 9, 7, 11, 6, 8, 10, 7, 9, 6, 8, 7][:n]
    return [dict(prompt=[1 + i, 2 + (i % 3), 3, 4, 5 + (i % 2)],
                 max_new_tokens=g, seed=100 + i,
                 arrival_time=0.002 * (i % 4))
            for i, g in enumerate(gens)]


def _reference(toy, sched_cfg, trace):
    model, params = toy
    class Clock:
        t = 0.0
    c = Clock()
    sched = ContinuousBatchingScheduler(
        model, params, sched_cfg, clock=lambda: c.t,
        clock_advance=lambda dt: setattr(c, "t", c.t + dt))
    done = sched.run([Request(**t) for t in trace])
    assert all(r.state.value == "finished" for r in done)
    return [r.generated for r in
            sorted(done, key=lambda r: r.request_id)]


def _cluster_tokens(cluster, trace):
    recs = [cluster.submit(**t) for t in trace]
    done = cluster.drain()
    assert len(done) == len(trace), [r.state for r in recs]
    return [r.tokens for r in sorted(done,
                                     key=lambda r: r.record_id)]


# ---------------------------------------------------------------------------
# Units: resume-key arithmetic and the shipment wire format
# ---------------------------------------------------------------------------

class TestUnits:
    def test_advance_request_key_matches_masked_step_chain(self):
        # The masked step advances an active row's key once per
        # executed step via _split_rows; the failover resume key must
        # be the same chain, recomputed host-side from the count.
        from triton_distributed_tpu.serving.engine_batched import (
            _split_rows, request_key)
        keys = jnp.asarray(request_key(7))[None, :]
        for g in range(5):
            np.testing.assert_array_equal(
                np.asarray(keys[0]), advance_request_key(7, g))
            keys, _ = _split_rows(keys)

    @pytest.mark.parametrize("fixture", ["toy", "toy_q"])
    def test_shipment_round_trips_bytes_exactly(self, fixture,
                                                request):
        model, params = request.getfixturevalue(fixture)
        prefill = jax.jit(model.make_prefill_fn())
        ids = jnp.asarray([[5, 6, 7, 0]], jnp.int32)
        _, row = prefill(params, ids, model.create_cache(1, max_seq=4))
        ship = KVShipment.from_row_cache(row, 3)
        back = KVShipment.from_bytes(ship.to_bytes())
        assert back.prompt_len == 3 and back.bucket == 4
        assert back.quantized == row.quantized
        rebuilt = back.to_row_cache()
        for a, b in zip(row.ks, rebuilt.ks):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b))
        for a, b in zip(row.vs, rebuilt.vs):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b))
        if row.quantized:
            for a, b in zip(row.kss, rebuilt.kss):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))

    def test_take_finished_hands_over_and_clears(self, toy):
        """A step()-driven server consumes completions through
        take_finished(); retention is the caller's choice, not a
        process-lifetime leak."""
        model, params = toy
        sc = SchedulerConfig(num_slots=2, prefill_buckets=(8, 16))
        cluster = ServingCluster(
            model, params, ClusterConfig(n_replicas=1, scheduler=sc))
        recs = [cluster.submit([1 + i, 2, 3], 2, seed=i,
                               arrival_time=0.0) for i in range(3)]
        while cluster.has_work():
            cluster.step()
        got = cluster.take_finished()
        assert sorted(r.record_id for r in got) == sorted(
            r.record_id for r in recs)
        assert cluster.finished == [] and cluster.take_finished() == []

    def test_transport_ships_as_bytes_and_models_wire_time(self, toy):
        model, params = toy
        prefill = jax.jit(model.make_prefill_fn())
        _, row = prefill(params, jnp.asarray([[5, 6, 7, 0]], jnp.int32),
                         model.create_cache(1, max_seq=4))
        tr = VirtualTransport(wire_gbps=1e-3)   # 1 MB/s: visible time
        token, nbytes = tr.ship(KVShipment.from_row_cache(row, 3))
        assert nbytes > 0 and tr.shipped_bytes == nbytes
        assert tr.ship_time_s(nbytes) == pytest.approx(nbytes / 1e6)
        ship = tr.claim(token)
        assert ship.prompt_len == 3
        assert tr.pending == []


# ---------------------------------------------------------------------------
# Token parity: cluster == single engine
# ---------------------------------------------------------------------------

class TestParity:
    @pytest.mark.parametrize(
        "layout,temperature,workers",
        [("slots", 0.0, 0), ("slots", 0.8, 1),
         ("paged", 0.0, 1), ("paged", 0.8, 0)])
    def test_cluster_matches_single_engine(self, toy, layout,
                                           temperature, workers):
        model, params = toy
        sc = SchedulerConfig(num_slots=3, prefill_buckets=(8, 16, 32),
                             kv_layout=layout, page_size=16,
                             temperature=temperature, top_k=8)
        trace = _trace()
        ref = _reference(toy, sc, trace)
        cluster = ServingCluster(
            model, params,
            ClusterConfig(n_replicas=2, n_prefill_workers=workers,
                          scheduler=sc))
        assert _cluster_tokens(cluster, trace) == ref
        if workers:
            assert cluster.transport.shipments == len(trace)

    def test_shipped_admission_counts_and_skips_local_prefill(
            self, toy):
        from triton_distributed_tpu.observability import get_registry
        model, params = toy
        get_registry().clear()
        sc = SchedulerConfig(num_slots=2, prefill_buckets=(8, 16))
        cluster = ServingCluster(
            model, params,
            ClusterConfig(n_replicas=1, n_prefill_workers=1,
                          scheduler=sc))
        for i in range(3):
            cluster.submit([1 + i, 2, 3], 3, seed=i, arrival_time=0.0)
        cluster.drain()
        snap = get_registry().snapshot()
        assert snap["counters"][
            "serving_shipped_inserts_total"] == 3
        # No local prefill ran on the decode replica — neither the
        # latency histogram nor the prefill counter moved (shipped
        # admissions have their own counter above).
        assert "serving_prefill_ms" not in snap["histograms"]
        assert not any(k.startswith("serving_prefills_total")
                       for k in snap["counters"])

    def test_oversized_prompt_rejects_cleanly_through_worker_path(
            self, toy):
        """The worker dispatch path must apply the same structural
        validation scheduler.submit() does — an unbucketable prompt
        is a clean reject, not an assert inside the prefill worker
        that strands every other in-flight request."""
        model, params = toy
        sc = SchedulerConfig(num_slots=2, prefill_buckets=(8, 16, 32))
        cluster = ServingCluster(
            model, params,
            ClusterConfig(n_replicas=1, n_prefill_workers=1,
                          scheduler=sc))
        ok = cluster.submit([1, 2, 3], 3, seed=0, arrival_time=0.0)
        bad = cluster.submit(list(range(1, 41)), 2, seed=1,
                             arrival_time=0.0)
        done = cluster.drain()
        assert len(done) == 1 and done[0] is ok
        assert ok.state == "finished"
        assert bad.state == "rejected"
        assert bad.reject_reason == "prompt_too_long"


# ---------------------------------------------------------------------------
# Routing: signal-aware scoring + round-robin degradation
# ---------------------------------------------------------------------------

class TestRouting:
    def _assignments(self, toy, mode, signals_fn=None, n=10):
        model, params = toy
        sc = SchedulerConfig(num_slots=2, prefill_buckets=(8, 16))
        cluster = ServingCluster(
            model, params,
            ClusterConfig(n_replicas=3, scheduler=sc,
                          router=RouterConfig(mode=mode)))
        if signals_fn is not None:
            cluster.router._signals_fn = signals_fn
        trace = [dict(prompt=[1 + i, 2, 3], max_new_tokens=3,
                      seed=i, arrival_time=0.001 * i)
                 for i in range(n)]
        recs = [cluster.submit(**t) for t in trace]
        tokens = [r.tokens for r in
                  sorted(cluster.drain(),
                         key=lambda r: r.record_id)]
        return [r.replica_history[0] for r in recs], tokens

    def test_absent_signals_degrade_bit_identically_to_round_robin(
            self, toy):
        rr, rr_tok = self._assignments(toy, "round_robin")
        degraded, deg_tok = self._assignments(
            toy, "signal_aware", signals_fn=lambda rep, now: None)
        assert degraded == rr
        assert deg_tok == rr_tok

    def test_stale_signals_degrade_bit_identically_to_round_robin(
            self, toy):
        rr, _ = self._assignments(toy, "round_robin")
        def stale(rep, now):
            s = rep.signals(now)
            s["ts"] = now - 1e6
            return s
        degraded, _ = self._assignments(toy, "signal_aware",
                                        signals_fn=stale)
        assert degraded == rr

    def test_signal_aware_avoids_link_contended_replica(self, toy):
        model, params = toy
        sc = SchedulerConfig(num_slots=4, prefill_buckets=(8, 16))
        cluster = ServingCluster(
            model, params,
            ClusterConfig(n_replicas=2, scheduler=sc))
        # Replica 0's links are saturated (the PR-8 follow-up: link
        # signals fold into placement) — everything routes to 1.
        cluster.replicas[0].link_busy = 0.85
        for i in range(4):
            cluster.submit([1 + i, 2, 3], 2, seed=i, arrival_time=0.0)
        recs = cluster.drain()
        assert all(r.replica_history == [1] for r in recs)

    def test_prefix_affinity_follows_home_replica(self, toy):
        model, params = toy
        sc = SchedulerConfig(num_slots=4, prefill_buckets=(8, 16, 32),
                             kv_layout="paged", page_size=16)
        cluster = ServingCluster(
            model, params,
            ClusterConfig(n_replicas=2, scheduler=sc))
        sysp = list(np.random.default_rng(3).integers(1, 61, 16))
        # Spaced arrivals: each request finishes before the next one
        # lands, so load never forces an affinity spill — every
        # same-prefix request must follow its home replica even when
        # the round-robin tie-break points elsewhere.
        recs = [cluster.submit(sysp + [1 + i], 2, seed=i,
                               arrival_time=0.05 * i)
                for i in range(4)]
        cluster.drain()
        homes = {r.replica_history[0] for r in recs}
        assert len(homes) == 1, (
            f"shared-prefix requests spread over {homes}")
        # ... and the affinity paid off: the home replica's radix
        # cache served the shared prefix for requests 2..4.
        home = cluster.replicas[homes.pop()]
        assert home.scheduler.slots.radix.hit_tokens == 3 * 16

    def test_prefix_affinity_yields_to_load(self, toy):
        """Dense same-prefix arrivals spill past the affinity slack —
        one hot system prompt must not melt one replica."""
        model, params = toy
        sc = SchedulerConfig(num_slots=4, prefill_buckets=(8, 16, 32))
        cluster = ServingCluster(
            model, params,
            ClusterConfig(n_replicas=2, scheduler=sc))
        sysp = list(np.random.default_rng(3).integers(1, 61, 16))
        recs = [cluster.submit(sysp + [1 + i], 6, seed=i,
                               arrival_time=0.0005 * i)
                for i in range(6)]
        cluster.drain()
        assert len({r.replica_history[0] for r in recs}) == 2

    def test_routing_decisions_are_recorded_schema_valid(self, toy):
        from triton_distributed_tpu.observability import feedback
        model, params = toy
        feedback.clear_recent_decisions()
        sc = SchedulerConfig(num_slots=2, prefill_buckets=(8, 16))
        cluster = ServingCluster(
            model, params,
            ClusterConfig(n_replicas=2, scheduler=sc))
        for i in range(3):
            cluster.submit([1 + i, 2, 3], 2, seed=i, arrival_time=0.0)
        cluster.drain()
        routes = [e for e in feedback.recent_decisions()
                  if e.consumer == "cluster.router"]
        assert len(routes) == 3
        for e in routes:
            assert not feedback.validate_decision(e.to_dict())
            assert e.choice.startswith("replica-")
            assert e.candidates, "signal-aware route must score"

    def test_backpressure_retries_record_one_decision_per_request(
            self, toy):
        """A dispatch refused on backpressure is retried every
        event-loop tick; only the attempt that LANDS may count — a
        blocked head must not inflate routed counters or flood the
        decision ring with phantom placements."""
        from triton_distributed_tpu.observability import feedback
        model, params = toy
        feedback.clear_recent_decisions()
        sc = SchedulerConfig(num_slots=1, max_queue=1,
                             prefill_buckets=(8, 16))
        cluster = ServingCluster(
            model, params,
            ClusterConfig(n_replicas=1, scheduler=sc))
        recs = [cluster.submit([1 + i, 2, 3], 6, seed=i,
                               arrival_time=0.0) for i in range(4)]
        cluster.drain()
        assert all(r.state == "finished" for r in recs)
        routes = [e for e in feedback.recent_decisions()
                  if e.consumer == "cluster.router"]
        assert len(routes) == len(recs)
        assert cluster.replicas[0].routed_total == len(recs)

    def test_worker_backpressure_commits_on_accept_and_ships_once(
            self, toy):
        """Same invariant through the prefill-worker path: a shipment
        refused on decode-side backpressure is re-routed with the
        already-claimed row (ONE prefill, ONE wire crossing per
        request — never back through the worker), and the route only
        commits when a replica actually accepts, so decisions and
        routed counts still reflect landed placements only."""
        from triton_distributed_tpu.observability import feedback
        model, params = toy
        feedback.clear_recent_decisions()
        sc = SchedulerConfig(num_slots=1, max_queue=1,
                             prefill_buckets=(8, 16))
        cluster = ServingCluster(
            model, params,
            ClusterConfig(n_replicas=1, n_prefill_workers=1,
                          scheduler=sc))
        recs = [cluster.submit([1 + i, 2, 3], 6, seed=i,
                               arrival_time=0.0) for i in range(4)]
        cluster.drain()
        assert all(r.state == "finished" for r in recs)
        assert cluster.workers[0].jobs_done == len(recs)
        assert cluster.transport.shipments == len(recs)
        routes = [e for e in feedback.recent_decisions()
                  if e.consumer == "cluster.router"]
        assert len(routes) == len(recs)
        assert cluster.replicas[0].routed_total == len(recs)


# ---------------------------------------------------------------------------
# Chaos: kill + straggle mid-trace, exact resume, doctor attribution
# ---------------------------------------------------------------------------

class TestChaos:
    @pytest.mark.parametrize("temperature", [0.0, 0.8])
    def test_kill_and_straggle_mid_trace_exact_resume(
            self, toy, temperature, tmp_path):
        model, params = toy
        sc = SchedulerConfig(num_slots=3, prefill_buckets=(8, 16, 32),
                             temperature=temperature, top_k=8)
        trace = _trace(10)
        ref = _reference(toy, sc, trace)
        cluster = ServingCluster(
            model, params,
            ClusterConfig(n_replicas=3, scheduler=sc,
                          router=RouterConfig(dead_after_s=0.01,
                                              straggle_ratio=4.0),
                          artifact_dir=str(tmp_path)))
        recs = [cluster.submit(**t) for t in trace]
        for _ in range(6):
            cluster.step()      # mid-trace: tokens already streamed
        cluster.kill_replica(1)
        cluster.straggle_replica(2, 8.0)
        done = cluster.drain()
        assert len(done) == len(trace), [r.state for r in recs]
        assert [r.tokens for r in
                sorted(done, key=lambda r: r.record_id)] == ref
        reasons = {f["reason"] for f in cluster.router.failovers}
        assert reasons == {"heartbeat_loss", "straggler"}
        # Requests really moved: at least one record failed over, and
        # every failed-over record finished on the sole survivor.
        moved = [r for r in recs if r.failovers]
        assert moved
        assert all(r.replica_history[-1] == 0 for r in moved)

        # The doctor ingests the router artifact and NAMES the dead
        # replica in its verdict — from router-state.json ALONE (a
        # virtual-clock cluster run writes no heartbeat/trace files).
        from triton_distributed_tpu.observability.doctor import (
            diagnose, render_markdown)
        report = diagnose([str(tmp_path)])
        assert "replica-1" in report["verdict"]
        assert "heartbeat_loss" in report["verdict"]
        assert set(report["cluster"]["failed_replicas"]) == {
            "replica-1", "replica-2"}
        md = render_markdown(report)
        assert "## Cluster" in md and "DEAD" in md

    def test_failover_decision_and_metrics_recorded(self, toy):
        from triton_distributed_tpu.observability import (
            feedback, get_registry)
        model, params = toy
        get_registry().clear()
        feedback.clear_recent_decisions()
        sc = SchedulerConfig(num_slots=2, prefill_buckets=(8, 16))
        cluster = ServingCluster(
            model, params,
            ClusterConfig(n_replicas=2, scheduler=sc,
                          router=RouterConfig(dead_after_s=0.01)))
        for i in range(4):
            cluster.submit([1 + i, 2, 3], 4, seed=i, arrival_time=0.0)
        for _ in range(2):
            cluster.step()
        cluster.kill_replica(0)
        cluster.drain()
        snap = get_registry().snapshot()
        assert snap["counters"][
            'cluster_failovers_total{reason="heartbeat_loss"}'] == 1
        drains = [e for e in feedback.recent_decisions()
                  if e.consumer == "cluster.failover"]
        assert len(drains) == 1 and drains[0].choice == "drain"
        assert drains[0].inputs["reason"] == "heartbeat_loss"

    @pytest.mark.parametrize("temperature", [0.0, 0.8])
    def test_double_failover_exact_resume(self, toy, temperature):
        """Kill the victim's replica, let it resume on a second
        replica, kill that one too: `advance_request_key` compounds
        across two re-queues (split^n from the total mirrored count,
        not from the last resume point), so the sampled stream must
        STILL match the single engine token-for-token."""
        model, params = toy
        sc = SchedulerConfig(num_slots=2, prefill_buckets=(8, 16, 32),
                             temperature=temperature, top_k=8)
        trace = [dict(prompt=[1 + i, 2, 3, 4], max_new_tokens=12,
                      seed=200 + i, arrival_time=0.001 * i)
                 for i in range(5)]
        ref = _reference(toy, sc, trace)
        cluster = ServingCluster(
            model, params,
            ClusterConfig(n_replicas=3, scheduler=sc,
                          router=RouterConfig(dead_after_s=0.01,
                                              dead_checks=2,
                                              readmit=False)))
        recs = [cluster.submit(**t) for t in trace]
        # Let streams start, then kill the replica serving record 0.
        while not recs[0].tokens:
            cluster.step()
        first = recs[0].replica
        cluster.kill_replica(first)
        # Wait for the drain + re-placement to produce MORE tokens on
        # a second replica, then kill that one too.
        n0 = len(recs[0].tokens)
        while not (recs[0].state == "running"
                   and recs[0].replica not in (None, first)
                   and len(recs[0].tokens) > n0):
            assert not recs[0].done, "victim finished too early"
            cluster.step()
        second = recs[0].replica
        assert second != first
        cluster.kill_replica(second)
        done = cluster.drain()
        assert len(done) == len(trace), [r.state for r in recs]
        assert recs[0].failovers == 2
        assert len(recs[0].replica_history) >= 3
        assert [r.tokens for r in
                sorted(done, key=lambda r: r.record_id)] == ref

    def test_shipment_to_failed_replica_is_rerouted(self, toy):
        """A KV shipment on the wire to a replica that dies before
        delivery must not strand its request."""
        model, params = toy
        sc = SchedulerConfig(num_slots=2, prefill_buckets=(8, 16))
        cluster = ServingCluster(
            model, params,
            ClusterConfig(n_replicas=2, n_prefill_workers=1,
                          scheduler=sc, wire_gbps=1e-4,
                          router=RouterConfig(dead_after_s=0.001)))
        rec = cluster.submit([1, 2, 3], 2, seed=5, arrival_time=0.0)
        cluster.step()          # routed; shipment now on the slow wire
        cluster.kill_replica(rec.replica_history[0])
        done = cluster.drain()
        assert len(done) == 1 and done[0].state == "finished"
        assert rec.failovers == 1
        assert rec.replica_history[-1] != rec.replica_history[0]
        assert len(rec.tokens) == 2


# ---------------------------------------------------------------------------
# Backpressure: QUEUE_FULL is transient — defer, never truncate/reject
# ---------------------------------------------------------------------------

class TestBackpressure:
    def test_queue_full_defers_instead_of_rejecting(self, toy):
        """A replica's bounded submit queue refusing a request is
        backpressure, not a verdict: the record must stay queued and
        re-route when capacity frees.  Tokens are a function of
        (prompt, seed) only, so the streams still match an
        uncontended reference."""
        from triton_distributed_tpu.observability import get_registry
        model, params = toy
        get_registry().clear()
        trace = _trace(6)
        ref = _reference(toy, SchedulerConfig(
            num_slots=3, prefill_buckets=(8, 16, 32)), trace)
        sc = SchedulerConfig(num_slots=1, max_queue=1,
                             prefill_buckets=(8, 16, 32))
        cluster = ServingCluster(
            model, params,
            ClusterConfig(n_replicas=2, scheduler=sc))
        recs = [cluster.submit(**t) for t in trace]
        done = cluster.drain()
        snap = get_registry().snapshot()
        assert snap["counters"].get(
            'serving_requests_rejected_total{reason="queue_full"}',
            0) > 0, "trace never hit the queue bound"
        assert len(done) == len(trace), [r.state for r in recs]
        assert all(r.reject_reason is None for r in recs)
        assert [r.tokens for r in
                sorted(done, key=lambda r: r.record_id)] == ref

    def test_failover_requeue_survives_backpressure(self, toy):
        """Drained victims re-queued onto a survivor whose queue is
        full must wait for capacity — and still resume exactly, not
        finish truncated."""
        model, params = toy
        trace = _trace(6)
        ref = _reference(toy, SchedulerConfig(
            num_slots=3, prefill_buckets=(8, 16, 32)), trace)
        sc = SchedulerConfig(num_slots=1, max_queue=1,
                             prefill_buckets=(8, 16, 32))
        cluster = ServingCluster(
            model, params,
            ClusterConfig(n_replicas=2, scheduler=sc,
                          router=RouterConfig(dead_after_s=0.01)))
        recs = [cluster.submit(**t) for t in trace]
        for _ in range(8):
            cluster.step()
        cluster.kill_replica(0)
        done = cluster.drain()
        assert len(done) == len(trace), [r.state for r in recs]
        assert any(r.failovers for r in recs)
        assert [r.tokens for r in
                sorted(done, key=lambda r: r.record_id)] == ref


# ---------------------------------------------------------------------------
# Satellites: launch --roles, /routing endpoint, observe_runtime
# ---------------------------------------------------------------------------

class TestRolePlumbing:
    def test_launch_roles_assigns_rank_ranges(self, tmp_path):
        worker = tmp_path / "w.py"
        # One os.write per worker: 4 processes share the captured
        # pipe, and only a single short write is atomic — print()'s
        # per-argument writes interleave mid-line across workers.
        worker.write_text(
            "import os\n"
            "line = ' '.join(['ROLE', os.environ['TDT_PROCESS_ID'],"
            " os.environ['TDT_ROLE'], os.environ['TDT_ROLE_INDEX'],"
            " os.environ['TDT_CLUSTER_SPEC']])\n"
            "os.write(1, (line + '\\n').encode())\n")
        res = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts/launch.py"),
             "--roles", "router:1,prefill:1,replica:2", str(worker)],
            capture_output=True, text=True, timeout=60)
        assert res.returncode == 0, res.stderr
        lines = sorted(ln.split()[1:] for ln in
                       res.stdout.splitlines() if ln.startswith("ROLE"))
        spec = "router:1,prefill:1,replica:2"
        assert lines == [
            ["0", "router", "0", spec],
            ["1", "prefill", "0", spec],
            ["2", "replica", "0", spec],
            ["3", "replica", "1", spec]], res.stdout

    def test_launch_roles_total_mismatch_fails(self, tmp_path):
        worker = tmp_path / "w.py"
        worker.write_text("print('never')\n")
        res = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts/launch.py"),
             "--nproc", "3", "--roles", "router:1,replica:1",
             str(worker)],
            capture_output=True, text=True, timeout=60)
        assert res.returncode == 2
        assert "totals 2" in res.stderr

    def test_role_from_env_round_trip(self, monkeypatch):
        monkeypatch.setenv("TDT_ROLE", "replica")
        monkeypatch.setenv("TDT_ROLE_INDEX", "1")
        monkeypatch.setenv("TDT_CLUSTER_SPEC",
                           "router:1,replica:2")
        out = role_from_env()
        assert out == {"role": "replica", "index": 1,
                       "spec": {"router": 1, "replica": 2}}
        monkeypatch.delenv("TDT_ROLE")
        assert role_from_env() is None


class TestRoutingEndpoint:
    def test_routing_endpoint_renders_router_table(self, toy):
        from triton_distributed_tpu.observability.exporter import (
            start_metrics_server)
        model, params = toy
        sc = SchedulerConfig(num_slots=2, prefill_buckets=(8, 16))
        cluster = ServingCluster(
            model, params,
            ClusterConfig(n_replicas=2, scheduler=sc))
        cluster.submit([1, 2, 3], 2, arrival_time=0.0)
        cluster.drain()
        srv = start_metrics_server(port=0)
        try:
            body = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/routing",
                timeout=10).read())
        finally:
            srv.stop()
        router = body["router"]
        assert router["kind"] == "router" and router["schema"] == 1
        assert [r["name"] for r in router["replicas"]] == [
            "replica-0", "replica-1"]
        assert sum(r["routed"] for r in router["replicas"]) == 1


class TestObserveRuntime:
    def test_serving_decode_loop_warms_tuned_baselines(
            self, toy, tmp_path, monkeypatch):
        """The ISSUE-9 satellite: an armed tuner's winner baseline
        fills from serving decode steps — no bench required."""
        monkeypatch.setenv("TDT_ANOMALY_BASELINES",
                           str(tmp_path / "b.json"))
        import triton_distributed_tpu.observability.anomaly as an
        from triton_distributed_tpu import autotuner as at
        an._STORE = None        # fresh store under the new env
        model, params = toy
        tuner = at.ContextualAutotuner(
            lambda x, config=None: x * config, configs=[1, 2],
            iters=1, warmup=0)
        x = jnp.ones((4,))
        tuner(x)
        at.clear_serving_observers()
        tuner.arm_serving(x)
        try:
            class Clock:
                t = 0.0
            c = Clock()
            sched = ContinuousBatchingScheduler(
                model, params,
                SchedulerConfig(num_slots=2, prefill_buckets=(8, 16)),
                clock=lambda: c.t,
                clock_advance=lambda dt: setattr(c, "t", c.t + dt))
            # (the first read carries the prefill: it is the prefill's
            # observation and feeds no step baseline)
            sched.run([Request(prompt=[1, 2, 3], max_new_tokens=7,
                               arrival_time=0.0)])
            cfg = tuner.cache[tuner.key_fn(x)].config
            store = an.get_baseline_store()
            b = store.get(tuner.winner_baseline_key(
                cfg, at.SERVING_SCOPE))
            assert b is not None and b.n >= 6, (
                "decode steps did not feed the winner baseline")
            # ... into the SERVING-scoped key only: whole-step
            # latency must never pollute the bench-fed kernel-only
            # baseline under the bare key.
            assert store.get(tuner.winner_baseline_key(cfg)) is None
            # Re-arming the same (tuner, key) is idempotent.
            n_armed = len(at._SERVING_OBSERVERS)
            tuner.arm_serving(x)
            assert len(at._SERVING_OBSERVERS) == n_armed
        finally:
            at.clear_serving_observers()
            an._STORE = None
