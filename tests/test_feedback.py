"""Closed-loop feedback: SignalBus snapshots, DecisionEvents, and the
three consumers — comm method selection, autotuner invalidation,
SLO-aware admission — plus the decisions.jsonl artifact, the doctor's
Control-decisions section and the exporter/heartbeat plumbing.

The two contracts every test here circles back to:

- **degradation**: with the bus absent, empty, or stale, every
  consumer's choice is BIT-IDENTICAL to the static behavior;
- **explainability**: every live control decision is a schema-v1
  DecisionEvent in the registry, the flight ring, and (when armed)
  the decisions.jsonl artifact the doctor replays.
"""

import json
import os
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from triton_distributed_tpu.autotuner import ContextualAutotuner
from triton_distributed_tpu.kernels.comm_perf_model import (
    choose_ll_or_fused,
    estimate_all_gather_time_us,
    estimate_one_shot_time_us,
    estimate_torus_ag_time_us,
    get_ici_spec,
    one_shot_beats_ring,
    torus_beats_single_axis,
)
from triton_distributed_tpu.observability import feedback
from triton_distributed_tpu.observability.anomaly import (
    SUSTAINED_N,
    WINDOW,
    BaselineStore,
    event_key,
)
from triton_distributed_tpu.observability.events import capture_events
from triton_distributed_tpu.observability.feedback import (
    DecisionEvent,
    Signals,
    effective_spec,
    load_decisions,
    record_decision,
    set_decision_log,
    synthetic_bus,
    validate_decision,
)

#: A deterministic "decode allreduce is hammering axis tp" fixture.
HOT_TP = {"tp:0>1": 0.8, "tp:1>2": 0.8, "tp:2>3": 0.8}


@pytest.fixture(autouse=True)
def _fresh_decisions():
    feedback.clear_recent_decisions()
    set_decision_log(None)
    yield
    feedback.clear_recent_decisions()
    set_decision_log(None)
    # Same ring hygiene as test_cluster: the SLO-admission tests run
    # real schedulers, whose decision AND lineage events land in the
    # process-global flight ring and lineage recorder — left behind
    # they break later modules' ring-length asserts and leak
    # in-flight "lineage" keys into heartbeat-payload tests.
    from triton_distributed_tpu.observability.lineage import (
        get_lineage_recorder)
    from triton_distributed_tpu.observability.recorder import (
        get_flight_recorder)
    get_lineage_recorder().clear()
    get_flight_recorder().clear()


# ---------------------------------------------------------------------------
# Signals / bus semantics
# ---------------------------------------------------------------------------

class TestSignals:
    def test_busy_fraction_axis_scoped(self):
        sig = Signals(ts=0.0, link_utilization={"tp:0>1": 0.5,
                                                "dp:0>1": 0.2})
        assert sig.busy_fraction("tp") == 0.5
        assert sig.busy_fraction("dp") == 0.2
        assert sig.busy_fraction() == 0.5          # overall worst
        assert sig.busy_fraction("ep") == 0.0

    def test_contended_floor_and_cap(self):
        sig = Signals(ts=0.0, contended_links=("tp:0>1",))
        assert sig.busy_fraction("tp") == feedback.CONTENDED_FLOOR
        sig2 = Signals(ts=0.0, link_utilization={"tp:0>1": 5.0})
        assert sig2.busy_fraction("tp") == feedback.UTILIZATION_CAP

    def test_mean_vs_worst(self):
        sig = Signals(ts=0.0, link_utilization={"x:0>1": 0.8})
        assert sig.busy_fraction("x") == 0.8
        assert sig.mean_busy_fraction(["x", "y"]) == pytest.approx(0.4)

    def test_staleness_bound(self):
        sig = Signals(ts=100.0)
        assert sig.fresh(now=100.0 + feedback.STALENESS_S)
        assert not sig.fresh(now=101.0 + feedback.STALENESS_S)

    def test_effective_spec_identity_when_idle(self):
        spec = get_ici_spec()
        assert effective_spec(spec, 0.0) is spec   # not a rebuilt copy
        derated = effective_spec(spec, 0.5)
        assert derated.link_gbps == pytest.approx(spec.link_gbps / 2)

    def test_bus_reads_live_link_tracker(self):
        from triton_distributed_tpu.observability.links import (
            LinkTracker)
        from triton_distributed_tpu.observability.metrics import (
            MetricsRegistry)
        tracker = LinkTracker(registry=MetricsRegistry())

        class Ev:
            op = "all_reduce"
            method = "one_shot"
            world = 4
            axis = "tp"
            rank = 0
            bytes_moved = 1 << 26
            ts = 1000.0
            measured_us = 500.0
            estimate_us = None
            extra = {"hops": "ring"}
        tracker.attribute(Ev())
        bus = feedback.SignalBus(tracker=tracker,
                                 clock=lambda: 1000.5)
        sig = bus.read()
        assert sig.link_utilization.get("tp:0>1", 0) > 0
        assert sig.busy_fraction("tp") > 0


# ---------------------------------------------------------------------------
# DecisionEvent recording
# ---------------------------------------------------------------------------

class TestDecisionRecord:
    def _event(self, **kw):
        base = dict(consumer="comm.method_select", op="all_gather",
                    choice="ring",
                    candidates=[{"name": "ring", "score_us": 1.0},
                                {"name": "one_shot",
                                 "score_us": 2.0}],
                    inputs={"axis_busy": {"tp": 0.8}})
        base.update(kw)
        return DecisionEvent(**base)

    def test_registry_ring_and_schema(self):
        from triton_distributed_tpu.observability.metrics import (
            get_registry)
        reg = get_registry()
        before = reg.peek("decisions_total",
                          consumer="comm.method_select",
                          choice="ring") or 0
        with capture_events() as evs:
            ev = record_decision(self._event())
        assert ev is not None and ev.ts > 0
        assert reg.peek("decisions_total",
                        consumer="comm.method_select",
                        choice="ring") == before + 1
        ring = [e for e in evs if e.kind == "decision"]
        assert ring and ring[0].extra["decision"]["choice"] == "ring"
        assert validate_decision(ev.to_dict()) == []
        assert feedback.recent_decisions()[-1] is ev

    def test_jsonl_roundtrip_and_validation(self, tmp_path):
        path = str(tmp_path / "decisions-rank-0.jsonl")
        set_decision_log(path)
        record_decision(self._event())
        record_decision(self._event(consumer="serving.admission",
                                    op="request:1", choice="defer",
                                    fallback=None))
        set_decision_log(None)
        rows = load_decisions(path)
        assert len(rows) == 2
        for row in rows:
            assert validate_decision(row) == []
        # torn tail line must be skipped, not crash the loader
        with open(path, "a") as f:
            f.write('{"consumer": "torn...')
        assert len(load_decisions(path)) == 2

    def test_observability_off_records_nothing(self, monkeypatch,
                                               tmp_path):
        monkeypatch.setenv("TDT_OBSERVABILITY", "0")
        path = str(tmp_path / "d.jsonl")
        set_decision_log(path)
        assert record_decision(self._event()) is None
        assert not os.path.exists(path)
        assert not feedback.closed_loop_enabled()

    def test_validate_catches_schema_drift(self):
        good = self._event().to_dict()
        assert validate_decision(good) == []
        bad = dict(good)
        bad.pop("inputs")
        bad["schema"] = 99
        bad["candidates"] = [{"score_us": 1.0}]
        problems = validate_decision(bad)
        assert len(problems) >= 3


# ---------------------------------------------------------------------------
# Consumer (a): comm method selection
# ---------------------------------------------------------------------------

#: (nbytes, world) grid wide enough to cross every static crossover.
GRID = [(1 << e, w) for w in (2, 4, 8, 16) for e in range(8, 25, 2)]


class TestMethodSelectionStatic:
    def test_bus_absent_empty_stale_bit_identical(self):
        empty = synthetic_bus()
        stale = synthetic_bus(link_utilization=dict(HOT_TP),
                              ts=0.0, clock=lambda: 1e6)
        for nb, w in GRID:
            want = one_shot_beats_ring(nb, w)
            assert want == one_shot_beats_ring(
                nb, w, axis="tp", bus=empty)
            assert want == one_shot_beats_ring(
                nb, w, axis="tp", bus=stale)
        for nb, _ in GRID:
            want = torus_beats_single_axis(nb, (4, 4))
            assert want == torus_beats_single_axis(
                nb, (4, 4), axes=("x", "y"), bus=empty)
            assert want == torus_beats_single_axis(
                nb, (4, 4), axes=("x", "y"), bus=stale)
        for nb in (1 << 12, 1 << 16, 1 << 20):
            want = choose_ll_or_fused(nb, 128, 2048, 1024, 4,
                                      jnp.bfloat16)
            assert want == choose_ll_or_fused(
                nb, 128, 2048, 1024, 4, jnp.bfloat16, axis="tp",
                bus=empty)
            assert want == choose_ll_or_fused(
                nb, 128, 2048, 1024, 4, jnp.bfloat16, axis="tp",
                bus=stale)

    def test_ambient_off_no_decision_events(self):
        # Without TDT_CLOSED_LOOP the static path must not even emit
        # decision events — existing event streams stay untouched.
        with capture_events() as evs:
            one_shot_beats_ring(1 << 20, 4)
            torus_beats_single_axis(1 << 16, (4, 4))
        assert not [e for e in evs if e.kind == "decision"]

    def test_context_resolve_static_parity(self):
        from triton_distributed_tpu.kernels.allgather import (
            AllGatherContext, AllGatherMethod)
        ctx = AllGatherContext(axis="tp", world_size=8)
        empty = synthetic_bus()
        for nb, _ in GRID:
            assert (ctx.resolve_method(nb)
                    == ctx.resolve_method(nb, bus=empty))
        assert ctx.resolve_method(1 << 8) in (
            AllGatherMethod.PUSH_ALL, AllGatherMethod.RING)


class TestMethodSelectionClosedLoop:
    def test_seeded_contention_flips_and_wins(self):
        """The ISSUE's scenario: a decode allreduce hammers axis x;
        closed-loop torus selection flips to the lane schedule that
        spreads over y — and under the contended ground-truth cost
        model the flipped choice is strictly faster."""
        bus = synthetic_bus(link_utilization={"x:0>1": 0.85,
                                              "x:1>2": 0.85})
        spec = get_ici_spec()
        sig = bus.read()
        flips = 0
        for e in range(8, 24):
            nb = 1 << e
            static = torus_beats_single_axis(nb, (4, 4))
            closed = torus_beats_single_axis(
                nb, (4, 4), axes=("x", "y"), bus=bus)
            # Ground truth: the contended scenario's cost of each
            # candidate (torus sees the mean load, the single-axis
            # schedule the worst).
            truth = {
                True: estimate_torus_ag_time_us(
                    nb, (4, 4), effective_spec(
                        spec, sig.mean_busy_fraction(["x", "y"]))),
                False: min(
                    estimate_all_gather_time_us(
                        nb, 16, effective_spec(
                            spec, sig.busy_fraction("x"))),
                    estimate_one_shot_time_us(
                        nb, 16, effective_spec(
                            spec, sig.busy_fraction("x")))),
            }
            assert truth[closed] <= truth[static]
            if closed != static:
                flips += 1
                assert truth[closed] < truth[static]
        assert flips > 0, "contention never changed a choice"

    def test_one_shot_yields_to_ring_under_contention(self):
        bus = synthetic_bus(link_utilization=dict(HOT_TP))
        flips = [(nb, w) for nb, w in GRID
                 if one_shot_beats_ring(nb, w)
                 and not one_shot_beats_ring(nb, w, axis="tp",
                                             bus=bus)]
        assert flips, "contention never shifted the crossover"
        # and never the other direction: contention cannot make the
        # bandwidth-heavy one-shot MORE attractive
        assert not [(nb, w) for nb, w in GRID
                    if not one_shot_beats_ring(nb, w)
                    and one_shot_beats_ring(nb, w, axis="tp",
                                            bus=bus)]

    def test_decision_event_explains_the_pick(self):
        bus = synthetic_bus(link_utilization=dict(HOT_TP),
                            contended=("tp:0>1",))
        with capture_events() as evs:
            one_shot_beats_ring(1 << 20, 8, axis="tp", bus=bus,
                                op="all_gather")
        dec = [e.extra["decision"] for e in evs
               if e.kind == "decision"]
        assert len(dec) == 1
        d = dec[0]
        assert d["consumer"] == "comm.method_select"
        assert d["op"] == "all_gather"
        assert d["fallback"] is None
        names = {c["name"] for c in d["candidates"]}
        assert names == {"one_shot", "ring"}
        assert all("score_us" in c for c in d["candidates"])
        assert d["inputs"]["axis_busy"]["tp"] == pytest.approx(0.8)
        assert "tp:0>1" in d["inputs"]["contended_links"]

    def test_explicit_empty_bus_records_truthful_fallback(self):
        with capture_events() as evs:
            one_shot_beats_ring(1 << 20, 8, axis="tp",
                                bus=synthetic_bus())
        d = [e.extra["decision"] for e in evs
             if e.kind == "decision"]
        assert d and d[0]["fallback"] == "signals_absent"

    def test_scheduler_context_threads_bus(self):
        from triton_distributed_tpu.kernels.torus import TorusContext
        ctx = TorusContext(axes=("x", "y"), sizes=(4, 4))
        bus = synthetic_bus(link_utilization={"x:0>1": 0.85,
                                              "x:1>2": 0.85})
        diff = [nb for nb, _ in GRID
                if ctx.resolve_method(nb)
                != ctx.resolve_method(nb, bus=bus)]
        assert diff, "TorusContext never consulted the bus"


# ---------------------------------------------------------------------------
# Consumer (b): autotuner invalidation + re-tune
# ---------------------------------------------------------------------------

def _tuned_op(x, *, config):
    return x * config


class TestAutotunerClosedLoop:
    def _tuner(self, tmp_path, store, name="cache.json"):
        t = ContextualAutotuner(_tuned_op, [2, 3], iters=1, warmup=1,
                                cache_path=str(tmp_path / name),
                                log_dir=str(tmp_path / "logs"))
        t.bus = synthetic_bus(store=store)
        return t

    def _poison_winner(self, tuner, store, config):
        key_b = tuner.winner_baseline_key(config)
        for _ in range(WINDOW):
            store.observe(key_b, 100.0)
        for _ in range(SUSTAINED_N):
            store.observe(key_b, 500.0)
        assert store.sustained_z(key_b) >= 3.0

    def test_sustained_z_invalidates_to_second_best(self, tmp_path):
        store = BaselineStore(str(tmp_path / "b.json"))
        tuner = self._tuner(tmp_path, store)
        tuner.retune_inline = False
        x = jnp.ones((4,))
        tuner(x)
        key = tuner.key_fn(x)
        entry = tuner.cache[key]
        winner, second = entry.config, entry.ranking[1][1]
        self._poison_winner(tuner, store, winner)
        # block the background thread so the demotion stays visible
        tuner._retunes_inflight.add(key)
        tuner(x)
        assert tuner.cache[key].config == second
        assert tuner.cache[key].stale is not None
        # persisted beside the disk cache
        disk = json.load(open(tuner.cache_path))
        assert any("stale" in rec for rec in disk.values())
        kinds = [(d.consumer, d.choice)
                 for d in feedback.recent_decisions()]
        assert ("autotune.invalidate", repr(second)) in kinds

    def test_stale_marker_survives_restart(self, tmp_path):
        store = BaselineStore(str(tmp_path / "b.json"))
        tuner = self._tuner(tmp_path, store)
        tuner.retune_inline = False
        x = jnp.ones((4,))
        tuner(x)
        key = tuner.key_fn(x)
        winner = tuner.cache[key].config
        second = tuner.cache[key].ranking[1][1]
        self._poison_winner(tuner, store, winner)
        tuner._retunes_inflight.add(key)
        tuner(x)
        # "restart": a fresh tuner over the same disk cache, with NO
        # anomaly history — the persisted marker alone must demote.
        fresh_store = BaselineStore(str(tmp_path / "empty_b.json"))
        t2 = self._tuner(tmp_path, fresh_store)
        t2._retunes_inflight.add(key)   # keep the demotion observable
        t2(x)
        assert t2.cache[key].config == second
        assert t2.cache[key].stale is not None

    def test_background_retune_heals(self, tmp_path):
        store = BaselineStore(str(tmp_path / "b.json"))
        tuner = self._tuner(tmp_path, store)
        tuner.retune_inline = True       # deterministic for the test
        x = jnp.ones((4,))
        tuner(x)
        key = tuner.key_fn(x)
        self._poison_winner(tuner, store, tuner.cache[key].config)
        tuner(x)
        # inline re-tune already landed: entry fresh, marker cleared
        assert tuner.cache[key].stale is None
        disk = json.load(open(tuner.cache_path))
        assert not any("stale" in rec for rec in disk.values())
        kinds = [d.consumer for d in feedback.recent_decisions()]
        assert "autotune.invalidate" in kinds
        assert "autotune.retune" in kinds

    def test_observability_off_is_static(self, tmp_path,
                                         monkeypatch):
        store = BaselineStore(str(tmp_path / "b.json"))
        tuner = self._tuner(tmp_path, store)
        x = jnp.ones((4,))
        tuner(x)
        key = tuner.key_fn(x)
        winner = tuner.cache[key].config
        self._poison_winner(tuner, store, winner)
        monkeypatch.setenv("TDT_OBSERVABILITY", "0")
        tuner(x)
        # no demotion, no stale marker, no re-tune scheduled
        assert tuner.cache[key].config == winner
        assert tuner.cache[key].stale is None
        assert not tuner._retunes_inflight
        disk = json.load(open(tuner.cache_path))
        assert not any("stale" in rec for rec in disk.values())

    def test_no_bus_is_static(self, tmp_path):
        store = BaselineStore(str(tmp_path / "b.json"))
        tuner = self._tuner(tmp_path, store)
        tuner.bus = None                 # and ambient is unarmed
        x = jnp.ones((4,))
        tuner(x)
        key = tuner.key_fn(x)
        winner = tuner.cache[key].config
        self._poison_winner(tuner, store, winner)
        tuner(x)
        assert tuner.cache[key].config == winner

    def test_healthy_winner_untouched(self, tmp_path):
        store = BaselineStore(str(tmp_path / "b.json"))
        tuner = self._tuner(tmp_path, store)
        x = jnp.ones((4,))
        tuner(x)
        key = tuner.key_fn(x)
        winner = tuner.cache[key].config
        bkey = tuner.winner_baseline_key(winner)
        for _ in range(WINDOW):
            store.observe(bkey, 100.0)
        store.observe(bkey, 500.0)       # ONE outlier is jitter
        tuner(x)
        assert tuner.cache[key].config == winner
        assert tuner.cache[key].stale is None

    def test_observe_runtime_feeds_winner_baseline(self, tmp_path,
                                                   monkeypatch):
        monkeypatch.setenv("TDT_ANOMALY_BASELINES",
                           str(tmp_path / "rt.json"))
        import triton_distributed_tpu.observability.anomaly as an
        monkeypatch.setattr(an, "_STORE", None)
        tuner = self._tuner(tmp_path, None)
        x = jnp.ones((4,))
        tuner(x)
        key = tuner.key_fn(x)
        for _ in range(10):
            tuner.observe_runtime(key, 100.0)
        bkey = tuner.winner_baseline_key(tuner.cache[key].config)
        assert an.get_baseline_store().zscore(bkey, 100.0) is not None


# ---------------------------------------------------------------------------
# Consumer (c): SLO-aware admission
# ---------------------------------------------------------------------------

class TestSloAdmission:
    def _run(self, slo, store, arrivals=(0.0, 0.0, 0.0),
             num_slots=4):
        from triton_distributed_tpu.serving import (
            ContinuousBatchingScheduler, Request, SchedulerConfig,
            ToyConfig, ToyModel)
        model = ToyModel(ToyConfig(vocab_size=61, hidden=16,
                                   max_seq_len=64))
        params = model.init_params(jax.random.key(0))

        class Clock:
            t = 0.0
        clock = Clock()
        bus = (synthetic_bus(store=store, clock=lambda: clock.t,
                             ts=0.0) if store is not None else None)
        sched = ContinuousBatchingScheduler(
            model, params,
            SchedulerConfig(num_slots=num_slots,
                            prefill_buckets=(8, 16),
                            slo_tbt_ms=slo),
            clock=lambda: clock.t,
            clock_advance=lambda dt: setattr(clock, "t",
                                             clock.t + dt),
            bus=bus)
        reqs = [Request(prompt=[1 + i, 2, 3, 4], max_new_tokens=3,
                        arrival_time=t)
                for i, t in enumerate(arrivals)]
        done = sched.run(reqs)
        done = sorted(done, key=lambda r: r.request_id)
        return sched, done

    def _slow_store(self, tmp_path, num_slots=4, step_us=50_000.0):
        store = BaselineStore(str(tmp_path / "slo.json"))
        key = event_key("serving.decode_step", None, (num_slots,), 1)
        for _ in range(WINDOW):
            store.observe(key, step_us)
        return store

    def test_defers_with_truthful_recorded_reason(self, tmp_path):
        store = self._slow_store(tmp_path)
        _, done = self._run(10.0, store)
        # admissions serialized: nobody joins a running batch whose
        # predicted step already blows the 10ms TBT target
        for r in done:
            assert len(r.generated) == 3
        decs = [d for d in feedback.recent_decisions()
                if d.consumer == "serving.admission"]
        defers = [d for d in decs if d.choice == "defer"]
        admits = [d for d in decs if d.choice == "admit"]
        assert len(defers) == 2 and len(admits) == 2
        d = defers[0]
        assert d.inputs["predicted_step_ms"] == pytest.approx(50.0)
        assert d.inputs["slo_tbt_ms"] == 10.0
        assert any(c["name"] == "defer" for c in d.candidates)
        assert all(a.inputs["cleared_by"] == "engine_empty"
                   for a in admits)
        from triton_distributed_tpu.observability.metrics import (
            get_registry)
        assert (get_registry().peek("serving_slo_deferrals_total")
                or 0) >= 2

    def test_no_slo_is_bit_identical(self, tmp_path):
        store = self._slow_store(tmp_path)
        _, base = self._run(None, None)
        _, same = self._run(None, store)   # bus present, slo unset
        assert ([r.generated for r in base]
                == [r.generated for r in same])
        assert ([r.t_admitted for r in base]
                == [r.t_admitted for r in same])
        decs = [d for d in feedback.recent_decisions()
                if d.consumer == "serving.admission"]
        assert not decs

    def test_fast_steps_admit_identically(self, tmp_path):
        # predicted 1ms step under a 10ms target: gate always opens
        store = self._slow_store(tmp_path, step_us=1_000.0)
        _, base = self._run(None, None)
        _, fast = self._run(10.0, store)
        assert ([r.t_admitted for r in base]
                == [r.t_admitted for r in fast])
        assert not [d for d in feedback.recent_decisions()
                    if d.choice == "defer"]

    def test_empty_engine_never_starves(self, tmp_path):
        store = self._slow_store(tmp_path, num_slots=2)
        _, done = self._run(10.0, store, arrivals=(0.0,),
                            num_slots=2)
        assert len(done) == 1 and len(done[0].generated) == 3

    def test_capacity_wait_not_recorded_as_slo_deferral(self,
                                                        tmp_path):
        # num_slots=1: CAPACITY, not the SLO, serializes admissions.
        # The gate runs only after capacity says yes, so a head the
        # engine had no room for must not open a deferral episode
        # (or record a spurious choice="admit" when the prediction
        # dips while slots are still full) — and admission times
        # stay bit-identical to the static scheduler.
        from triton_distributed_tpu.observability.metrics import (
            get_registry)
        store = self._slow_store(tmp_path, num_slots=1)
        _, base = self._run(None, None, num_slots=1)
        before = (get_registry().peek("serving_slo_deferrals_total")
                  or 0)
        _, same = self._run(10.0, store, num_slots=1)
        assert ([r.t_admitted for r in base]
                == [r.t_admitted for r in same])
        assert not [d for d in feedback.recent_decisions()
                    if d.consumer == "serving.admission"]
        assert (get_registry().peek("serving_slo_deferrals_total")
                or 0) == before

    def test_no_baseline_admits_statically(self, tmp_path):
        empty = BaselineStore(str(tmp_path / "none.json"))
        _, base = self._run(None, None)
        _, same = self._run(10.0, empty)
        assert ([r.t_admitted for r in base]
                == [r.t_admitted for r in same])
        assert not [d for d in feedback.recent_decisions()
                    if d.consumer == "serving.admission"]


# ---------------------------------------------------------------------------
# Satellite: baseline-store resilience + sustained z
# ---------------------------------------------------------------------------

class TestStoreResilience:
    def test_truncated_file_warns_and_starts_fresh(self, tmp_path):
        path = str(tmp_path / "b.json")
        store = BaselineStore(path)
        for _ in range(6):
            store.observe("k", 100.0)
        assert store.save() == path
        text = open(path).read()
        with open(path, "w") as f:
            f.write(text[:len(text) // 2])     # torn mid-write
        fresh = BaselineStore(path)
        assert fresh.get("k") is None          # fresh, not a crash
        for _ in range(6):
            fresh.observe("k2", 50.0)
        assert fresh.save() == path            # and saving works
        assert "k2" in json.load(open(path))["baselines"]

    def test_truncated_to_empty_tolerated(self, tmp_path):
        path = str(tmp_path / "b.json")
        open(path, "w").close()
        store = BaselineStore(path)
        assert len(store) == 0
        store.observe("k", 1.0)
        assert store.save() == path

    def test_bad_rows_dropped_good_kept(self, tmp_path):
        path = str(tmp_path / "b.json")
        json.dump({"schema": 1,
                   "baselines": {"good": [6, 100.0, 10.0],
                                 "bad": "not-a-row"}},
                  open(path, "w"))
        store = BaselineStore(path)
        assert store.get("good") is not None
        assert store.get("bad") is None

    def test_atomic_save_leaves_no_tmp(self, tmp_path):
        path = str(tmp_path / "b.json")
        store = BaselineStore(path)
        store.observe("k", 1.0)
        store.save()
        assert os.listdir(str(tmp_path)) == ["b.json"]

    def test_sustained_z_requires_consecutive(self, tmp_path):
        store = BaselineStore(str(tmp_path / "b.json"))
        for _ in range(WINDOW):
            store.observe("k", 100.0)
        store.observe("k", 500.0)
        s = store.sustained_z("k")
        assert s is None or s < 3.0                # one outlier
        store.observe("k", 100.0)
        store.observe("k", 500.0)
        s = store.sustained_z("k")
        assert s is None or s < 3.0                # interleaved calm
        for _ in range(SUSTAINED_N):
            store.observe("k", 600.0)
        assert store.sustained_z("k") >= 3.0       # N in a row


# ---------------------------------------------------------------------------
# Doctor + exporter plumbing
# ---------------------------------------------------------------------------

def _write_heartbeat(d, rank=0, t=1000.0, decisions=None):
    hb = {"schema": 1, "rank": rank, "pid": 1, "unix_time": t,
          "step": 1, "last_span": "serving.request",
          "open_spans": []}
    if decisions is not None:
        hb["decisions"] = decisions
    with open(os.path.join(d, f"heartbeat-rank-{rank}.json"),
              "w") as f:
        json.dump(hb, f)


class TestDoctorDecisions:
    def _decide(self, path):
        set_decision_log(path)
        record_decision(DecisionEvent(
            consumer="serving.admission", op="request:3",
            choice="defer",
            candidates=[{"name": "admit", "score_us": 50000.0},
                        {"name": "defer"}],
            inputs={"predicted_step_ms": 50.0, "slo_tbt_ms": 10.0},
            ts=1000.5))
        record_decision(DecisionEvent(
            consumer="comm.method_select", op="all_gather",
            choice="ring",
            candidates=[{"name": "ring", "score_us": 10.0},
                        {"name": "one_shot", "score_us": 30.0}],
            inputs={"contended_links": ["tp:0>1"]}, ts=1001.0))
        set_decision_log(None)

    def test_section_replayed_from_artifact(self, tmp_path):
        from triton_distributed_tpu.observability.doctor import (
            diagnose, render_markdown)
        d = str(tmp_path)
        _write_heartbeat(d, t=1002.0)
        self._decide(os.path.join(d, "decisions-rank-0.jsonl"))
        report = diagnose([d])
        dec = report["decisions"]
        assert dec["source"] == "artifact" and dec["count"] == 2
        assert dec["by_consumer"] == {"comm.method_select": 1,
                                      "serving.admission": 1}
        rows = {r["op"]: r for r in dec["recent"]}
        assert rows["request:3"]["choice"] == "defer"
        assert "50.0ms" in rows["request:3"]["why"]
        assert "tp:0>1" in rows["all_gather"]["why"]
        md = render_markdown(report)
        assert "## Control decisions" in md
        assert "predicted step 50.0ms vs SLO 10.0ms" in md

    def test_absent_artifact_absent_section(self, tmp_path):
        from triton_distributed_tpu.observability.doctor import (
            diagnose, render_markdown)
        d = str(tmp_path)
        _write_heartbeat(d, t=1002.0)
        report = diagnose([d])
        assert "decisions" not in report
        assert "## Control decisions" not in render_markdown(report)

    def test_heartbeat_summaries_as_fallback_source(self, tmp_path):
        from triton_distributed_tpu.observability.doctor import (
            diagnose)
        d = str(tmp_path)
        _write_heartbeat(d, t=1002.0, decisions=[
            {"ts": 1000.0, "consumer": "autotune.invalidate",
             "op": "kernels.matmul", "choice": "cfg2",
             "fallback": None}])
        report = diagnose([d])
        dec = report["decisions"]
        assert dec["source"] == "heartbeats" and dec["count"] == 1
        assert dec["recent"][0]["consumer"] == "autotune.invalidate"

    def test_golden_corpus_unchanged(self):
        # The committed incident corpus has no decisions artifact:
        # its reports must not grow the key (the goldens themselves
        # are compared in test_doctor.py::test_matches_golden).
        from triton_distributed_tpu.observability.doctor import (
            diagnose)
        base = os.path.join(os.path.dirname(__file__), "data",
                            "incidents")
        for scenario in ("stalled_rank", "clean"):
            report = diagnose([os.path.join(base, scenario)])
            assert "decisions" not in report


class TestExporterDecisions:
    def test_decisions_endpoint_and_heartbeat(self):
        from triton_distributed_tpu.observability import (
            heartbeat_payload, start_metrics_server)
        record_decision(DecisionEvent(
            consumer="comm.method_select", op="gemm_rs",
            choice="fused",
            candidates=[{"name": "fused", "score_us": 5.0},
                        {"name": "ll", "score_us": 9.0}],
            inputs={}))
        srv = start_metrics_server(0)
        try:
            body = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/decisions",
                timeout=5).read())
        finally:
            srv.stop()
        assert body["schema"] == 1
        assert body["decisions"][-1]["consumer"] == (
            "comm.method_select")
        assert validate_decision(body["decisions"][-1]) == []
        hb = heartbeat_payload()
        assert hb["decisions"][-1]["choice"] == "fused"

    def test_heartbeat_without_decisions_unchanged(self):
        from triton_distributed_tpu.observability import (
            heartbeat_payload)
        feedback.clear_recent_decisions()
        assert "decisions" not in heartbeat_payload()
