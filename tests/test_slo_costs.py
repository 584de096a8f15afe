"""SLO error budgets + per-tenant cost accounting + time-series
retention (ISSUE 16).

The load-bearing assertions:

- **Exact balance.**  Per phase, Σ per-request device-µs is
  *rationally equal* to the measured ledger — no epsilon — and
  per-tenant aggregates sum exactly to the untenanted totals
  (tenants partition requests).
- **Golden discipline.**  An untenanted, policy-free run arms
  nothing: no ``serving_cost_*`` / ``serving_slo_*`` series in the
  Prometheus exposition, no ``cost`` key on request rows, no cost
  rows in the lineage artifact.
- **Burn alerts are schema-v1 DecisionEvents.**  Edge-triggered, one
  per class per excursion, valid under ``validate_decision``.
"""

import dataclasses
import json
import threading
import urllib.request
from fractions import Fraction

import jax
import pytest

from triton_distributed_tpu.observability import (
    SLOClass,
    SLOPolicy,
    SLOTracker,
    TimeSeriesRing,
    cost_accounting_enabled,
    evaluate_outcomes,
    get_cost_recorder,
    load_timeseries,
    series_trends,
    set_cost_accounting,
    validate_decision,
    validate_timeseries,
)
from triton_distributed_tpu.observability import costs as costs_mod
from triton_distributed_tpu.observability.metrics import (
    MetricsRegistry,
    get_registry,
)
from triton_distributed_tpu.serving import (
    ClusterConfig,
    ContinuousBatchingScheduler,
    Request,
    SchedulerConfig,
    ServingCluster,
    ToyConfig,
    ToyModel,
)


@pytest.fixture(autouse=True)
def _fresh_state():
    """Cost accounting and the decision/lineage rings are process
    globals; every test here starts and ends disarmed + empty so the
    golden-discipline tests hold regardless of ordering."""
    from triton_distributed_tpu.observability import feedback
    from triton_distributed_tpu.observability.lineage import (
        get_lineage_recorder)
    from triton_distributed_tpu.observability.recorder import (
        get_flight_recorder)
    set_cost_accounting(False)
    get_cost_recorder().clear()
    feedback.clear_recent_decisions()
    yield
    set_cost_accounting(False)
    get_cost_recorder().clear()
    feedback.clear_recent_decisions()
    get_flight_recorder().clear()
    get_lineage_recorder().clear()


@pytest.fixture(scope="module")
def toy():
    model = ToyModel(ToyConfig(vocab_size=61, hidden=16,
                               max_seq_len=64))
    params = model.init_params(jax.random.key(0))
    return model, params


class Clock:
    def __init__(self):
        self.t = 0.0

    def now(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _run_sched(toy, trace):
    model, params = toy
    ck = Clock()
    sched = ContinuousBatchingScheduler(
        model, params,
        SchedulerConfig(num_slots=3, prefill_buckets=(8, 16, 32)),
        clock=ck.now, clock_advance=ck.advance)
    done = sched.run([Request(**t) for t in trace])
    assert all(r.state.value == "finished" for r in done)
    return done


def _trace(n=6, tenants=("default",)):
    return [dict(prompt=[1 + i, 2 + (i % 3), 3, 4], max_new_tokens=4 + (i % 3),
                 seed=50 + i, arrival_time=0.0,
                 tenant=tenants[i % len(tenants)])
            for i in range(n)]


# ---------------------------------------------------------------------------
# Cost recorder units: exact splits, exact balance
# ---------------------------------------------------------------------------

class TestCostRecorder:
    def test_device_split_is_exact_thirds(self):
        rec = costs_mod.CostRecorder()
        shares = [("r1", "a"), ("r2", "a"), ("r3", "b")]
        rec.charge_device("prefill", 10.0, shares)
        third = Fraction(10) / 3
        assert rec.vector_for("r1").prefill_us == third
        assert rec.vector_for("r3").prefill_us == third
        # 10/3 is not a float — the sum is still exactly 10.
        bal = rec.balance()
        assert bal["exact"] is True
        assert bal["phases"]["prefill"]["exact"] is True

    def test_tenant_totals_partition_the_measured_ledger(self):
        rec = costs_mod.CostRecorder()
        rec.charge_device("prefill", 7.0, [("r1", "a"), ("r2", "b")])
        rec.charge_device("decode", 5.0,
                          [("r1", "a"), ("r2", "b"), ("r3", "b")])
        rec.charge_device("spec_verify", 1.0, [("r3", "b")])
        totals = rec.tenant_totals()
        assert set(totals) == {"a", "b"}
        tenant_sum = sum((v.device_us for v in totals.values()),
                        Fraction(0))
        measured_sum = sum(rec.measured.values(), Fraction(0))
        assert tenant_sum == measured_sum == Fraction(13)

    def test_kv_occupancy_integrates_pages_times_dt(self):
        rec = costs_mod.CostRecorder()
        rec.charge_kv_occupancy("r1", "a", 4, 1.0)   # grid point only
        rec.charge_kv_occupancy("r1", "a", 4, 1.5)   # 4 pages * 0.5s
        rec.charge_kv_occupancy("r1", "a", 2, 2.0)   # 2 pages * 0.5s
        assert rec.vector_for("r1").kv_page_seconds == Fraction(3)

    def test_waste_and_wire_kinds(self):
        rec = costs_mod.CostRecorder()
        rec.charge_tokens("wasted_spec", "r1", "a", 3)
        rec.charge_tokens("reprefill", "r1", "a", 5)
        rec.charge_wire("r1", "a", 1024)
        d = rec.summary("r1")
        assert d["wasted_spec_tokens"] == 3
        assert d["reprefill_tokens"] == 5
        assert d["wire_bytes"] == 1024
        with pytest.raises(AssertionError):
            rec.charge_tokens("not_a_kind", "r1", "a", 1)

    def test_eviction_breaks_exactness_honestly(self):
        rec = costs_mod.CostRecorder(max_requests=2)
        for i in range(4):
            rec.charge_device("decode", 1.0, [(f"r{i}", "a")])
        assert len(rec) == 2
        bal = rec.balance()
        assert bal["evicted_requests"] == 2
        assert bal["exact"] is False   # ledger kept the evicted µs

    def test_arming_is_tenant_gated(self):
        assert not cost_accounting_enabled()
        costs_mod.maybe_arm_for_tenant("default")
        assert not cost_accounting_enabled()
        costs_mod.maybe_arm_for_tenant("acme")
        assert cost_accounting_enabled()


# ---------------------------------------------------------------------------
# Tenant plumbing through the real scheduler (satellite 4)
# ---------------------------------------------------------------------------

class TestTenantPlumbing:
    def test_mixed_tenant_sums_equal_untenanted_totals(self, toy):
        """Tenants partition requests: per-tenant aggregates sum
        EXACTLY (rational ==) to the measured device ledger."""
        _run_sched(toy, _trace(6, tenants=("acme", "widget", "acme")))
        assert cost_accounting_enabled()
        rec = get_cost_recorder()
        bal = rec.balance()
        assert bal["exact"] is True, bal
        for p in costs_mod.PHASES:
            assert bal["phases"][p]["exact"] is True
        totals = rec.tenant_totals()
        assert set(totals) == {"acme", "widget"}
        tenant_sum = sum((v.device_us for v in totals.values()),
                        Fraction(0))
        measured_sum = sum(rec.measured.values(), Fraction(0))
        assert tenant_sum == measured_sum
        assert measured_sum > 0

    def test_cost_summary_joins_lineage_and_request_table(
            self, toy, tmp_path):
        from triton_distributed_tpu.observability.exporter import (
            request_table)
        from triton_distributed_tpu.observability.lineage import (
            get_lineage_recorder,
            load_lineage,
            load_lineage_costs,
            write_lineage_artifact,
        )
        get_lineage_recorder().clear()
        _run_sched(toy, _trace(4, tenants=("acme", "widget")))
        rows = request_table()["requests"]
        with_cost = [r for r in rows if "cost" in r]
        assert with_cost, rows
        assert all(r["cost"]["tenant"] in ("acme", "widget")
                   for r in with_cost)
        path = write_lineage_artifact(str(tmp_path))
        cost_rows = load_lineage_costs(path)
        assert cost_rows and all(r["kind"] == "cost"
                                 for r in cost_rows)
        # load_lineage filters kind=="lineage": appended cost rows
        # never leak into lineage consumers.
        assert all(ev.get("kind", "lineage") == "lineage"
                   for ev in load_lineage(path))

    def test_untenanted_run_stays_byte_identical(self, toy, tmp_path):
        """Golden discipline end-to-end: no tenants, no policy —
        nothing arms, no new metric families, no cost keys."""
        from triton_distributed_tpu.observability.exporter import (
            prometheus_text, request_table)
        from triton_distributed_tpu.observability.lineage import (
            get_lineage_recorder,
            write_lineage_artifact,
        )
        get_registry().clear()
        get_lineage_recorder().clear()
        _run_sched(toy, _trace(4))
        assert not cost_accounting_enabled()
        assert len(get_cost_recorder()) == 0
        text = prometheus_text()
        assert "serving_cost_" not in text
        assert "serving_slo_" not in text
        assert all("cost" not in r
                   for r in request_table()["requests"])
        path = write_lineage_artifact(str(tmp_path))
        with open(path) as f:
            assert all(json.loads(line).get("kind", "lineage")
                       == "lineage" for line in f if line.strip())


# ---------------------------------------------------------------------------
# SLO policy + tracker
# ---------------------------------------------------------------------------

def _policy(objective=0.9, windows=(10.0, 30.0), ttft=1.0, tbt=1.0):
    return SLOPolicy(
        classes=(SLOClass("interactive", ttft_p99_ms=ttft,
                          tbt_p99_ms=tbt, objective=objective),
                 SLOClass("batch", ttft_p99_ms=1e6, tbt_p99_ms=1e6,
                          objective=objective)),
        tenant_class={"web": "interactive", "bulk": "batch"},
        windows=windows, burn_alert_threshold=2.0)


class TestSLOPolicy:
    def test_policy_validation(self):
        with pytest.raises(ValueError):
            SLOPolicy(classes=())
        c = SLOClass("a", 1.0, 1.0)
        with pytest.raises(ValueError):
            SLOPolicy(classes=(c, SLOClass("a", 2.0, 2.0)))
        with pytest.raises(ValueError):
            SLOPolicy(classes=(c,), tenant_class={"t": "nope"})
        with pytest.raises(ValueError):
            SLOPolicy(classes=(c,), default_class="nope")

    def test_unmeasured_dimension_cannot_breach(self):
        c = SLOClass("a", ttft_p99_ms=1.0, tbt_p99_ms=1.0)
        assert c.compliant(None, None)
        assert c.compliant(0.5, None)
        assert not c.compliant(2.0, None)
        assert not c.compliant(None, 2.0)

    def test_evaluate_outcomes_per_class(self):
        pol = _policy(objective=0.5)
        verdicts = evaluate_outcomes(pol, [
            ("web", 0.5, 0.5),      # compliant
            ("web", 5.0, 0.5),      # TTFT breach
            ("bulk", 100.0, 100.0),  # batch targets are huge
        ])
        assert verdicts["interactive"]["total"] == 2
        assert verdicts["interactive"]["compliant"] == 1
        assert verdicts["interactive"]["ok"] is True   # 0.5 >= 0.5
        assert verdicts["batch"]["ok"] is True
        strict = evaluate_outcomes(_policy(objective=0.99),
                                   [("web", 5.0, 0.5)])
        assert strict["interactive"]["ok"] is False


class TestSLOTracker:
    def test_burn_alert_is_valid_edge_triggered_decision(self):
        from triton_distributed_tpu.observability import feedback
        tr = SLOTracker(_policy())
        # Every interactive request breaches: burn = 1/(1-0.9) = 10.
        for i in range(5):
            tr.observe("web", ttft_ms=50.0, tbt_ms=None,
                       ts=float(i))
        fired = tr.check(now=5.0)
        assert [a["class"] for a in fired] == ["interactive"]
        assert tr.check(now=6.0) == []      # edge-triggered
        assert tr.alerts_fired == 1
        evs = [d for d in feedback.recent_decisions()
               if d.consumer == "slo.burn_alert"]
        assert len(evs) == 1
        d = dataclasses.asdict(evs[0])
        assert validate_decision(d) == []
        assert d["inputs"]["class"] == "interactive"
        assert d["inputs"]["dominant_tenant"] == "web"
        assert all(b > 2.0 for b in d["inputs"]["burn"].values())

    def test_recovery_rearms_the_alert(self):
        tr = SLOTracker(_policy(windows=(5.0,)))
        for i in range(3):
            tr.observe("web", 50.0, None, ts=float(i))
        assert len(tr.check(now=3.0)) == 1
        # Breaches age out of the 5s window; compliant traffic lands.
        for i in range(20):
            tr.observe("web", 0.1, None, ts=10.0 + 0.1 * i)
        assert tr.check(now=12.0) == []
        for i in range(5):
            tr.observe("web", 50.0, None, ts=13.0 + 0.1 * i)
        assert len(tr.check(now=14.0)) == 1
        assert tr.alerts_fired == 2

    def test_burn_gauges_ride_the_registry(self):
        get_registry().clear()
        tr = SLOTracker(_policy())
        tr.observe("web", 50.0, None, ts=1.0)
        tr.check(now=1.0)
        snap = get_registry().snapshot()
        assert snap["gauges"]["serving_slo_burn_max"] == pytest.approx(10.0)
        assert snap["gauges"]["serving_slo_budget_min"] == pytest.approx(-9.0)
        labelled = [k for k in snap["gauges"]
                    if k.startswith("serving_slo_burn_rate")]
        assert labelled   # per-class/window Prometheus series

    def test_state_dict_is_json_round_trippable(self):
        tr = SLOTracker(_policy())
        tr.observe("web", 50.0, None, ts=1.0)
        tr.observe("bulk", 1.0, 1.0, ts=1.0)
        state = json.loads(json.dumps(tr.state_dict(now=2.0),
                                      default=str))
        assert state["schema"] == 1
        cls = state["classes"]["interactive"]
        assert cls["total"] == 1 and cls["breaches"] == 1
        assert state["classes"]["batch"]["compliance"] == 1.0
        assert "web" in state["tenants"]


# ---------------------------------------------------------------------------
# Time-series ring
# ---------------------------------------------------------------------------

class TestTimeSeries:
    def test_ring_bounds_with_counted_eviction(self):
        ring = TimeSeriesRing(interval_s=1.0, capacity=4,
                              registry=MetricsRegistry())
        for t in range(10):
            ring.sample(float(t))
        assert len(ring) == 4
        assert ring.dropped_samples == 6
        assert [r["ts"] for r in ring.samples()] == [6.0, 7.0, 8.0,
                                                     9.0]

    def test_maybe_sample_honors_interval(self):
        ring = TimeSeriesRing(interval_s=1.0,
                              registry=MetricsRegistry())
        assert ring.maybe_sample(0.0) is not None
        assert ring.maybe_sample(0.5) is None
        assert ring.maybe_sample(1.0) is not None
        assert len(ring) == 2

    def test_write_load_roundtrip_tolerates_torn_lines(
            self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("steps_total").inc(3)
        reg.gauge("serving_queue_depth").set(7)
        ring = TimeSeriesRing(interval_s=1.0, registry=reg)
        ring.sample(1.0)
        ring.sample(2.0)
        path = ring.write(str(tmp_path), rank=3)
        assert path.endswith("timeseries-rank-3.jsonl")
        with open(path, "a") as f:
            f.write('{"kind": "timeseries", "truncat')   # torn tail
        rows = load_timeseries(path)
        assert len(rows) == 2
        for r in rows:
            assert validate_timeseries(r) == []
        assert rows[-1]["gauges"]["serving_queue_depth"] == 7
        assert rows[-1]["counters"]["steps_total"] == 3

    def test_empty_ring_writes_nothing(self, tmp_path):
        ring = TimeSeriesRing(registry=MetricsRegistry())
        assert ring.write(str(tmp_path)) is None
        assert list(tmp_path.iterdir()) == []

    def test_trends_find_monotone_tails_only(self):
        def row(ts, depth):
            return {"ts": ts, "gauges": {"serving_queue_depth": depth,
                                         "serving_slot_occupancy": 1.0}}
        rows = [row(float(t), float(v))
                for t, v in enumerate([2, 1, 1, 3, 4, 5])]
        trends = series_trends(rows)
        assert [t["metric"] for t in trends] == [
            "serving_queue_depth"]   # flat occupancy filtered out
        t = trends[0]
        assert t["direction"] == "rising"
        # The flat 1->1 step extends the monotone tail: run=5.
        assert t["run"] == 5 and t["delta"] == 4.0
        # A 2-sample tail is noise, not a trend.
        assert series_trends([row(0.0, 1.0), row(1.0, 2.0)]) == []


# ---------------------------------------------------------------------------
# SLO-configured cluster end-to-end + artifacts + doctor
# ---------------------------------------------------------------------------

class TestClusterSLO:
    def _cluster(self, toy, policy):
        model, params = toy
        return ServingCluster(model, params, ClusterConfig(
            n_replicas=2,
            scheduler=SchedulerConfig(num_slots=2,
                                      prefill_buckets=(8, 16)),
            step_time_s=1e-3, prefill_time_s=2e-3,
            slo_policy=policy, timeseries_interval_s=2e-3))

    def test_burn_alert_artifacts_and_doctor_section(
            self, toy, tmp_path):
        from triton_distributed_tpu.observability import feedback
        from triton_distributed_tpu.observability.doctor import (
            diagnose, render_markdown)
        from triton_distributed_tpu.observability.lineage import (
            get_lineage_recorder)
        get_registry().clear()
        get_lineage_recorder().clear()
        # Impossible interactive targets on the virtual clock: every
        # web request breaches, the burn rule trips mid-drain.
        policy = SLOPolicy(
            classes=(SLOClass("interactive", 1e-6, 1e-6,
                              objective=0.9),
                     SLOClass("batch", 1e6, 1e6, objective=0.9)),
            tenant_class={"web": "interactive", "bulk": "batch"},
            windows=(0.05, 0.2), burn_alert_threshold=2.0)
        cluster = self._cluster(toy, policy)
        assert cost_accounting_enabled()   # policy arms the join
        for i, tenant in enumerate(["web", "web", "bulk", "web"]):
            cluster.submit([1 + i, 2, 3, 4], 4, seed=i,
                           arrival_time=0.0, tenant=tenant)
        done = cluster.drain()
        assert len(done) == 4

        alerts = [d for d in feedback.recent_decisions()
                  if d.consumer == "slo.burn_alert"]
        assert [a.op for a in alerts] == ["class:interactive"]
        assert validate_decision(dataclasses.asdict(alerts[0])) == []

        assert get_cost_recorder().balance()["exact"] is True
        assert len(cluster.timeseries) >= 2

        cluster.write_artifact(str(tmp_path))
        names = {p.name for p in tmp_path.iterdir()}
        assert {"lineage.jsonl", "slo-state.json",
                "timeseries-rank-0.jsonl"} <= names
        state = json.loads((tmp_path / "slo-state.json").read_text())
        assert state["classes"]["interactive"]["breaches"] == 3
        assert state["classes"]["interactive"]["alerting"] is True
        assert state["tenant_costs"]["web"]["device_us"] > 0

        report = diagnose([str(tmp_path)])
        assert report["slo"]["burning"] == ["interactive"]
        assert report["slo"]["dominant_tenant"] == "web"
        assert report["timeseries"]["samples"] >= 2
        assert "interactive" in report["verdict"]
        md = render_markdown(report)
        assert "## SLO" in md and "## Time series" in md
        assert "Tenant bill (cost join)" in md

    def test_policy_free_cluster_has_no_slo_surface(self, toy,
                                                    tmp_path):
        from triton_distributed_tpu.observability.doctor import (
            diagnose)
        model, params = toy
        cluster = ServingCluster(model, params, ClusterConfig(
            n_replicas=1,
            scheduler=SchedulerConfig(num_slots=2,
                                      prefill_buckets=(8, 16))))
        assert cluster.slo is None and cluster.timeseries is None
        cluster.submit([1, 2, 3], 2, arrival_time=0.0)
        cluster.drain()
        cluster.write_artifact(str(tmp_path))
        names = {p.name for p in tmp_path.iterdir()}
        assert "slo-state.json" not in names
        assert not any(n.startswith("timeseries-") for n in names)
        report = diagnose([str(tmp_path)])
        assert "slo" not in report and "timeseries" not in report


# ---------------------------------------------------------------------------
# Exporter hardening (satellite 2)
# ---------------------------------------------------------------------------

class TestExporterHardening:
    def test_healthz_carries_build_info_and_uptime(self):
        from triton_distributed_tpu import __version__
        from triton_distributed_tpu.observability.exporter import (
            heartbeat_payload, start_metrics_server)
        srv = start_metrics_server(port=0)
        try:
            body = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz",
                timeout=10).read())
        finally:
            srv.stop()
        info = body["tdt_build_info"]
        assert info["version"] == __version__
        assert info["python"] and info["platform"]
        assert body["uptime_s"] >= 0
        # Response-only hardening: heartbeat FILE bodies unchanged.
        hb = heartbeat_payload()
        assert "tdt_build_info" not in hb and "uptime_s" not in hb

    def test_concurrent_scrape_during_live_serving(self, toy):
        """Two scraper threads hammer /metrics + /timeseries while
        the cluster drains a trace: every response is 200 and
        parseable (the registry and ring are lock-protected)."""
        from triton_distributed_tpu.observability.exporter import (
            start_metrics_server)
        model, params = toy
        cluster = ServingCluster(model, params, ClusterConfig(
            n_replicas=2,
            scheduler=SchedulerConfig(num_slots=2,
                                      prefill_buckets=(8, 16)),
            timeseries_interval_s=1e-3))
        for i in range(6):
            cluster.submit([1 + i, 2, 3, 4], 5, seed=i,
                           arrival_time=0.0)
        srv = start_metrics_server(port=0)
        errors = []
        bodies = {"metrics": 0, "timeseries": 0}

        def scrape(path, key):
            for _ in range(15):
                try:
                    raw = urllib.request.urlopen(
                        f"http://127.0.0.1:{srv.port}/{path}",
                        timeout=10).read()
                    if key == "timeseries":
                        json.loads(raw)
                    else:
                        raw.decode()
                    bodies[key] += 1
                except Exception as e:   # noqa: BLE001 (collected)
                    errors.append(f"{path}: {e!r}")

        threads = [
            threading.Thread(target=scrape,
                             args=("metrics", "metrics")),
            threading.Thread(target=scrape,
                             args=("timeseries", "timeseries")),
        ]
        try:
            for t in threads:
                t.start()
            cluster.drain()
            for t in threads:
                t.join(timeout=30)
        finally:
            srv.stop()
        assert errors == []
        assert bodies == {"metrics": 15, "timeseries": 15}
        assert len(cluster.timeseries) >= 1
