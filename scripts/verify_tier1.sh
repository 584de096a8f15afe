#!/usr/bin/env bash
# Tier-1 verification gate — the exact ROADMAP.md invocation, wrapped
# so CI and humans run the same thing.  CPU-only, non-slow tests,
# bounded at 870 s; prints DOTS_PASSED=<n> (count of passing tests)
# and exits with pytest's status.
#
# Hardened beyond the raw invocation:
#  - pytest collection ERRORS fail the gate even when every collected
#    test passed (a broken import silently shrinking the suite must
#    not read as green);
#  - a lint gate (`ruff check .` when installed, scripts/lint.py as
#    the dependency-free fallback — see ruff.toml);
#  - a static comm-sanitizer sweep over every registered kernel
#    (`python -m triton_distributed_tpu.analysis`), which must report
#    ZERO findings — a leaked semaphore or unmatched wait in a shipped
#    collective fails tier-1 before any TPU sees it;
#  - a trace-export smoke run (span -> Chrome trace -> timeline merge
#    -> Prometheus render) guards the observability runtime on CPU;
#  - a doctor smoke over the seeded incident corpus
#    (tests/data/incidents): every scenario's report must match its
#    committed golden byte-for-byte in structure — silent report
#    drift fails tier-1;
#  - a closed-loop smoke (synthetic contended bus -> method flip,
#    SLO deferral, schema-valid decisions.jsonl, doctor
#    Control-decisions section) plus the paired closed-loop bench
#    gate (bus-disabled rows exactly match the committed results);
#  - a router smoke (2-replica + 1-prefill virtual-clock cluster:
#    prefix-affinity routing, kill-a-replica failover, /routing
#    endpoint render) plus the router bench gate (signal-aware beats
#    round-robin under seeded imbalance, matches it balanced);
#  - a chaos smoke (seeded lossy-wire fault schedule on the virtual
#    clock -> token-for-token exact survivors -> schema-valid
#    faults.jsonl -> doctor "Chaos" section names the fault classes);
#  - a net smoke (launch.py --roles stands up REAL multi-process
#    clusters over length-prefixed TCP: a 2-process run token-exact
#    vs the in-process virtual transport, a 4-process seeded chaos
#    run at the socket seam with every request finishing exactly,
#    and one doctor invocation merging the per-rank directories);
#  - a lineage smoke (2-replica virtual cluster -> schema-valid
#    lineage.jsonl -> TTFT hop decomposition sums EXACTLY to the
#    measured TTFT for every request -> doctor "Request lineage"
#    section names the dominant hop);
#  - a speculative-decoding smoke (draft-verify rounds on both KV
#    layouts, n-gram AND draft-model sources, greedy + sampled ->
#    token-for-token vs the non-speculative engine, exact KV
#    rollback, accept metrics in the Prometheus render);
#  - a KV-tier smoke (2-replica virtual cluster: a prefix prefilled
#    on replica A served from replica B via peer prefix shipment with
#    zero second prefill, bit-exact; per-tier hit counters in the
#    Prometheus render; doctor "KV tier" section);
#  - a metrics-reference drift check (docs/observability.md's
#    generated table must match the scraped call sites);
#  - an SLO smoke (2-class SLOPolicy on the virtual clock: a burn
#    alert fires as a schema-valid DecisionEvent, cost vectors
#    balance exactly, timeseries + slo-state + cost-joined lineage
#    artifacts land, the doctor renders an "SLO" section, and the
#    capacity planner answers "2 replicas" bit-exactly twice) plus
#    the planner bench gate (every committed plan row feasible AND
#    deterministic);
#  - a telemetry smoke (2-replica virtual cluster with the fleet
#    telemetry plane armed: every source folds into the front door's
#    collector, /fleet + fleet-labeled Prometheus render, a seeded
#    burn frame fires exactly one edge-triggered alert and clears,
#    the watch --once render is byte-stable, the doctor gains a
#    "Fleet alerts" section) plus the telemetry bench gate (paired
#    plane-off/plane-on trace: exact token parity, bounded
#    overhead).
set -o pipefail
cd "$(dirname "$0")/.."

# Lint gate: prefer ruff (full scoped rules), fall back to the
# stdlib-only checker so the gate runs in every container.
if command -v ruff >/dev/null 2>&1; then
    if ! ruff check .; then
        echo "LINT=FAILED (ruff)"
        exit 1
    fi
else
    if ! python scripts/lint.py; then
        echo "LINT=FAILED (scripts/lint.py)"
        exit 1
    fi
fi
echo "LINT=ok"

# Metrics-reference drift gate: the generated table in
# docs/observability.md must match the registry call sites the code
# actually contains (scripts/gen_metrics_reference.py --check).
if ! python scripts/gen_metrics_reference.py --check; then
    echo "METRICS_REFERENCE=FAILED"
    exit 1
fi
echo "METRICS_REFERENCE=ok"

# Static comm-graph sanitizer sweep: every registered kernel on its
# representative meshes must analyze clean (docs/analysis.md).
# Bounded like the pytest stage: replays run kernel loops as plain
# Python, so a runaway loop bound must fail the gate, not hang CI
# (normal sweep is ~5 s; 120 s is generous headroom).
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
        python -m triton_distributed_tpu.analysis -q; then
    echo "ANALYSIS_SWEEP=FAILED"
    exit 1
fi
echo "ANALYSIS_SWEEP=ok"

# Resource sanitizer sweep: every registered kernel — comm (replayed
# run_scoped/emit_pipeline footprint) AND compute (captured
# pallas_call geometry) — must fit VMEM, tile legally and keep every
# block index in bounds, including page-table indirection
# (docs/analysis.md "Resource sanitizer").
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu \
        python -m triton_distributed_tpu.analysis --check resources -q
then
    echo "RESOURCE_SWEEP=FAILED"
    exit 1
fi
echo "RESOURCE_SWEEP=ok"

# Serving-state model check: exhaustive small-scope exploration of the
# paged KV layer (refcounts, sharing, donation) must be clean
# (docs/analysis.md "Serving model checker").
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
        python -m triton_distributed_tpu.analysis --check serving -q
then
    echo "SERVING_MODEL_CHECK=FAILED"
    exit 1
fi
echo "SERVING_MODEL_CHECK=ok"

# Cluster protocol model check: exhaustive small-scope exploration of
# the wire/routing/failover state machines — every interleaving of
# delivery, loss, duplication, corruption, crash and staleness over
# the standard scope matrix must terminate with exactly-once effects
# (docs/analysis.md "Protocol checker").  The mutant corpus
# (tests/test_protocol_analysis.py) proves the checker still CATCHES
# each defect class it exists for.
if ! timeout -k 10 240 env JAX_PLATFORMS=cpu \
        python -m triton_distributed_tpu.analysis --check protocol -q
then
    echo "PROTOCOL_CHECK=FAILED"
    exit 1
fi
if ! timeout -k 10 360 env JAX_PLATFORMS=cpu python -m pytest \
        tests/test_protocol_analysis.py -q \
        -p no:cacheprovider -p no:xdist -p no:randomly
then
    echo "PROTOCOL_CHECK=FAILED (mutant corpus)"
    exit 1
fi
echo "PROTOCOL_CHECK=ok"

LOG="${TIER1_LOG:-/tmp/_t1.log}"
rm -f "$LOG"
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee "$LOG"
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$LOG" \
    | tr -cd . | wc -c)

# Collection errors are failures, not noise: pytest's summary line
# ("... N errors in 12.3s") reports them — catch them even if rc came
# back 0.  Match only the timing summary line, not arbitrary test
# output that happens to contain the word "errors".
n_errors=$(grep -aE 'in [0-9.]+s' "$LOG" \
    | grep -aoE '[0-9]+ errors?' | tail -1 \
    | grep -oE '[0-9]+' || true)
if [ "${n_errors:-0}" -gt 0 ]; then
    echo "COLLECTION_ERRORS=${n_errors}"
    [ "$rc" -eq 0 ] && rc=1
fi

# Trace-export smoke: spans -> per-rank Chrome trace -> merged
# timeline + straggler report -> Prometheus text.  Pure host-side
# observability, cheap enough to run every gate.
smoke_log=$(JAX_PLATFORMS=cpu python - <<'EOF' 2>&1
import json, os, tempfile, time
from triton_distributed_tpu.observability import (
    get_registry, get_tracer, prometheus_text, span)
from triton_distributed_tpu.observability.timeline import (
    merge_directory)

d = tempfile.mkdtemp(prefix="tdt-smoke-")
with span("smoke.outer", phase="verify"):
    with span("smoke.inner"):
        time.sleep(0.001)
for rank in (0, 1):  # two synthetic ranks so the merge has work
    os.environ["TDT_PROCESS_ID"] = str(rank)
    path = get_tracer().export_chrome_trace(
        os.path.join(d, f"trace-rank-{rank}.json"))
    trace = json.load(open(path))
    assert any(e.get("ph") == "X" for e in trace["traceEvents"]), path
report = merge_directory(d)
assert os.path.exists(os.path.join(d, "merged_trace.json"))
assert "smoke.outer" in report["spans"], report
get_registry().counter("smoke_total").inc()
text = prometheus_text()
assert any(line.split() == ["smoke_total", "1.0"]
           for line in text.splitlines()), text
print("TRACE_SMOKE=ok")
EOF
)
smoke_rc=$?
echo "$smoke_log" | tail -5
if [ "$smoke_rc" -ne 0 ]; then
    echo "TRACE_SMOKE=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi

# Doctor smoke: run the incident doctor over every seeded scenario
# and fail on drift from the committed golden reports.  Reports are
# deterministic by construction ("now" = newest artifact timestamp),
# so any diff is a real behavior change in links/anomaly/doctor.
doctor_rc=0
for scenario in stalled_rank sem_leak slow_link clean \
        lossy_transport slow_request replayed_fault \
        socket_partition fleet_alert; do
    if ! JAX_PLATFORMS=cpu python -m \
            triton_distributed_tpu.observability.doctor \
            "tests/data/incidents/$scenario" -q \
            --json "/tmp/_t1_doctor_${scenario}.json" \
            --md "/tmp/_t1_doctor_${scenario}.md" \
            --check "tests/data/incidents/$scenario/report.golden.json"
    then
        echo "DOCTOR_SMOKE=FAILED ($scenario)"
        doctor_rc=1
    fi
done
if [ "$doctor_rc" -ne 0 ]; then
    [ "$rc" -eq 0 ] && rc=1
else
    echo "DOCTOR_SMOKE=ok"
fi

# Serving smoke: continuous-batching scheduler end-to-end on CPU —
# tiny model, 8 requests with staggered arrivals through 3 slots,
# SLO metrics present in the Prometheus render, one span per request.
serving_log=$(JAX_PLATFORMS=cpu python - <<'EOF' 2>&1
import jax
from triton_distributed_tpu.observability import (
    get_registry, get_tracer, prometheus_text)
from triton_distributed_tpu.serving import (
    ContinuousBatchingScheduler, Request, SchedulerConfig, ToyConfig,
    ToyModel)

model = ToyModel(ToyConfig(vocab_size=61, hidden=16, max_seq_len=64))
params = model.init_params(jax.random.key(0))
get_registry().clear()
get_tracer().clear()

class Clock:  # virtual time: deterministic, no sleeps
    t = 0.0
clock = Clock()
sched = ContinuousBatchingScheduler(
    model, params,
    SchedulerConfig(num_slots=3, prefill_buckets=(8, 16)),
    clock=lambda: clock.t,
    clock_advance=lambda dt: setattr(clock, "t", clock.t + dt))
# Heterogeneous max_new: rows retire at different steps, so joiners
# really insert into a mid-decode batch (staggered arrival_time under
# a virtual clock would serialize instead); the staggered arrivals
# additionally exercise the arrival gate.
gens = [2, 5, 3, 6, 2, 4, 7, 3]
reqs = [Request(prompt=[1 + i, 2, 3, 4], max_new_tokens=g,
                arrival_time=(i % 2) * 0.01)
        for i, g in enumerate(gens)]
done = sched.run(reqs)
assert len(done) == 8, [r.state for r in reqs]
assert all(len(r.generated) == g
           for r, g in zip(sorted(done, key=lambda r: r.request_id),
                           gens))
assert all(r.ttft is not None and r.ttft >= 0 for r in done)
snap = get_registry().snapshot()
assert snap["counters"]["serving_requests_submitted_total"] == 8
assert snap["histograms"]["serving_ttft_ms"]["count"] == 8
text = prometheus_text()
for name in ("serving_ttft_ms_bucket", "serving_tbt_ms_bucket",
             "serving_queue_depth", "serving_slot_occupancy"):
    assert name in text, name
spans = [s for s in get_tracer().finished()
         if s.name == "serving.request"]
assert len(spans) == 8, len(spans)
print("SERVING_SMOKE=ok")
EOF
)
serving_rc=$?
echo "$serving_log" | tail -3
if [ "$serving_rc" -ne 0 ]; then
    echo "SERVING_SMOKE=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi

# Paged-serving smoke: the page-table engine end-to-end on CPU —
# shared-system-prompt workload through the radix prefix cache under a
# virtual clock, token-for-token against the slot engine, page gauges
# + prefix counters present in the Prometheus render.
paged_log=$(JAX_PLATFORMS=cpu python - <<'EOF' 2>&1
import jax
import numpy as np
from triton_distributed_tpu.observability import (
    get_registry, prometheus_text)
from triton_distributed_tpu.serving import (
    ContinuousBatchingScheduler, Request, SchedulerConfig, ToyConfig,
    ToyModel)

model = ToyModel(ToyConfig(vocab_size=61, hidden=16, max_seq_len=64))
params = model.init_params(jax.random.key(0))
get_registry().clear()
rng = np.random.default_rng(7)
sysp = list(rng.integers(1, 61, 16))     # one full shared page
def reqs():
    return [Request(prompt=sysp + [1 + i, 2 + i], max_new_tokens=g,
                    arrival_time=(i % 2) * 0.01)
            for i, g in enumerate([2, 5, 3, 6, 2, 4])]
outs = {}
for layout in ("slots", "paged"):
    class Clock:
        t = 0.0
    clock = Clock()
    sched = ContinuousBatchingScheduler(
        model, params,
        SchedulerConfig(num_slots=3, prefill_buckets=(8, 16, 32),
                        kv_layout=layout, page_size=16),
        clock=lambda: clock.t,
        clock_advance=lambda dt: setattr(clock, "t", clock.t + dt))
    done = sched.run(reqs())
    assert len(done) == 6, [r.state for r in done]
    outs[layout] = [r.generated for r in
                    sorted(done, key=lambda r: r.request_id)]
assert outs["slots"] == outs["paged"], "paged != slots token streams"
assert sched.slots.radix.hit_tokens == 5 * 16, sched.slots.radix.hit_tokens
snap = get_registry().snapshot()
assert snap["counters"]["serving_prefix_cache_hit_tokens_total"] == 80
text = prometheus_text()
for name in ("serving_kv_pages_free", "serving_kv_pages_used",
             "serving_kv_page_occupancy",
             "serving_prefix_cache_hit_tokens_total"):
    assert name in text, name
print("PAGED_SMOKE=ok")
EOF
)
paged_rc=$?
echo "$paged_log" | tail -3
if [ "$paged_rc" -ne 0 ]; then
    echo "PAGED_SMOKE=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi

# Speculative-decoding smoke: draft-verify on the masked batched
# step — greedy AND sampled streams must be token-for-token identical
# to the non-speculative engine on both KV layouts, draft KV must
# roll back exactly (pool balances after drain), and the accept
# metrics must land in the Prometheus render.
spec_log=$(JAX_PLATFORMS=cpu python - <<'EOF' 2>&1
import jax
from triton_distributed_tpu.observability import (
    get_registry, prometheus_text)
from triton_distributed_tpu.serving import (
    BatchedDraftModelDrafter, ContinuousBatchingScheduler, Request,
    SchedulerConfig, ToyConfig, ToyModel)

model = ToyModel(ToyConfig(vocab_size=61, hidden=16, max_seq_len=96))
params = model.init_params(jax.random.key(0))
get_registry().clear()

def run(layout, spec_k, drafter=None, temperature=0.0):
    class Clock:
        t = 0.0
    c = Clock()
    sched = ContinuousBatchingScheduler(
        model, params,
        SchedulerConfig(num_slots=3, prefill_buckets=(8, 16),
                        kv_layout=layout, page_size=8,
                        temperature=temperature, spec_k=spec_k,
                        spec_drafter=drafter),
        clock=lambda: c.t,
        clock_advance=lambda dt: setattr(c, "t", c.t + dt))
    reqs = [Request(prompt=[1 + i, 2, 3, 4], max_new_tokens=14 + i,
                    seed=i, arrival_time=(i % 2) * 0.01)
            for i in range(5)]
    done = sched.run(reqs)
    assert len(done) == 5, [r.state for r in done]
    return (sched, [r.generated for r in
                    sorted(done, key=lambda r: r.request_id)],
            sum(r.spec_accepted for r in done),
            sum(r.spec_proposed for r in done))

fac = lambda s: BatchedDraftModelDrafter(
    model, params, num_slots=s.config.num_slots, max_seq=s.max_seq,
    prefill_buckets=(8, 16))
for temp in (0.0, 1.0):
    for layout in ("slots", "paged"):
        _, ref, _, _ = run(layout, 0, temperature=temp)
        s_ng, out, acc, prop = run(layout, 3, temperature=temp)
        assert out == ref, f"ngram spec diverged ({layout}, {temp})"
        sched, out, acc, prop = run(layout, 3, drafter=fac,
                                    temperature=temp)
        assert out == ref, f"draft spec diverged ({layout}, {temp})"
        assert prop > 0, prop
        if temp == 0.0:
            # greedy self-draft agrees totally; a greedy drafter
            # against a SAMPLED target rightly accepts ~nothing —
            # exactness above is the sampled-mode claim
            assert acc == prop, (acc, prop)
        if layout == "paged":
            kv = sched.slots
            assert kv.pool.used_pages == kv.radix.cached_pages, (
                "rollback left pages pinned")
text = prometheus_text()
for name in ("serving_spec_accept_tokens_bucket",
             "serving_spec_proposed_tokens_total",
             "serving_spec_accepted_tokens_total",
             "serving_spec_rejected_tokens_total",
             "serving_spec_accept_rate"):
    assert name in text, name
print("SPEC_SMOKE=ok")
EOF
)
spec_rc=$?
echo "$spec_log" | tail -3
if [ "$spec_rc" -ne 0 ]; then
    echo "SPEC_SMOKE=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi

# Closed-loop smoke: a serving run against a synthetic contended-bus
# fixture with SLO admission armed must (1) write a schema-valid
# decisions.jsonl, (2) flip a method choice vs static selection, and
# (3) render a doctor "Control decisions" section — while the golden
# incident corpus (no decisions artifact) stayed byte-identical in
# the DOCTOR_SMOKE above.
closed_log=$(JAX_PLATFORMS=cpu python - <<'EOF' 2>&1
import json, os, tempfile
os.environ["TDT_ANOMALY_BASELINES"] = os.path.join(
    tempfile.mkdtemp(prefix="tdt-cl-b-"), "baselines.json")
import jax
from triton_distributed_tpu.kernels.comm_perf_model import (
    torus_beats_single_axis)
from triton_distributed_tpu.observability import feedback
from triton_distributed_tpu.observability.anomaly import (
    WINDOW, BaselineStore, event_key)
from triton_distributed_tpu.observability.doctor import (
    diagnose, render_markdown)
from triton_distributed_tpu.serving import (
    ContinuousBatchingScheduler, Request, SchedulerConfig, ToyConfig,
    ToyModel)

d = tempfile.mkdtemp(prefix="tdt-cl-")
feedback.set_decision_log(os.path.join(d, "decisions-rank-0.jsonl"))

# (a) seeded contention flips a method choice, recorded
hot = feedback.synthetic_bus(link_utilization={"x:0>1": 0.85,
                                               "x:1>2": 0.85})
flipped = any(
    torus_beats_single_axis(1 << e, (4, 4))
    != torus_beats_single_axis(1 << e, (4, 4), axes=("x", "y"),
                               bus=hot)
    for e in range(8, 24))
assert flipped, "contended bus never changed a method choice"

# (c) SLO admission defers against a seeded slow-step baseline
store = BaselineStore(os.environ["TDT_ANOMALY_BASELINES"])
for _ in range(WINDOW):
    store.observe(event_key("serving.decode_step", None, (3,), 1),
                  50_000.0)
model = ToyModel(ToyConfig(vocab_size=61, hidden=16, max_seq_len=64))
params = model.init_params(jax.random.key(0))
class Clock:
    t = 0.0
clock = Clock()
sched = ContinuousBatchingScheduler(
    model, params,
    SchedulerConfig(num_slots=3, prefill_buckets=(8, 16),
                    slo_tbt_ms=10.0),
    clock=lambda: clock.t,
    clock_advance=lambda dt: setattr(clock, "t", clock.t + dt),
    bus=feedback.synthetic_bus(store=store, clock=lambda: clock.t,
                               ts=0.0))
done = sched.run([Request(prompt=[1 + i, 2, 3], max_new_tokens=2,
                          arrival_time=0.0) for i in range(3)])
assert len(done) == 3 and all(len(r.generated) == 2 for r in done)
feedback.set_decision_log(None)

# decisions.jsonl: present, schema-valid, carries both consumers
rows = feedback.load_decisions(os.path.join(d,
                                            "decisions-rank-0.jsonl"))
assert rows, "no decisions recorded"
for row in rows:
    problems = feedback.validate_decision(row)
    assert not problems, (problems, row)
consumers = {r["consumer"] for r in rows}
assert {"comm.method_select", "serving.admission"} <= consumers

# doctor replays them into a Control-decisions section
with open(os.path.join(d, "heartbeat-rank-0.json"), "w") as f:
    json.dump({"schema": 1, "rank": 0, "pid": 1,
               "unix_time": max(r["ts"] for r in rows) + 1.0,
               "step": 1, "last_span": None, "open_spans": []}, f)
report = diagnose([d])
assert report.get("decisions", {}).get("count") == len(rows)
assert "## Control decisions" in render_markdown(report)
print("CLOSED_LOOP_SMOKE=ok")
EOF
)
closed_rc=$?
echo "$closed_log" | tail -3
if [ "$closed_rc" -ne 0 ]; then
    echo "CLOSED_LOOP_SMOKE=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi

# Router smoke: the disaggregated cluster end-to-end on CPU — 2
# decode replicas + 1 prefill worker on the virtual clock; asserts
# prefix-affinity routing, kill-one-replica failover with every
# request finishing, and the /routing endpoint rendering the replica
# table (ISSUE-9 ROUTER_SMOKE gate).
router_log=$(JAX_PLATFORMS=cpu python - <<'EOF' 2>&1
import json, urllib.request
import numpy as np
import jax
from triton_distributed_tpu.observability.exporter import (
    start_metrics_server)
from triton_distributed_tpu.serving import (
    ClusterConfig, SchedulerConfig, ServingCluster, ToyConfig,
    ToyModel)
from triton_distributed_tpu.serving.cluster import RouterConfig

model = ToyModel(ToyConfig(vocab_size=61, hidden=16, max_seq_len=64))
params = model.init_params(jax.random.key(0))
sc = SchedulerConfig(num_slots=3, prefill_buckets=(8, 16, 32),
                     kv_layout="paged", page_size=16)
cluster = ServingCluster(model, params, ClusterConfig(
    n_replicas=2, n_prefill_workers=1, scheduler=sc,
    router=RouterConfig(dead_after_s=0.01)))

# Prefix affinity: spaced same-prefix requests must all land on one
# replica (whose radix cache then serves the shared page).
sysp = list(np.random.default_rng(7).integers(1, 61, 16))
aff = [cluster.submit(sysp + [1 + i], 2, seed=i,
                      arrival_time=0.05 * i) for i in range(3)]
# Distinct-prefix background traffic spreads round-robin-ish.
bg = [cluster.submit([40 + i, 2, 3, 4], 3, seed=10 + i,
                     arrival_time=0.05 * i + 0.01) for i in range(3)]
done = cluster.drain()
assert len(done) == 6, [r.state for r in done]
homes = {r.replica_history[0] for r in aff}
assert len(homes) == 1, f"prefix affinity spread: {homes}"
assert cluster.transport.shipments == 6

# Failover: kill the affinity home mid-flight; everything finishes
# on the survivor, token streams intact.
more = [cluster.submit(sysp + [30 + i], 4, seed=20 + i)
        for i in range(3)]
cluster.step()
cluster.kill_replica(homes.pop())
done2 = cluster.drain()
assert all(r.state == "finished" for r in more), (
    [r.state for r in more])
assert cluster.router.failovers, "no failover recorded"

# /routing endpoint renders the table with the dead replica named.
srv = start_metrics_server(port=0)
try:
    body = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{srv.port}/routing", timeout=10).read())
finally:
    srv.stop()
router = body["router"]
assert router["kind"] == "router"
states = {r["name"]: r["alive"] for r in router["replicas"]}
assert sorted(states) == ["replica-0", "replica-1"]
assert list(states.values()).count(False) == 1, states
assert router["failovers"][0]["reason"] == "heartbeat_loss"
print("ROUTER_SMOKE=ok")
EOF
)
router_rc=$?
echo "$router_log" | tail -3
if [ "$router_rc" -ne 0 ]; then
    echo "ROUTER_SMOKE=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi

# Net smoke: the REAL wire (ISSUE-18 NET_SMOKE gate).  launch.py
# --roles forks genuinely separate OS processes that rendezvous over
# TCP and speak the length-prefixed frame protocol: the 2-process run
# must be token-for-token identical to the in-process virtual
# transport for the same seeded trace; a 4-process run with a seeded
# fault schedule armed at the SOCKET seam must finish every request
# with tokens exactly matching the fault-free virtual reference while
# wire faults demonstrably fired; and a single doctor invocation must
# merge all the per-rank artifact directories into one Cluster view.
net_dir=$(mktemp -d)
net_chaos_dir=$(mktemp -d)
net_rc=0
JAX_PLATFORMS=cpu python scripts/launch.py --cpu \
    --roles router:1,replica:1 --timeout 180 \
    scripts/cluster_worker.py --out "$net_dir" \
    --requests 5 --seed 13 >/dev/null 2>&1 || net_rc=1
JAX_PLATFORMS=cpu python scripts/launch.py --cpu \
    --roles router:1,prefill:1,replica:2 --timeout 180 \
    scripts/cluster_worker.py --out "$net_chaos_dir" \
    --requests 6 --seed 21 --chaos-seed 5 >/dev/null 2>&1 \
    || net_rc=1
net_log=$(JAX_PLATFORMS=cpu NET_DIR="$net_dir" \
    NET_CHAOS_DIR="$net_chaos_dir" python - <<'EOF' 2>&1
import json, os
import jax
from triton_distributed_tpu.observability import doctor
from triton_distributed_tpu.serving import (
    ClusterConfig, SchedulerConfig, ServingCluster, ToyConfig,
    ToyModel)
from triton_distributed_tpu.serving.cluster import RouterConfig
from triton_distributed_tpu.serving.cluster.net.fabric import (
    seeded_trace)

model = ToyModel(ToyConfig(vocab_size=61, hidden=16, max_seq_len=64))
params = model.init_params(jax.random.key(0))


def virtual(n_replicas, n_prefill, trace):
    """The in-process fault-free reference on the virtual clock —
    mirrors cluster_worker.py's config exactly."""
    sc = SchedulerConfig(num_slots=3, prefill_buckets=(8, 16, 32))
    cluster = ServingCluster(model, params, ClusterConfig(
        n_replicas=n_replicas, n_prefill_workers=n_prefill,
        scheduler=sc, router=RouterConfig(dead_after_s=5.0)))
    recs = [cluster.submit(p, n, seed=s) for p, n, s in trace]
    cluster.drain()
    return [list(r.tokens) for r in recs]


# 2-process socket run == in-process virtual run, token for token.
with open(os.path.join(os.environ["NET_DIR"], "results.json")) as f:
    got = json.load(f)
assert all(r["state"] == "finished" for r in got), got
assert [r["tokens"] for r in got] == virtual(
    1, 0, seeded_trace(13, 5)), "socket/virtual token divergence"

# Chaos at the socket seam: every request finished, tokens exact vs
# the fault-free reference, and wire faults really fired.
with open(os.path.join(os.environ["NET_CHAOS_DIR"],
                       "results.json")) as f:
    chaos = json.load(f)
assert all(r["state"] == "finished" for r in chaos), chaos
assert [r["tokens"] for r in chaos] == virtual(
    2, 1, seeded_trace(21, 6)), "chaos run perturbed tokens"
with open(os.path.join(os.environ["NET_CHAOS_DIR"], "rank-0",
                       "faults.jsonl")) as f:
    fired = {json.loads(ln)["fault"] for ln in f if ln.strip()}
assert fired & {"drop", "dup", "corrupt", "reorder"}, fired

# One doctor invocation merges the per-rank directories.
report = doctor.diagnose([os.environ["NET_CHAOS_DIR"]])
md = doctor.render_markdown(report)
assert md.count("## Cluster") == 1, md
assert report["chaos"]["count"] >= 1, report["chaos"]
assert report["lineage"]["events"] >= 1, report["lineage"]
print("NET_SMOKE=ok")
EOF
)
[ $? -ne 0 ] && net_rc=1
echo "$net_log" | tail -3
rm -rf "$net_dir" "$net_chaos_dir"
if [ "$net_rc" -ne 0 ]; then
    echo "NET_SMOKE=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi

# Chaos smoke: a seeded fault schedule (drop/dup/corrupt/reorder on
# the wire, a suppressed heartbeat) against the 2-replica + 1-worker
# virtual cluster — every request must finish token-for-token exact
# vs the single-engine scheduler, the retries/failover must be
# RECORDED (faults.jsonl schema-valid), and the doctor must render a
# "Chaos" section naming the fault classes from the artifact.
chaos_log=$(JAX_PLATFORMS=cpu python - <<'EOF' 2>&1
import tempfile
import jax
from triton_distributed_tpu.serving import (
    ClusterConfig, ContinuousBatchingScheduler, FaultInjector,
    FaultSchedule, Request, SchedulerConfig, ServingCluster,
    ToyConfig, ToyModel)
from triton_distributed_tpu.serving.cluster import (
    RouterConfig, load_faults, validate_fault)
from triton_distributed_tpu.observability.doctor import (
    diagnose, render_markdown)

model = ToyModel(ToyConfig(vocab_size=61, hidden=16, max_seq_len=64))
params = model.init_params(jax.random.key(0))
sc = SchedulerConfig(num_slots=2, prefill_buckets=(8, 16),
                     temperature=0.8, top_k=8)
trace = [dict(prompt=[1 + i, 2, 3], max_new_tokens=4 + (i % 3),
              seed=i, arrival_time=0.002 * i) for i in range(6)]

class Clock:
    t = 0.0
c = Clock()
sched = ContinuousBatchingScheduler(
    model, params, sc, clock=lambda: c.t,
    clock_advance=lambda dt: setattr(c, "t", c.t + dt))
ref = [r.generated for r in
       sorted(sched.run([Request(**t) for t in trace]),
              key=lambda r: r.request_id)]

d = tempfile.mkdtemp(prefix="tdt-chaos-")
inj = FaultInjector(FaultSchedule(
    7, classes=("drop", "dup", "corrupt", "reorder", "stale_hb"),
    ship_fault_rate=0.5, window_s=0.03))
cluster = ServingCluster(
    model, params,
    ClusterConfig(n_replicas=2, n_prefill_workers=1, scheduler=sc,
                  ship_retry_base_s=0.002, ship_deadline_s=0.1,
                  router=RouterConfig(dead_after_s=0.005,
                                      dead_checks=2,
                                      probation_checks=2),
                  artifact_dir=d),
    fault_injector=inj)
recs = [cluster.submit(**t) for t in trace]
done = cluster.drain()
assert len(done) == len(trace), [r.state for r in recs]
toks = [r.tokens for r in sorted(done, key=lambda r: r.record_id)]
assert toks == ref, "seeded faults changed a token stream"
assert inj.events, "schedule injected nothing"
cluster.write_artifact(d)
rows = load_faults(f"{d}/faults.jsonl")
assert rows, "faults.jsonl empty"
for row in rows:
    problems = validate_fault(row)
    assert not problems, (problems, row)
report = diagnose([d])
classes = set(report["chaos"]["by_class"])
assert classes == {e.fault for e in inj.events}, classes
assert "## Chaos" in render_markdown(report)
print("CHAOS_SMOKE=ok")
EOF
)
chaos_rc=$?
echo "$chaos_log" | tail -3
if [ "$chaos_rc" -ne 0 ]; then
    echo "CHAOS_SMOKE=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi

# Replay smoke: record a chaotic run (record_dir armed), re-execute
# it bit-exactly from replay.jsonl alone (EXACT at all three parity
# levels), then counterfactually suppress the first injected fault —
# the report must name that fault and the causality clause must
# render.  This is the deterministic-incident contract end-to-end.
replay_log=$(JAX_PLATFORMS=cpu python - <<'EOF' 2>&1
import tempfile
import jax
from triton_distributed_tpu.serving import (
    ClusterConfig, FaultInjector, FaultSchedule, SchedulerConfig,
    ServingCluster, ToyConfig, ToyModel)
from triton_distributed_tpu.serving.cluster import RouterConfig
from triton_distributed_tpu.observability.replay import (
    causality_clause, load_replay, replay_run)

model = ToyModel(ToyConfig(vocab_size=61, hidden=16, max_seq_len=64))
params = model.init_params(jax.random.PRNGKey(3))
d = tempfile.mkdtemp(prefix="tdt-replay-")
inj = FaultInjector(FaultSchedule(
    7, classes=("drop", "dup", "corrupt", "reorder", "stale_hb"),
    ship_fault_rate=0.5, window_s=0.03))
cluster = ServingCluster(
    model, params,
    ClusterConfig(n_replicas=2, n_prefill_workers=1,
                  scheduler=SchedulerConfig(
                      num_slots=2, prefill_buckets=(8, 16),
                      temperature=0.8, top_k=8),
                  ship_retry_base_s=0.002, ship_deadline_s=0.1,
                  router=RouterConfig(dead_after_s=0.005,
                                      dead_checks=2,
                                      probation_checks=2),
                  record_dir=d, record_params_seed=3),
    fault_injector=inj)
for i in range(6):
    cluster.submit([1 + i, 2, 3], 4 + (i % 3), seed=i)
done = cluster.drain()
assert len(done) == 6, [r.state for r in done]
assert inj.events, "schedule injected nothing"

report = replay_run(d, model=model, params=params)
assert report["status"] == "EXACT", report["first_divergence"]
for level, stats in report["levels"].items():
    assert stats["divergences"] == 0, (level, stats)
    assert stats["compared"] > 0, level

faults = [r for r in load_replay(d)
          if r.get("kind") == "fault_injected"]
cf = replay_run(d, model=model, params=params,
                override={"suppress_fault": int(faults[0]["index"])}
                )["counterfactual"]
assert cf["fault"]["fault"] == faults[0]["fault"], cf
clause = causality_clause(cf)
assert clause.startswith("without the "), clause
print("REPLAY_SMOKE=ok")
EOF
)
replay_rc=$?
echo "$replay_log" | tail -3
if [ "$replay_rc" -ne 0 ]; then
    echo "REPLAY_SMOKE=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi

# Lineage smoke: request lineage end-to-end on the virtual clock — a
# 2-replica + 1-prefill cluster run must write a schema-valid
# lineage.jsonl, every request's TTFT hop decomposition must sum
# EXACTLY to its measured TTFT (the asserted invariant), and the
# doctor must render a "Request lineage" section naming a dominant
# hop from the artifact alone.
lineage_log=$(JAX_PLATFORMS=cpu python - <<'EOF' 2>&1
import tempfile
import jax
from triton_distributed_tpu.observability.doctor import (
    diagnose, render_markdown)
from triton_distributed_tpu.observability.lineage import (
    get_lineage_recorder, load_lineage, ttft_breakdown,
    validate_lineage)
from triton_distributed_tpu.serving import (
    ClusterConfig, SchedulerConfig, ServingCluster, ToyConfig,
    ToyModel)

model = ToyModel(ToyConfig(vocab_size=61, hidden=16, max_seq_len=64))
params = model.init_params(jax.random.key(0))
get_lineage_recorder().clear()
cluster = ServingCluster(model, params, ClusterConfig(
    n_replicas=2, n_prefill_workers=1,
    scheduler=SchedulerConfig(num_slots=3,
                              prefill_buckets=(8, 16, 32))))
recs = [cluster.submit([1 + i, 2, 3, 4], 3 + (i % 3), seed=i,
                       arrival_time=0.001 * i) for i in range(8)]
done = cluster.drain()
assert len(done) == 8, [r.state for r in recs]

# Exact hop-sum on every request, against the cluster's own TTFT.
rec = get_lineage_recorder()
for r in done:
    bd = ttft_breakdown(rec.events_for(r.record_id),
                        arrival=r.arrival_time, measured_ttft=r.ttft)
    assert bd is not None and bd["exact"], (r.record_id, bd)

# Schema-valid artifact...
d = tempfile.mkdtemp(prefix="tdt-lineage-")
cluster.write_artifact(d)
rows = load_lineage(f"{d}/lineage.jsonl")
assert rows, "lineage.jsonl empty"
for row in rows:
    problems = validate_lineage(row)
    assert not problems, (problems, row)

# ...the doctor replays into a Request-lineage section + verdict.
report = diagnose([d])
lineage = report.get("lineage")
assert lineage and lineage["exact"], lineage
assert lineage["completed"] == 8, lineage
assert lineage["slowest"][0]["dominant_hop"], lineage
assert "## Request lineage" in render_markdown(report)
assert "hop '" in report["verdict"], report["verdict"]
print("LINEAGE_SMOKE=ok")
EOF
)
lineage_rc=$?
echo "$lineage_log" | tail -3
if [ "$lineage_rc" -ne 0 ]; then
    echo "LINEAGE_SMOKE=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi

# KV-tier smoke (ISSUE 15): the cluster-wide cache hierarchy end to
# end on the virtual clock — a prefix prefilled on replica A is
# served from replica B via a peer PREFIX shipment (real bytes + CRC
# on the wire) with zero second prefill, token-for-token identical to
# the single-engine scheduler; the per-tier hit counters render in
# the Prometheus export and the doctor renders a "KV tier" section
# from a heartbeat carrying the tier gauges.
kvtier_log=$(JAX_PLATFORMS=cpu python - <<'EOF' 2>&1
import json, os, tempfile
os.environ["TDT_ANOMALY_BASELINES"] = os.path.join(
    tempfile.mkdtemp(prefix="tdt-kvt-b-"), "baselines.json")
import jax
import numpy as np
from triton_distributed_tpu.observability import (
    feedback, get_registry, prometheus_text)
from triton_distributed_tpu.observability.anomaly import (
    WINDOW, BaselineStore)
from triton_distributed_tpu.observability.doctor import (
    diagnose, render_markdown)
from triton_distributed_tpu.observability.exporter import (
    heartbeat_payload)
from triton_distributed_tpu.serving import (
    ClusterConfig, ContinuousBatchingScheduler, Request,
    SchedulerConfig, ServingCluster, ToyConfig, ToyModel)
from triton_distributed_tpu.serving.cluster import RouterConfig
from triton_distributed_tpu.serving.scheduler import (
    prefill_baseline_key)

model = ToyModel(ToyConfig(vocab_size=61, hidden=16, max_seq_len=64))
params = model.init_params(jax.random.key(0))
rng = np.random.default_rng(7)
sysp = [int(x) for x in rng.integers(1, 61, 32)]  # 2 full KV pages
trace = [dict(prompt=sysp + [1 + i, 2 + i], max_new_tokens=3 + (i % 3),
              seed=i, arrival_time=0.0 if i == 0 else 0.004)
         for i in range(6)]
sc = SchedulerConfig(num_slots=2, prefill_buckets=(8, 16, 32, 64),
                     kv_layout="paged", page_size=16)

# single-engine reference (the exactness bar)
class Clock:
    t = 0.0
c = Clock()
sched = ContinuousBatchingScheduler(
    model, params, sc, clock=lambda: c.t,
    clock_advance=lambda dt: setattr(c, "t", c.t + dt))
ref = [r.generated for r in
       sorted(sched.run([Request(**t) for t in trace]),
              key=lambda r: r.request_id)]

# seeded prefill baseline + synthetic bus: the ship-vs-recompute
# model engages deterministically
store = BaselineStore(os.environ["TDT_ANOMALY_BASELINES"])
for b in (16, 32, 64):
    for _ in range(WINDOW):
        store.observe(prefill_baseline_key(b), 5000.0)
get_registry().clear()
feedback.clear_recent_decisions()
cluster = ServingCluster(model, params, ClusterConfig(
    n_replicas=2, scheduler=sc,
    router=RouterConfig(affinity_tokens=0),
    bus=feedback.synthetic_bus(store=store, ts=0.0,
                               clock=lambda: 0.0)))
recs = [cluster.submit(**t) for t in trace]
done = cluster.drain()
assert len(done) == 6, [r.state for r in recs]
toks = [r.tokens for r in sorted(done, key=lambda r: r.record_id)]
assert toks == ref, "peer prefix shipping changed a token stream"

snap = get_registry().snapshot()
assert snap["counters"]["cluster_prefix_ships_total"] >= 1
assert snap["counters"]['serving_kvtier_hit_total{tier="peer"}'] >= 1
# zero second prefill: the prefix was full-prefilled ONCE fleet-wide
miss = snap["counters"]["serving_prefix_cache_miss_tokens_total"]
assert miss == len(trace[0]["prompt"]) + 2 * (len(trace) - 1), miss
assert len({r.replica_history[0] for r in recs}) == 2
assert any(d.consumer == "cluster.kv_fetch" and d.choice == "peer_ship"
           for d in feedback.recent_decisions())

text = prometheus_text()
for needle in ('serving_kvtier_hit_total{tier="device"}',
               'serving_kvtier_hit_total{tier="peer"}',
               "cluster_prefix_ships_total",
               "serving_kvtier_hit_peer"):
    assert needle in text, needle

# doctor: a heartbeat carrying the tier gauges yields a KV-tier table
d = tempfile.mkdtemp(prefix="tdt-kvt-")
hb = heartbeat_payload()
assert "serving_kvtier_hit_peer" in hb["serving"], hb["serving"]
with open(os.path.join(d, "heartbeat-rank-0.json"), "w") as f:
    json.dump(hb, f)
report = diagnose([d])
assert report.get("kvtier"), report.get("kvtier")
assert report["kvtier"][0]["hits"]["peer"] >= 1
assert "## KV tier" in render_markdown(report)
print("KVTIER_SMOKE=ok")
EOF
)
kvtier_rc=$?
echo "$kvtier_log" | tail -3
if [ "$kvtier_rc" -ne 0 ]; then
    echo "KVTIER_SMOKE=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi

# MoE smoke (ISSUE 14): (a) the ragged-packed plan's combine is exact
# vs the gather-based staged reference on CPU; (b) the fused
# combine-in-epilogue kernel itself runs fused-vs-staged bit-close in
# CPU interpret mode on a small shape where this jax can execute
# Pallas TPU interpret kernels (skips gracefully where it cannot —
# the same availability gating as the kernel test suite); then (c)
# the resource + comm sanitizer sweeps of all four moe_reduce_rs
# kernel variants must report ZERO findings.
moe_log=$(JAX_PLATFORMS=cpu python - <<'EOF' 2>&1
import functools
import jax
import jax.numpy as jnp
import numpy as np
from triton_distributed_tpu.kernels import moe_utils

world, mc, e, topk, cap, k, n, h = 1, 32, 4, 2, 16, 128, 128, 16
key = jax.random.key(14)
ids = jax.random.randint(key, (world * mc, topk), 0, e)
w = jax.nn.softmax(jax.random.normal(
    jax.random.fold_in(key, 1), (world * mc, topk)), axis=-1)
plan = moe_utils.plan_chunks(ids, w, world, e, cap)

# (a) packed plan ≡ gather-based staged combine (pure XLA, runs
# anywhere).
eo = jax.random.normal(jax.random.fold_in(key, 2), (e, cap, h))
golden = moe_utils.combine_tokens(eo, ids, plan.slot_of_pair[0], w)
dense = moe_utils.dense_combine_mats(plan, cap)
got = jnp.einsum("emc,ech->mh", dense[0], eo).astype(golden.dtype)
assert float(jnp.abs(got - golden).max()) < 1e-5, "packed plan drift"
print("MOE_PLAN_EXACT=ok")

# (b) interpret-mode fused-vs-staged kernel exactness, where the
# Pallas interpret stack exists in this jax.
try:
    from triton_distributed_tpu.kernels.matmul import MatmulConfig
    from triton_distributed_tpu.kernels.moe_reduce_rs import (
        MoEReduceRSContext, moe_reduce_rs_fused)
    from jax.sharding import Mesh, PartitionSpec as P
    smap = functools.partial(jax.shard_map, check_vma=False)
    buckets = jax.random.normal(jax.random.fold_in(key, 3),
                                (world, e, cap, k), jnp.float32) / 8
    wdown = jax.random.normal(jax.random.fold_in(key, 4), (e, k, n),
                              jnp.float32) / 8
    ctx = MoEReduceRSContext(axis="tp", world_size=world,
                             num_experts=e, topk=topk,
                             gemm=MatmulConfig(16, 128, 128))
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    fused = smap(lambda b, ww: moe_reduce_rs_fused(b, ww, plan, ctx),
                 mesh=mesh, in_specs=(P(), P()), out_specs=P())
    out = jax.jit(fused)(buckets, wdown)
    part = jnp.einsum("wecK,eKn->wecn", buckets, wdown)
    ref = moe_utils.combine_tokens(part[0], ids, plan.slot_of_pair[0],
                                   w).astype(out.dtype)
    err = float(jnp.abs(out - ref).max())
    assert err < 1e-4, f"fused != staged in interpret mode ({err})"
    print("MOE_KERNEL_EXACT=ok")
except (AttributeError, NotImplementedError, TypeError) as exc:
    print(f"MOE_KERNEL_EXACT=skipped (pallas interpret unavailable: "
          f"{type(exc).__name__})")
print("MOE_SMOKE=ok")
EOF
)
moe_rc=$?
echo "$moe_log" | tail -3
if [ "$moe_rc" -ne 0 ]; then
    echo "MOE_SMOKE=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi
moe_sweep_ok=1
for check in comm resources; do
    if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
            python -m triton_distributed_tpu.analysis --check $check \
            -k moe_reduce_rs.fused -k moe_reduce_rs.two_phase \
            -k moe_reduce_rs.w8a8 -k moe_reduce_rs.w8a8_two_phase \
            -q; then
        moe_sweep_ok=0
    fi
done
if [ "$moe_sweep_ok" -eq 1 ]; then
    echo "MOE_SWEEP=ok"
else
    echo "MOE_SWEEP=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi

# SLO smoke (ISSUE 16): the error-budget + cost observatory end to
# end on the virtual clock — a 2-class SLOPolicy over mixed-tenant
# traffic must fire a burn alert as a schema-valid DecisionEvent
# naming the dominant tenant, cost vectors must balance EXACTLY
# (rational arithmetic), write_artifact must land slo-state.json +
# timeseries-rank-0.jsonl + cost-joined lineage.jsonl, the doctor
# must render "SLO" and "Time series" sections with the burning
# class in the verdict, and the capacity planner must answer
# "2 replicas" bit-exactly across two full runs.
slo_log=$(JAX_PLATFORMS=cpu python - <<'EOF' 2>&1
import dataclasses, json, os, tempfile
import jax
from triton_distributed_tpu.observability import (
    SLOClass, SLOPolicy, feedback, get_cost_recorder, get_registry,
    load_timeseries, set_cost_accounting, validate_decision,
    validate_timeseries)
from triton_distributed_tpu.observability.doctor import (
    diagnose, render_markdown)
from triton_distributed_tpu.observability.lineage import (
    get_lineage_recorder, load_lineage_costs)
from triton_distributed_tpu.serving import (
    ClusterConfig, SchedulerConfig, ServingCluster, ToyConfig,
    ToyModel)

model = ToyModel(ToyConfig(vocab_size=61, hidden=16, max_seq_len=64))
params = model.init_params(jax.random.key(0))
get_registry().clear()
get_lineage_recorder().clear()
feedback.clear_recent_decisions()
set_cost_accounting(False)
get_cost_recorder().clear()

# Impossible interactive targets on the virtual clock: every web
# request breaches, the multi-window burn rule must trip mid-drain.
policy = SLOPolicy(
    classes=(SLOClass("interactive", ttft_p99_ms=1e-6,
                      tbt_p99_ms=1e-6, objective=0.9),
             SLOClass("batch", ttft_p99_ms=1e6, tbt_p99_ms=1e6,
                      objective=0.9)),
    tenant_class={"web": "interactive", "bulk": "batch"},
    windows=(0.05, 0.2), burn_alert_threshold=2.0)
cluster = ServingCluster(model, params, ClusterConfig(
    n_replicas=2,
    scheduler=SchedulerConfig(num_slots=2, prefill_buckets=(8, 16)),
    step_time_s=1e-3, prefill_time_s=2e-3,
    slo_policy=policy, timeseries_interval_s=2e-3))
for i, tenant in enumerate(["web", "web", "bulk", "web", "bulk",
                            "web"]):
    cluster.submit([1 + i, 2, 3, 4], 4 + (i % 2), seed=i,
                   arrival_time=0.0, tenant=tenant)
done = cluster.drain()
assert len(done) == 6, [r.state for r in done]

# One edge-triggered, schema-valid burn alert naming the tenant.
alerts = [d for d in feedback.recent_decisions()
          if d.consumer == "slo.burn_alert"]
assert [a.op for a in alerts] == ["class:interactive"], alerts
row = dataclasses.asdict(alerts[0])
problems = validate_decision(row)
assert not problems, (problems, row)
assert row["inputs"]["dominant_tenant"] == "web", row["inputs"]

# Exact cost balance + the per-tenant bill.
bal = get_cost_recorder().balance()
assert bal["exact"] is True, bal
totals = get_cost_recorder().tenant_totals()
assert set(totals) == {"web", "bulk"}, set(totals)

# Artifacts: slo-state + timeseries + cost-joined lineage.
d = tempfile.mkdtemp(prefix="tdt-slo-")
cluster.write_artifact(d)
state = json.loads(open(os.path.join(d, "slo-state.json")).read())
assert state["classes"]["interactive"]["alerting"] is True, state
assert state["tenant_costs"]["web"]["device_us"] > 0, state
ts_rows = load_timeseries(os.path.join(d, "timeseries-rank-0.jsonl"))
assert len(ts_rows) >= 2, len(ts_rows)
for r in ts_rows:
    assert validate_timeseries(r) == [], r
cost_rows = load_lineage_costs(os.path.join(d, "lineage.jsonl"))
assert cost_rows, "no cost rows joined onto lineage.jsonl"

# Doctor: SLO + Time series sections, burning class in the verdict.
report = diagnose([d])
assert report["slo"]["burning"] == ["interactive"], report["slo"]
assert report["slo"]["dominant_tenant"] == "web", report["slo"]
md = render_markdown(report)
assert "## SLO" in md and "## Time series" in md
assert "interactive" in report["verdict"], report["verdict"]

# Planner: the committed question — smallest fleet holding the SLO
# at 1x traffic — answers "2 replicas", bit-exactly, twice.
set_cost_accounting(False)
get_cost_recorder().clear()
from triton_distributed_tpu.observability.planner import plan
kw = dict(replicas_max=3, rates=(1.0,), n_requests=24, seed=1234)
first = plan(model, params, **kw)
again = plan(model, params, **kw)
assert (json.dumps(first, sort_keys=True)
        == json.dumps(again, sort_keys=True)), "planner nondeterminism"
rate = first["rates"][0]
assert rate["min_replicas"] == 2, rate["min_replicas"]
assert rate["deterministic"] is True, rate
print("SLO_SMOKE=ok")
EOF
)
slo_rc=$?
echo "$slo_log" | tail -3
if [ "$slo_rc" -ne 0 ]; then
    echo "SLO_SMOKE=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi

# Telemetry smoke: the fleet telemetry plane end-to-end in-process —
# a 2-replica virtual cluster with the plane armed must fold frames
# from every source into the front door's collector, render the
# fleet-labeled Prometheus exposition and the /fleet status body, a
# seeded SLO-burn frame must fire EXACTLY one edge-triggered alert
# and clear on the falling edge, the watch CLI's --once render over
# the written artifacts must be byte-stable, and the doctor must
# pick the artifacts up into a "Fleet alerts" section with the
# firing rule in the verdict.
telemetry_log=$(JAX_PLATFORMS=cpu python - <<'EOF' 2>&1
import json, os, tempfile
import jax
from triton_distributed_tpu.observability import feedback
from triton_distributed_tpu.observability.doctor import (
    diagnose, render_markdown)
from triton_distributed_tpu.observability.lineage import (
    get_lineage_recorder)
from triton_distributed_tpu.observability.metrics import get_registry
from triton_distributed_tpu.observability.telemetry import (
    AlertEngine, FleetCollector, fleet_prometheus, fleet_status,
    validate_alert, validate_telemetry)
from triton_distributed_tpu.observability.watch import snapshot_once
from triton_distributed_tpu.serving import (
    ClusterConfig, SchedulerConfig, ServingCluster, ToyConfig,
    ToyModel)

get_registry().clear()
get_lineage_recorder().clear()
feedback.clear_recent_decisions()

model = ToyModel(ToyConfig(vocab_size=61, hidden=16, max_seq_len=64))
params = model.init_params(jax.random.key(0))
cluster = ServingCluster(model, params, ClusterConfig(
    n_replicas=2,
    scheduler=SchedulerConfig(num_slots=2, prefill_buckets=(8, 16)),
    telemetry_interval_s=0.25))
for i in range(6):
    cluster.submit([1 + i, 2, 3, 4], 4 + (i % 2), seed=i,
                   arrival_time=0.0)
done = cluster.drain()
assert len(done) == 6, [r.state for r in done]

# Every local source folded into the front door's collector.
fleet = cluster.fleet
assert fleet is not None and fleet.collector.folded > 0
assert fleet.collector.sources() == [
    "replica-0", "replica-1", "router-0"], fleet.collector.sources()
for f in fleet.frames:
    validate_telemetry(f)

# The aggregated /fleet body + fleet-labeled Prometheus exposition.
status = fleet_status()
assert status["fleet"] is not None, status
assert len(status["fleet"]["table"]) == 3, status["fleet"]
prom = fleet_prometheus()
assert prom and 'src="replica-0"' in prom, prom[:400]

# Seeded burn: one edge-triggered alert, silent while held, cleared
# on the falling edge.
c2 = FleetCollector()
eng = AlertEngine()
def burn_frame(seq, ts, burn):
    return {"schema": 1, "kind": "telemetry", "ts": ts,
            "src": {"rank": 1, "role": "replica", "index": 0},
            "seq": seq, "full": seq == 0,
            "counters": {}, "histograms": {},
            "gauges": {"serving_slo_burn_max": burn}}
c2.fold(burn_frame(0, 0.5, 5.0))
fired = eng.evaluate(1.0, c2)
assert [e["rule"] for e in fired] == ["slo_burn"], fired
assert eng.evaluate(1.5, c2) == []
c2.fold(burn_frame(1, 2.0, 0.1))
cleared = eng.evaluate(2.5, c2)
assert [e["state"] for e in cleared] == ["cleared"], cleared
for e in eng.events:
    validate_alert(e)

# Artifacts -> byte-stable watch render -> doctor section.
d = tempfile.mkdtemp(prefix="tdt-telemetry-")
fleet.write_artifacts(d)
from triton_distributed_tpu.observability.telemetry import (
    write_alerts_artifact, write_telemetry_artifact)
write_telemetry_artifact(d, [burn_frame(0, 0.5, 5.0)], rank=7)
write_alerts_artifact(d, eng.events)
screen = snapshot_once([d])
assert screen == snapshot_once([d])
assert "replica-0" in screen and "router-0" in screen, screen
report = diagnose([d])
assert report["fleet"]["frames"] > 0, report.get("fleet")
md = render_markdown(report)
assert "## Fleet alerts" in md
print("TELEMETRY_SMOKE=ok")
EOF
)
telemetry_rc=$?
echo "$telemetry_log" | tail -3
if [ "$telemetry_rc" -ne 0 ]; then
    echo "TELEMETRY_SMOKE=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi

# Telemetry bench gate: the paired plane-off/plane-on serving trace
# must hold EXACT token parity with bounded overhead and a
# non-empty plane.
if JAX_PLATFORMS=cpu python benchmark/bench_telemetry.py \
        --out /tmp/_t1_telemetry.json > /dev/null \
   && python scripts/check_bench_regression.py \
        --fresh /tmp/_t1_telemetry.json \
        --baselines /tmp/_t1_nonexistent_baselines.json > /dev/null
then
    echo "TELEMETRY_BENCH=ok"
else
    echo "TELEMETRY_BENCH=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi

# Planner bench gate: the capacity-planner sweep is deterministic
# model output — re-run it and require every plan row feasible AND
# deterministic, every cell compliance in [0, 1].
if JAX_PLATFORMS=cpu python benchmark/bench_planner.py \
        --out /tmp/_t1_planner.json > /dev/null \
   && python scripts/check_bench_regression.py \
        --fresh /tmp/_t1_planner.json \
        --baselines /tmp/_t1_nonexistent_baselines.json > /dev/null
then
    echo "PLANNER_BENCH=ok"
else
    echo "PLANNER_BENCH=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi

# Router bench gate: the virtual-clock router bench is deterministic
# — re-run it and require every paired summary to hold (signal-aware
# beats round-robin under seeded imbalance, matches it balanced).
if JAX_PLATFORMS=cpu python benchmark/bench_router.py \
        --out /tmp/_t1_router.json > /dev/null \
   && python scripts/check_bench_regression.py \
        --fresh /tmp/_t1_router.json \
        --baselines /tmp/_t1_nonexistent_baselines.json > /dev/null
then
    echo "ROUTER_BENCH=ok"
else
    echo "ROUTER_BENCH=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi

# Closed-loop bench gate: the paired static-vs-closed-loop bench is
# deterministic model output — re-run it and require (1) the
# bus-disabled (static) rows EXACTLY match the committed results and
# (2) every recorded flip wins under its own ground truth.
if JAX_PLATFORMS=cpu python benchmark/bench_closed_loop.py \
        --out /tmp/_t1_closed_loop.json > /dev/null \
   && python scripts/check_bench_regression.py \
        --fresh /tmp/_t1_closed_loop.json \
        --baselines /tmp/_t1_nonexistent_baselines.json > /dev/null
then
    echo "CLOSED_LOOP_BENCH=ok"
else
    echo "CLOSED_LOOP_BENCH=FAILED"
    [ "$rc" -eq 0 ] && rc=1
fi

exit $rc
