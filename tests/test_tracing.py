"""Runtime-observability tests: span tracer semantics (nesting,
threading, ring cap, disabled path), Chrome-trace export validity,
cross-rank timeline merge + skew/straggler attribution on synthetic
traces, heartbeat freshness, Prometheus exposition, flight-dump span
forensics, and two real 2-process `scripts/launch.py` runs — a happy
path whose per-rank traces must merge into one valid timeline, and a
forced hang whose `--timeout` exit must name the stalled rank and its
last span."""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time
import urllib.request

import jax.numpy as jnp
import pytest

from triton_distributed_tpu.observability import (
    KernelEvent,
    MetricsRegistry,
    get_tracer,
    prometheus_text,
    rank_health_report,
    format_rank_health,
    span,
    start_metrics_server,
    traced,
)
from triton_distributed_tpu.observability.exporter import (
    HeartbeatWriter,
    heartbeat_path,
)
from triton_distributed_tpu.observability.recorder import FlightRecorder
from triton_distributed_tpu.observability.timeline import (
    MERGED_NAME,
    REPORT_NAME,
    main as timeline_main,
    merge_traces,
    skew_rows,
    straggler_report,
)
from triton_distributed_tpu.observability.tracing import (
    NULL_SPAN,
    SpanTracer,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_global_tracer_and_recorder():
    """Every test starts from an empty process tracer and flight ring:
    spans or events an earlier test on this worker left behind must
    not decide this one."""
    from triton_distributed_tpu.observability import get_flight_recorder
    get_tracer().clear()
    get_flight_recorder().clear()
    yield


# ---------------------------------------------------------------------------
# Span tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_attrs():
    tr = SpanTracer(capacity=16)
    with tr.span("outer", phase="p") as outer:
        assert outer.depth == 0
        assert [s.name for s in tr.open_spans()] == ["outer"]
        with tr.span("inner") as inner:
            assert inner.depth == 1
            assert tr.last_span().name == "inner"
        assert tr.last_span().name == "outer"
    done = tr.finished()
    assert [s.name for s in done] == ["inner", "outer"]  # close order
    assert done[1].attrs == {"phase": "p"}
    assert done[0].dur >= 0 and done[0].ts <= done[1].ts + done[1].dur
    assert tr.open_spans() == []


def test_span_ring_is_bounded():
    tr = SpanTracer(capacity=4)
    for i in range(9):
        with tr.span(f"s{i}"):
            pass
    assert len(tr) == 4
    assert [s.name for s in tr.finished()] == ["s5", "s6", "s7", "s8"]


def test_span_records_exceptions():
    tr = SpanTracer(capacity=4)
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    (s,) = tr.finished()
    assert s.attrs["error"] == "'RuntimeError'" or "RuntimeError" in str(
        s.attrs["error"])
    assert s.dur is not None


def test_span_disabled_is_allocation_free(monkeypatch):
    monkeypatch.setenv("TDT_OBSERVABILITY", "0")
    before = len(get_tracer())
    # The disabled path hands back ONE shared object: no Span, no
    # ring append, no lock.
    assert span("a") is span("b") is NULL_SPAN
    with span("c", k=1):
        pass
    assert len(get_tracer()) == before


def test_traced_decorator():
    tr = get_tracer()

    @traced(name="unit.work")
    def work(x):
        return x + 1

    assert work(1) == 2
    assert any(s.name == "unit.work" for s in tr.finished())


def test_span_threading():
    tr = SpanTracer(capacity=64)
    barrier = threading.Barrier(4)

    def worker(i):
        barrier.wait()
        with tr.span("thread.outer", idx=i):
            with tr.span("thread.inner", idx=i):
                time.sleep(0.005)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    done = tr.finished()
    assert len(done) == 8
    inners = [s for s in done if s.name == "thread.inner"]
    assert len({s.tid for s in inners}) == 4       # one per thread
    assert all(s.depth == 1 for s in inners)       # nesting per-thread
    assert tr.open_spans() == []


# ---------------------------------------------------------------------------
# Chrome-trace export
# ---------------------------------------------------------------------------

def test_chrome_trace_export_is_valid(tmp_path, monkeypatch):
    monkeypatch.setenv("TDT_PROCESS_ID", "3")
    tr = SpanTracer(capacity=16)
    with tr.span("phase.a", step=1):
        time.sleep(0.001)
    open_span = tr.span("phase.open")
    open_span.__enter__()
    try:
        path = str(tmp_path / "trace-rank-3.json")
        assert tr.export_chrome_trace(path) == path
        trace = json.load(open(path))     # valid JSON on disk
    finally:
        open_span.__exit__(None, None, None)
    assert trace["metadata"]["rank"] == 3
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"phase.a", "phase.open"}
    for e in xs:
        assert e["pid"] == 3
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["dur"] >= 0
    (still_open,) = [e for e in xs if e["name"] == "phase.open"]
    assert still_open["args"]["open"] is True
    # Metadata lanes for Perfetto.
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in trace["traceEvents"])
    # No armed dir and no explicit path -> nowhere to write.
    monkeypatch.delenv("TDT_TRACE_DIR", raising=False)
    assert tr.export_chrome_trace() is None


# ---------------------------------------------------------------------------
# Timeline merge / skew / straggler (synthetic traces)
# ---------------------------------------------------------------------------

def _mk_trace(rank, starts, name="train.step", dur=50.0):
    evs = [{"name": name, "ph": "X", "cat": "span", "ts": t,
            "dur": dur, "pid": rank, "tid": 1, "args": {}}
           for t in starts]
    return {"traceEvents": evs, "metadata": {"rank": rank}}


def test_timeline_skew_and_straggler():
    tr0 = _mk_trace(0, [1000.0, 2000.0, 3000.0])
    tr1 = _mk_trace(1, [1100.0, 2200.0, 3050.0])
    rows = skew_rows([tr0, tr1])
    assert [r["skew_us"] for r in rows] == [100.0, 200.0, 50.0]
    assert all(r["last_rank"] == 1 for r in rows)

    report = straggler_report([tr0, tr1])
    agg = report["spans"]["train.step"]
    assert agg["straggler_rank"] == 1
    assert agg["straggler_fraction"] == 1.0
    assert agg["occurrences"] == 3
    assert agg["max_skew_us"] == 200.0
    assert agg["mean_skew_us"] == pytest.approx(350.0 / 3, abs=1e-3)
    # Rank 0 waited for rank 1 at every barrier: 100+200+50.
    assert agg["barrier_wait_us"]["0"] == pytest.approx(350.0)
    json.dumps(report)  # report is JSON-serialisable as-is

    # A span seen on one rank only contributes nothing.
    solo = _mk_trace(0, [1.0], name="solo")
    assert "solo" not in straggler_report([tr0, tr1, solo])["spans"]


def test_timeline_merge_rebases_clock():
    tr0 = _mk_trace(0, [5000.0])
    tr1 = _mk_trace(1, [5100.0])
    merged = merge_traces([tr0, tr1])
    xs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert min(e["ts"] for e in xs) == 0.0
    assert {e["pid"] for e in xs} == {0, 1}
    assert merged["metadata"]["t0_unix_us"] == 5000.0
    assert merged["metadata"]["ranks"] == [0, 1]
    names = [e for e in merged["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"]
    assert {e["args"]["name"] for e in names} == {"rank 0", "rank 1"}


def test_timeline_cli_merges_directory(tmp_path, capsys):
    for rank, starts in ((0, [10.0, 20.0]), (1, [15.0, 26.0])):
        with open(tmp_path / f"trace-rank-{rank}.json", "w") as f:
            json.dump(_mk_trace(rank, starts), f)
    assert timeline_main([str(tmp_path), "--report"]) == 0
    out = capsys.readouterr().out
    assert "straggler=rank 1" in out
    merged = json.load(open(tmp_path / MERGED_NAME))
    assert {e["pid"] for e in merged["traceEvents"]
            if e.get("ph") == "X"} == {0, 1}
    report = json.load(open(tmp_path / REPORT_NAME))
    assert report["spans"]["train.step"]["straggler_rank"] == 1
    # Empty dir: a clean error, not a stack trace.
    empty = tmp_path / "empty"
    empty.mkdir()
    assert timeline_main([str(empty)]) == 2


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------

def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("c_total", op="ag").inc(2)
    reg.gauge("occ").set(1.5)
    h = reg.histogram("lat_us", op="x")
    for v in (1.0, 3.0, 100.0):
        h.observe(v)
    lines = prometheus_text(registry=reg).splitlines()
    assert "# TYPE c_total counter" in lines
    assert 'c_total{op="ag"} 2.0' in lines
    assert "occ 1.5" in lines
    # po2 buckets surface as cumulative Prometheus le= series:
    # 1.0 -> le=1.0, 3.0 -> le=4.0, 100.0 -> le=128.0.
    assert 'lat_us_bucket{op="x",le="1.0"} 1' in lines
    assert 'lat_us_bucket{op="x",le="4.0"} 2' in lines
    assert 'lat_us_bucket{op="x",le="128.0"} 3' in lines
    assert 'lat_us_bucket{op="x",le="+Inf"} 3' in lines
    assert 'lat_us_sum{op="x"} 104.0' in lines
    assert 'lat_us_count{op="x"} 3' in lines
    # One TYPE line per metric name, before its samples.
    assert sum(1 for l in lines
               if l == "# TYPE lat_us histogram") == 1


def test_metrics_server_serves_prometheus_and_health():
    reg = MetricsRegistry()
    reg.counter("served_total").inc()
    srv = start_metrics_server(0, registry=reg)
    try:
        url = f"http://127.0.0.1:{srv.port}"
        resp = urllib.request.urlopen(f"{url}/metrics", timeout=10)
        assert resp.status == 200
        assert "text/plain" in resp.headers["Content-Type"]
        body = resp.read().decode()
        assert "served_total 1.0" in body.splitlines()
        health = json.loads(urllib.request.urlopen(
            f"{url}/healthz", timeout=10).read())
        assert health["schema"] == 1 and "last_span" in health
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{url}/nope", timeout=10)
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Heartbeats
# ---------------------------------------------------------------------------

def test_heartbeat_freshness_and_stall_report(tmp_path):
    hb_dir = str(tmp_path)
    w = HeartbeatWriter(hb_dir, interval=0.05)
    with span("serving.decode", step=7):
        path = w.write_now()
    payload = json.load(open(path))
    assert payload["last_span"] == "serving.decode"
    assert payload["rank"] == 0
    assert abs(payload["unix_time"] - time.time()) < 5.0

    # A peer whose heartbeat stopped 60s ago reads as stalled.
    stale = dict(payload, rank=1, unix_time=payload["unix_time"] - 60,
                 last_span="dcn_collective.wait", step=3)
    with open(heartbeat_path(hb_dir, 1), "w") as f:
        json.dump(stale, f)
    report = rank_health_report(hb_dir, interval=1.0)
    assert report["stalest_rank"] == 1
    assert report["stalled_ranks"] == [1]
    assert report["ranks"][1]["last_span"] == "dcn_collective.wait"
    assert report["ranks"][0]["stale"] is False
    text = format_rank_health(report)
    assert "STALLED" in text and "dcn_collective.wait" in text

    # Background writer refreshes the file.
    w.start()
    time.sleep(0.2)
    w.stop()
    assert rank_health_report(hb_dir, interval=0.05)["ranks"][0][
        "age_s"] < 1.0


def test_maybe_start_exporters_tolerate_bad_env(monkeypatch):
    """Malformed opt-in env must never kill the rank at startup
    (these run inside initialize_distributed)."""
    from triton_distributed_tpu.observability.exporter import (
        maybe_start_heartbeat, maybe_start_metrics_server)

    monkeypatch.setenv("TDT_METRICS_PORT", "")
    assert maybe_start_metrics_server() is None
    monkeypatch.setenv("TDT_METRICS_PORT", "auto")
    assert maybe_start_metrics_server() is None
    monkeypatch.delenv("TDT_HEARTBEAT_DIR", raising=False)
    assert maybe_start_heartbeat() is None


def test_launcher_health_lines_do_not_blame_fresh_ranks(tmp_path):
    """The watchdog must not pin a hang on a healthy rank: when every
    heartbeat is fresh it reports facts, naming a STALLED rank only
    when one actually stopped beating."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_launch_under_test", os.path.join(REPO, "scripts",
                                           "launch.py"))
    launch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(launch)

    now = time.time()
    for rank, age in ((0, 0.1), (1, 0.4)):
        with open(tmp_path / f"heartbeat-rank-{rank}.json", "w") as f:
            json.dump({"rank": rank, "unix_time": now - age,
                       "last_span": "train.step", "step": 2}, f)
    lines = "\n".join(launch._rank_health_lines(str(tmp_path)))
    assert "watchdog: stalled rank" not in lines
    assert "STALLED" not in lines
    assert "all heartbeats fresh" in lines

    # Rank 1 stops beating -> it (and only it) is the verdict.
    with open(tmp_path / "heartbeat-rank-1.json", "w") as f:
        json.dump({"rank": 1, "unix_time": now - 60,
                   "last_span": "dcn.wait", "step": 2}, f)
    lines = "\n".join(launch._rank_health_lines(str(tmp_path)))
    assert "watchdog: stalled rank 1" in lines and "dcn.wait" in lines


# ---------------------------------------------------------------------------
# Flight-recorder forensics (satellite: dumps answer "what was this
# rank doing")
# ---------------------------------------------------------------------------

def test_flight_dump_includes_open_spans_and_heartbeat(tmp_path):
    fr = FlightRecorder(capacity=4)
    fr.record(KernelEvent(kind="collective", op="all_gather"))
    with span("engine.decode_step", step=11):
        path = fr.dump(str(tmp_path / "f.json"), reason="test")
    payload = json.load(open(path))
    assert "engine.decode_step" in [s["name"]
                                    for s in payload["open_spans"]]
    assert payload["heartbeat"]["last_span"] == "engine.decode_step"
    assert payload["heartbeat"]["open_spans"] == ["engine.decode_step"]


# ---------------------------------------------------------------------------
# group_profile (satellite: rank-aware; a broken profiler raises)
# ---------------------------------------------------------------------------

def test_group_profile_rank_aware_and_raises(tmp_path, monkeypatch):
    from triton_distributed_tpu.utils import profiling

    # Multi-process: each rank writes its own subdirectory, no
    # collisions on a shared trace path.
    monkeypatch.setenv("TDT_NUM_PROCESSES", "2")
    monkeypatch.setenv("TDT_PROCESS_ID", "1")
    with profiling.group_profile("unit", trace_dir=str(tmp_path)):
        pass
    assert (tmp_path / "unit" / "rank-1").is_dir()

    # A profiler that cannot start is an error, not an untraced run.
    def broken(*a, **k):
        raise RuntimeError("profiler plugin unavailable")

    monkeypatch.setattr(profiling.jax.profiler, "start_trace", broken)
    ran = []
    with pytest.raises(RuntimeError, match="plugin unavailable"):
        with profiling.group_profile("unit2", trace_dir=str(tmp_path)):
            ran.append(1)
    assert ran == []

    # Single-process keeps the flat layout (back-compat).
    monkeypatch.undo()
    monkeypatch.setenv("TDT_NUM_PROCESSES", "1")
    with profiling.group_profile("flat", trace_dir=str(tmp_path)):
        pass
    assert (tmp_path / "flat").is_dir()
    assert not (tmp_path / "flat" / "rank-0").exists()


# ---------------------------------------------------------------------------
# Bench per-iteration percentiles (satellite: p50/p99, not just mean)
# ---------------------------------------------------------------------------

def test_bench_record_attaches_percentiles_and_histogram():
    from triton_distributed_tpu.observability import (
        bench_record, get_registry)

    reg = get_registry()
    before = reg.histogram("bench_iteration_us",
                           bench="ag_gemm").snapshot()["count"]
    rec = bench_record(
        {"bench": "ag_gemm", "world": 8, "M": 4096, "K": 7168,
         "N": 7168, "method": "fused", "us": 900.0,
         "samples_us": [850.0, 900.0, 950.0, 1200.0]},
        print_line=False)
    assert "samples_us" not in rec        # raw list consumed, not printed
    assert rec["p50_us"] == 900.0
    assert rec["p99_us"] == 1200.0        # tail, not mean
    h = reg.histogram("bench_iteration_us", bench="ag_gemm").snapshot()
    assert h["count"] == before + 4 and h["max"] == 1200.0
    json.dumps(rec)                       # still one JSON line


def test_percentile_nearest_rank():
    from triton_distributed_tpu.observability import percentile

    xs = list(range(1, 101))
    assert percentile(xs, 50) == 50
    assert percentile(xs, 99) == 99
    assert percentile(xs, 100) == 100
    assert percentile([7.0], 99) == 7.0
    with pytest.raises(ValueError):
        percentile([], 50)


# ---------------------------------------------------------------------------
# Autotuner trial spans
# ---------------------------------------------------------------------------

def test_autotuner_emits_trial_spans():
    from triton_distributed_tpu.autotuner import ContextualAutotuner

    tr = get_tracer()
    before = sum(1 for s in tr.finished()
                 if s.name == "autotune.trial")

    def op(a, *, config):
        return a * config

    tuner = ContextualAutotuner(op, [2.0, 3.0], iters=1, warmup=1)
    tuner(jnp.ones((4, 8)))
    trials = [s for s in tr.finished() if s.name == "autotune.trial"]
    assert len(trials) - before == 2
    assert {s.attrs["config"] for s in trials[-2:]} == {"2.0", "3.0"}


# ---------------------------------------------------------------------------
# Real 2-process launch.py --trace-dir runs
# ---------------------------------------------------------------------------

WORKER_TRACE = textwrap.dedent("""
    import os, sys, time
    from triton_distributed_tpu.observability import (
        maybe_install_trace_export, maybe_start_heartbeat, set_step,
        span)

    rank = int(os.environ["TDT_PROCESS_ID"])
    assert maybe_install_trace_export()
    assert maybe_start_heartbeat() is not None

    # File barrier: process spawn + import times differ by O(seconds),
    # which would swamp the deliberate skew below.
    ready = sys.argv[1]
    open(os.path.join(ready, f"r{rank}"), "w").close()
    for _ in range(2400):
        if all(os.path.exists(os.path.join(ready, f"r{i}"))
               for i in (0, 1)):
            break
        time.sleep(0.05)

    for step in range(3):
        set_step(step)
        if rank == 1:
            time.sleep(0.06)   # rank 1 is the deliberate straggler
        with span("train.step", step=step):
            with span("collective.all_gather"):
                time.sleep(0.01)
""")


def _run_launcher(extra_args, worker_src, tmp_path, env_extra=None,
                  worker_args=()):
    worker = tmp_path / "worker.py"
    worker.write_text(worker_src)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("TDT_OBSERVABILITY", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "launch.py"),
         "--nproc", "2", "--cpu", *extra_args, str(worker),
         *[str(a) for a in worker_args]],
        env=env, capture_output=True, text=True, timeout=300)


def test_launcher_trace_dir_merges_timeline(tmp_path):
    """Happy path: 2 ranks emit spans, exit cleanly; the launcher must
    leave per-rank traces, ONE valid merged Chrome trace, and a
    straggler report that names rank 1 (the deliberate laggard)."""
    trace_dir = tmp_path / "traces"
    res = _run_launcher(["--trace-dir", str(trace_dir)], WORKER_TRACE,
                        tmp_path, worker_args=[tmp_path])
    assert res.returncode == 0, (res.returncode, res.stdout, res.stderr)
    for rank in (0, 1):
        per_rank = json.load(open(trace_dir / f"trace-rank-{rank}.json"))
        assert per_rank["metadata"]["rank"] == rank
        assert any(e.get("name") == "train.step"
                   for e in per_rank["traceEvents"])
    merged = json.load(open(trace_dir / MERGED_NAME))
    xs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert {e["pid"] for e in xs} == {0, 1}
    assert {e["name"] for e in xs} >= {"train.step",
                                       "collective.all_gather"}
    report = json.load(open(trace_dir / REPORT_NAME))
    step = report["spans"]["train.step"]
    assert step["occurrences"] == 3
    assert step["straggler_rank"] == 1, (report, res.stderr)
    assert step["max_skew_us"] > 10_000        # >= one 60 ms delay
    # Heartbeats were written under the trace dir.
    assert (trace_dir / "heartbeats" / "heartbeat-rank-0.json").exists()


WORKER_STALL = textwrap.dedent("""
    import os, time
    from triton_distributed_tpu.observability import (
        maybe_install_flight_recorder, maybe_start_heartbeat, span)
    from triton_distributed_tpu.observability.lineage import (
        record_hop)

    rank = int(os.environ["TDT_PROCESS_ID"])
    maybe_install_flight_recorder()
    hb = maybe_start_heartbeat()
    assert hb is not None
    with span("warmup", rank=rank):
        time.sleep(0.05)
    if rank == 1:
        # Simulate a rank wedged inside a compiled collective: a span
        # left open and the heartbeat thread silenced (the real wedge
        # holds the GIL so the beat thread starves the same way).
        # A request admitted mid-decode rides along — the SIGTERM
        # flight dump must say which hop it was stuck in.
        record_hop(9001, "admit", time.time(), "replica-1", slot=0,
                   bucket=8, mode="local")
        ctx = span("dcn_collective.wait", step=3)
        ctx.__enter__()
        hb.write_now()
        hb.stop()
    time.sleep(600)
""")


def test_launcher_timeout_names_stalled_rank(tmp_path):
    """Forced hang: --timeout must still exit 124, and the watchdog
    must say WHICH rank stalled and what its last span was (read from
    heartbeats) instead of a bare timeout."""
    trace_dir = tmp_path / "traces"
    # 12 s watchdog: worker startup (interpreter + jax + distributed
    # init, x2 concurrently) can exceed 6 s on a loaded 2-core CI box,
    # and a watchdog that fires before the ranks arm their heartbeats
    # reports "no heartbeats" instead of the stalled rank.  Staleness
    # is relative to the 0.2 s interval, so the longer run only makes
    # rank 1's silence more clear-cut.
    res = _run_launcher(
        ["--trace-dir", str(trace_dir), "--timeout", "12"],
        WORKER_STALL, tmp_path,
        env_extra={"TDT_HEARTBEAT_INTERVAL": "0.2",
                   "TDT_FLIGHT_RECORDER": str(tmp_path / "flight")})
    assert res.returncode == 124, (res.returncode, res.stdout,
                                   res.stderr)
    assert "stalled rank 1" in res.stderr, res.stderr
    assert "dcn_collective.wait" in res.stderr, res.stderr
    # Rank 0 kept beating: reported healthy, with its own last span.
    assert "rank 0" in res.stderr and "'warmup'" in res.stderr
    # The stalled rank's SIGTERM flight dump names the hop each
    # in-flight request was stuck in (request-lineage satellite).
    dump = json.load(open(tmp_path / "flight" / "flight-rank-1.json"))
    stuck = dump["lineage"]
    assert [s["request_id"] for s in stuck] == [9001], stuck
    assert stuck[0]["hop"] == "admit"
    # The wedged rank's last heartbeat carried the same summary.
    hb = json.load(open(trace_dir / "heartbeats"
                        / "heartbeat-rank-1.json"))
    assert hb["lineage"][0]["hop"] == "admit"


# ---------------------------------------------------------------------------
# Cause, lifetimes and clocks (ISSUE 24)
# ---------------------------------------------------------------------------

def test_span_records_id_and_parent():
    tr = SpanTracer(capacity=16)
    with tr.span("step") as step:
        with tr.span("admit") as admit:
            with tr.span("admit.request") as one:
                pass
        with tr.span("dispatch") as dispatch:
            pass
    assert step.parent is None and step.depth == 0
    assert admit.parent == step.id and dispatch.parent == step.id
    assert one.parent == admit.id and one.depth == 2
    assert len({s.id for s in tr.finished()}) == 4
    d = one.to_dict()
    assert d["id"] == one.id and d["parent"] == admit.id
    ev = one.chrome_event(rank=0)
    assert ev["args"]["id"] == one.id
    assert ev["args"]["parent"] == admit.id


def test_self_time_is_duration_less_children():
    tr = SpanTracer(capacity=16)
    with tr.span("step") as step:
        with tr.span("a"):
            time.sleep(0.002)
        time.sleep(0.003)
        with tr.span("b"):
            time.sleep(0.002)
    kids = [s for s in tr.finished() if s.parent == step.id]
    assert [k.name for k in kids] == ["a", "b"]
    self_s = step.dur - sum(k.dur for k in kids)
    assert 0.003 <= self_s < step.dur - 0.004 + 1e-9
    # children lie inside their parent on the raw clock
    for k in kids:
        assert step.t0 <= k.t0 and k.t0 + k.dur <= step.t0 + step.dur


def test_detached_span_is_a_lifetime_not_a_frame(monkeypatch):
    from triton_distributed_tpu.observability import tracing
    entered = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracing, "_TraceAnnotation", Ann)
    tr = SpanTracer(capacity=16)
    with tr.span("step") as step:
        a = tr.detached("request", request_id=1)
        a.__enter__()
        b = tr.detached("request", request_id=2)
        b.__enter__()
        with tr.span("dispatch") as dispatch:
            pass
    # caused by the step, never a parent, never shown to the profiler
    assert a.parent == step.id and b.parent == step.id
    assert dispatch.parent == step.id
    assert entered == ["step", "dispatch"]
    assert {s.attrs["request_id"] for s in tr.open_spans()} == {1, 2}
    with tr.span("later") as later:
        a.__exit__(None, None, None)      # out of order: a before b
    assert later.parent is None and later.depth == 0
    assert [s.attrs["request_id"] for s in tr.open_spans()] == [2]
    b.__exit__(None, None, None)
    assert tr.open_spans() == []
    done = [s for s in tr.finished() if s.name == "request"]
    assert [s.attrs["request_id"] for s in done] == [1, 2]
    assert all(s.detached and s.dur is not None for s in done)
    # an open lifetime is exported as open
    c = tr.detached("request", request_id=3)
    c.__enter__()
    evs = [e for e in tr.chrome_trace()["traceEvents"]
           if e.get("name") == "request"]
    assert sum(bool(e["args"].get("open")) for e in evs) == 1
    c.__exit__(None, None, None)


def test_detached_disabled_is_the_shared_noop(monkeypatch):
    monkeypatch.setenv("TDT_OBSERVABILITY", "0")
    tr = SpanTracer(capacity=4)
    assert tr.detached("request") is NULL_SPAN


def test_monotonic_offset_places_spans_on_the_harness_clock():
    from triton_distributed_tpu.observability.tracing import (
        MONOTONIC_OFFSET)
    tr = SpanTracer(capacity=4)
    assert tr.monotonic_offset == MONOTONIC_OFFSET
    before = time.monotonic()
    with tr.span("x") as sp:
        pass
    after = time.monotonic()
    start = sp.t0 + tr.monotonic_offset
    assert before - 1e-3 <= start <= after + 1e-3
    assert start + sp.dur <= after + 1e-3


def test_a_recorded_span_is_over_and_under_what_was_open():
    """`SpanTracer.record`: a span the caller timed itself — in the
    ring with its own start and duration, caused by the span open on
    the thread, never opened, never shown to the profiler."""
    tr = SpanTracer(capacity=8)
    t0 = time.perf_counter()
    tr.record("loose", t0, 0.25, n=1)
    with tr.span("pages") as pages:
        tr.record("runtime.gc", t0 + 1.0, 0.5, generation=2)
        assert [s.name for s in tr.open_spans()] == ["pages"]
    loose, gc_, _ = tr.finished()
    assert (loose.parent, loose.dur, loose.attrs) == (None, 0.25, {"n": 1})
    assert gc_.parent == pages.id and gc_.depth == 1
    assert (gc_.t0, gc_.dur) == (t0 + 1.0, 0.5)
    assert gc_.chrome_event(rank=0)["dur"] == 0.5e6
    assert tr.open_spans() == []


@pytest.mark.parametrize("long", [True, False])
def test_a_long_collection_is_a_runtime_gc_span(monkeypatch, long):
    """One hook a process: a collection over the threshold leaves a
    `runtime.gc` span under whatever was open and an observation of
    `runtime_gc_pause_ms`; a short one leaves nothing."""
    import gc

    from triton_distributed_tpu.observability import (
        get_registry, get_tracer, tracing)
    tracing.install_gc_hook()
    hooks = len(gc.callbacks)
    tracing.install_gc_hook()
    assert len(gc.callbacks) == hooks
    monkeypatch.setattr(tracing, "GC_PAUSE_MIN_S",
                        0.0 if long else 3600.0)
    tr = get_tracer()
    tr.clear()
    get_registry().clear()
    with tr.span("serving.pages") as pages:
        gc.collect()
    found = [s for s in tr.finished() if s.name == "runtime.gc"]
    hist = get_registry().snapshot()["histograms"].get(
        "runtime_gc_pause_ms")
    if not long:
        assert found == [] and hist is None
        return
    (pause,) = found
    assert pause.parent == pages.id
    assert pause.attrs["generation"] == 2 and "collected" in pause.attrs
    assert pages.t0 <= pause.t0 and pause.dur <= pages.dur
    assert hist["count"] == 1
    assert hist["sum"] == pytest.approx(pause.dur * 1e3)
    monkeypatch.setenv("TDT_OBSERVABILITY", "0")
    tr.clear()
    gc.collect()
    assert tr.finished() == []


def test_ring_counts_what_it_drops():
    from triton_distributed_tpu.observability import get_registry
    c = get_registry().counter("trace_dropped_spans_total")
    c0 = c.value
    tr = SpanTracer(capacity=3)
    for i in range(3):
        with tr.span("s", i=i):
            pass
    assert tr.dropped == 0
    for i in range(2):
        with tr.span("s", i=3 + i):
            pass
    assert tr.dropped == 2 and c.value == c0 + 2
    assert [s.attrs["i"] for s in tr.finished()] == [2, 3, 4]
    tr.clear()
    assert tr.dropped == 0


def test_default_ring_holds_a_serving_run(monkeypatch):
    monkeypatch.delenv("TDT_TRACE_RING", raising=False)
    # ~2000 steps of 9 spans, with headroom
    assert SpanTracer().capacity >= 3 * 2000 * 9


def test_span_under_profiler_is_on_a_host_plane(tmp_path):
    """One clock: a span entered while `jax.profiler` traces reaches
    the xplane by name, stamped by the profiler itself; a detached
    span does not."""
    import glob

    import jax
    tr = SpanTracer(capacity=8)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tr.span("probe.step"):
            with tr.span("probe.sync"):
                jnp.ones(4).block_until_ready()
            life = tr.detached("probe.request")
            life.__enter__()
            life.__exit__(None, None, None)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert found
    data = jax.profiler.ProfileData.from_file(found[-1])
    seen = {}
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("probe."):
                    seen[e.name] = (plane.name, e.start_ns,
                                    e.duration_ns)
    assert set(seen) == {"probe.step", "probe.sync"}
    assert all(p.startswith("/host:") for p, _, _ in seen.values())
    (_, s0, d0), (_, s1, d1) = seen["probe.step"], seen["probe.sync"]
    assert s0 <= s1 and s1 + d1 <= s0 + d0       # nested on that clock


# ---------------------------------------------------------------------------
# Kernels carry a name into the jaxpr (and so into the device trace)
# ---------------------------------------------------------------------------

def _pallas_names(jaxpr):
    """`name` of every pallas_call in ``jaxpr``, sub-jaxprs included."""
    from jax._src import core
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for sub in core.jaxprs_in_params(eqn.params):
            out.extend(_pallas_names(sub))
    return out


@pytest.fixture(scope="module")
def serving_kernel_names():
    """Names of the Pallas kernels in the programs the scheduler runs
    for the tiny Qwen3 in fused mode at tp=4: one bucketed prefill and
    one paged decode step."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from triton_distributed_tpu.models import ModelConfig
    from triton_distributed_tpu.models.qwen import Qwen3
    mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))
    model = Qwen3(ModelConfig.tiny(dtype="float32"), mesh, mode="fused")
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    names = []
    row = jax.eval_shape(lambda: model.create_cache(1, max_seq=16))
    ids = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    names += _pallas_names(jax.make_jaxpr(model.make_prefill_fn())(
        params, ids, row).jaxpr)
    cache = jax.eval_shape(lambda: model.create_paged_cache(4, 9, 16, 8))
    toks = jax.ShapeDtypeStruct((4,), jnp.int32)
    names += _pallas_names(jax.make_jaxpr(
        model.make_paged_decode_fn(page_size=16))(
            params, toks, cache).jaxpr)
    return names


@pytest.mark.parametrize("prefix", [
    "flash_decode_paged", "flash_attention_fwd", "ag_gemm_", "gemm_rs_"])
def test_serving_path_kernels_are_named(serving_kernel_names, prefix):
    assert all(serving_kernel_names), serving_kernel_names
    assert any(n.startswith(prefix) for n in serving_kernel_names), (
        prefix, sorted(set(serving_kernel_names)))


@pytest.mark.parametrize("op,method,name", [
    ("ag_gemm", "ll", "ag_gemm_ll"),
    ("ag_gemm", "fused", "ag_gemm_ring"),
    ("gemm_rs", "ll", "gemm_rs_ll"),
    ("gemm_rs", "fused", "gemm_rs_fused"),
])
def test_overlap_gemm_kernels_are_named_by_method(tp4_mesh, op, method,
                                                  name):
    import functools

    import jax
    from jax.sharding import PartitionSpec as P

    from triton_distributed_tpu.kernels.allgather_gemm import (
        AllGatherGEMMContext, ag_gemm)
    from triton_distributed_tpu.kernels.gemm_reduce_scatter import (
        GEMMReduceScatterContext, gemm_rs)
    from triton_distributed_tpu.ops import shard_map_op
    if op == "ag_gemm":
        ctx = AllGatherGEMMContext(axis="tp", world_size=4,
                                   method=method)
        fn = shard_map_op(functools.partial(ag_gemm, ctx=ctx), tp4_mesh,
                          in_specs=(P("tp", None), P(None, "tp")),
                          out_specs=P(None, "tp"))
        shapes = ((64, 256), (256, 512))
    else:
        ctx = GEMMReduceScatterContext(axis="tp", world_size=4,
                                       method=method)
        fn = shard_map_op(functools.partial(gemm_rs, ctx=ctx), tp4_mesh,
                          in_specs=(P(None, "tp"), P("tp", None)),
                          out_specs=P("tp", None))
        shapes = ((64, 512), (512, 256))
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    assert _pallas_names(jax.make_jaxpr(fn)(*args).jaxpr) == [name]


def test_kernel_name_reaches_the_lowered_text():
    """The name is what the device trace shows: on the CPU the lowered
    (interpret-mode) program keeps it in its locations."""
    import jax

    from triton_distributed_tpu.kernels.flash_decode import (
        flash_decode_paged)
    S = jax.ShapeDtypeStruct
    args = (S((2, 4, 128), jnp.float32), S((8, 2, 16, 128), jnp.float32),
            S((8, 2, 16, 128), jnp.float32), S((2, 4), jnp.int32),
            S((2,), jnp.int32))
    jaxpr = jax.make_jaxpr(flash_decode_paged)(*args)
    assert _pallas_names(jaxpr.jaxpr) == ["flash_decode_paged"]
    assert "name=flash_decode_paged" in str(jaxpr)
