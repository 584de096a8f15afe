"""Observability subsystem tests: registry semantics, event-schema
round-trip, flight-recorder dump-on-signal (in-process and through a
real 2-process `scripts/launch.py` run), perf-model audit coverage for
AG/RS/AR/AG-GEMM, and kernel instrumentation byte counts."""

import json
import os
import signal
import subprocess
import sys
import textwrap
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.observability import (
    KernelEvent,
    MetricsRegistry,
    audit_events,
    bench_record,
    capture_events,
    emit_kernel_event,
    estimate_overlap_gemm_us,
    format_report,
    get_flight_recorder,
    get_registry,
    get_tracer,
    merge_snapshots,
)
from triton_distributed_tpu.observability.instrument import (
    collective_bytes_per_rank,
    estimate_collective_us,
)
from triton_distributed_tpu.observability.recorder import FlightRecorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


@pytest.fixture(autouse=True)
def _clean_global_tracer_and_recorder():
    """Every test starts from an empty flight ring and process tracer:
    a ring an earlier test on this worker filled (its length then no
    longer grows) or spans one left open must not decide this one."""
    get_flight_recorder().clear()
    get_tracer().clear()
    yield


def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", op="ag")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    # Same name+labels -> same object; different labels -> distinct.
    assert reg.counter("reqs_total", op="ag") is c
    assert reg.counter("reqs_total", op="rs") is not c

    g = reg.gauge("occ")
    g.set(0.5)
    g.inc(0.25)
    g.dec(0.5)
    assert abs(g.value - 0.25) < 1e-12

    h = reg.histogram("lat_us")
    for v in (1.0, 3.0, 100.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 3 and snap["min"] == 1.0
    assert snap["max"] == 100.0
    assert abs(snap["mean"] - 104.0 / 3) < 1e-9
    # Power-of-two buckets: 1 -> e=0, 3 -> e=2, 100 -> e=7.
    assert snap["buckets"] == {"0": 1, "2": 1, "7": 1}

    # A name registered as one kind cannot be reused as another.
    with pytest.raises(TypeError):
        reg.gauge("reqs_total", op="ag")  # noqa: M003

    full = reg.snapshot()
    assert full["counters"]['reqs_total{op="ag"}'] == 3.5
    assert "meta" in full and full["meta"]["schema"] == 1


def test_registry_export_and_merge(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc(2)  # noqa: M001
    reg.gauge("g").set(4.0)
    reg.histogram("h").observe(8.0)  # noqa: M002
    path = str(tmp_path / "metrics.json")
    reg.export(path)
    loaded = json.load(open(path))
    assert loaded["counters"]["c"] == 2

    other = {"counters": {"c": 3}, "gauges": {"g": 6.0},
             "histograms": {"h": {"count": 2, "sum": 6.0, "min": 2.0,
                                  "max": 4.0, "buckets": {"1": 1,
                                                          "2": 1}}}}
    merged = merge_snapshots([loaded, other])
    assert merged["counters"]["c"] == 5
    assert merged["gauges"]["g"] == {"min": 4.0, "max": 6.0,
                                     "sum": 10.0, "n": 2, "mean": 5.0}
    mh = merged["histograms"]["h"]
    assert mh["count"] == 3 and mh["min"] == 2.0 and mh["max"] == 8.0
    assert mh["buckets"] == {"1": 1, "2": 1, "3": 1}


# ---------------------------------------------------------------------------
# Event schema
# ---------------------------------------------------------------------------

def test_event_schema_round_trip():
    ev = KernelEvent(kind="collective", op="all_gather", method="ring",
                     axis="tp", world=8, shape=(64, 128),
                     dtype="bfloat16", bytes_moved=1 << 20,
                     flops=0, estimate_us=12.5, measured_us=25.0,
                     config="MatmulConfig(256,256,512)",
                     extra={"payload_bytes": 4096}, ts=1.0, rank=3)
    d = ev.to_dict()
    json.loads(json.dumps(d))          # JSON-serialisable
    back = KernelEvent.from_dict(d)
    assert back == ev
    assert back.deviation == 2.0
    # Unknown fields in a future record are ignored, not fatal.
    d2 = dict(d, some_future_field=1)
    assert KernelEvent.from_dict(d2) == ev


def test_emit_event_updates_registry_and_recorder():
    reg = get_registry()
    rec = get_flight_recorder()
    before = len(rec)
    c0 = reg.counter("events_total", kind="collective",
                     op="op_under_test").value
    with capture_events() as events:
        ev = emit_kernel_event("op_under_test", method="ring", world=4,
                               shape=(8, 128), dtype=jnp.float32,
                               bytes_moved=512, measured_us=3.0)
    assert events == [ev]
    assert ev.method == "ring" and ev.dtype == "float32"
    assert reg.counter("events_total", kind="collective",
                       op="op_under_test").value == c0 + 1
    assert reg.counter("bytes_moved_total",
                       op="op_under_test").value >= 512
    assert len(rec) == before + 1 and rec.events()[-1] is ev


def test_observability_opt_out(monkeypatch):
    monkeypatch.setenv("TDT_OBSERVABILITY", "0")
    with capture_events() as events:
        assert emit_kernel_event("nope", world=2) is None
    assert events == []


# ---------------------------------------------------------------------------
# Instrumentation byte counts + estimates (host-level, no shard_map)
# ---------------------------------------------------------------------------

def test_collective_byte_counts():
    shard = 64 * 128 * 4                      # (64, 128) f32 shard
    assert collective_bytes_per_rank("all_gather", shard, 8) == 7 * shard
    assert collective_bytes_per_rank("reduce_scatter", shard, 8) == 7 * shard
    assert collective_bytes_per_rank("all_gather", shard, 1) == 0
    nbytes = 1 << 20
    assert collective_bytes_per_rank(
        "all_reduce", nbytes, 8, "one_shot") == 7 * nbytes
    assert collective_bytes_per_rank(
        "all_reduce", nbytes, 8, "ring") == 2 * 7 * (nbytes // 8)
    assert collective_bytes_per_rank(
        "all_reduce", nbytes, 8, "chain") == 2 * nbytes


def test_collective_estimates_exist():
    for op, method in [("all_gather", "ring"), ("all_gather", "push_all"),
                       ("reduce_scatter", "scatter_reduce"),
                       ("all_reduce", "one_shot"),
                       ("all_reduce", "two_shot"),
                       ("all_reduce", "ring"), ("all_reduce", "chain")]:
        t = estimate_collective_us(op, 1 << 20, 8, method)
        assert t and t > 0, (op, method)
    assert estimate_collective_us("all_gather", 1 << 20, 1) is None
    # Torus model path.
    t = estimate_collective_us("all_gather_torus", 1 << 20, 16,
                               "torus", sizes=(4, 4))
    assert t and t > 0
    for method in ("fused", "ll", "xla"):
        t = estimate_overlap_gemm_us("ag_gemm", 512, 7168, 7168, 8,
                                     jnp.bfloat16, method)
        assert t and t > 0, method


def test_instrumented_kernel_emits_event_with_byte_counts():
    """Interpret-mode check: tracing the instrumented all_gather /
    gemm_rs entry points emits launch-metadata events whose byte
    counts match the shard sizes.  Entry points must run inside
    shard_map (axis_index), so this needs the full harness."""
    from jax.sharding import Mesh, PartitionSpec as P

    from triton_distributed_tpu.kernels.allgather import (
        AllGatherContext, AllGatherMethod, all_gather)
    from triton_distributed_tpu.ops import shard_map_op

    world, m, n = 4, 8, 128
    mesh = Mesh(np.array(jax.devices()[:world]), ("tp",))
    ctx = AllGatherContext(axis="tp", world_size=world,
                           method=AllGatherMethod.RING)
    x = jnp.zeros((world * m, n), jnp.float32)
    import functools
    fn = shard_map_op(functools.partial(all_gather, ctx=ctx), mesh,
                      in_specs=P("tp", None), out_specs=P(None, None))
    with capture_events() as events:
        jax.eval_shape(fn, x)          # trace only: no kernel run
    ags = [e for e in events if e.op == "all_gather"]
    assert len(ags) == 1
    ev = ags[0]
    shard_bytes = m * n * 4
    assert ev.method == "ring" and ev.world == world
    assert ev.bytes_moved == (world - 1) * shard_bytes
    assert ev.extra["payload_bytes"] == shard_bytes
    assert ev.estimate_us and ev.estimate_us > 0


# ---------------------------------------------------------------------------
# Perf-model audit
# ---------------------------------------------------------------------------

def test_perf_audit_covers_core_ops_and_flags_deviation():
    mk = lambda op, est, meas, **kw: KernelEvent(
        kind="collective", op=op, estimate_us=est, measured_us=meas,
        **kw)
    events = [
        mk("all_gather", 100.0, 120.0, method="ring", world=8),
        mk("reduce_scatter", 100.0, 90.0, method="ring", world=8),
        mk("all_reduce", 50.0, 40.0, method="two_shot", world=8),
        mk("ag_gemm", 500.0, 5000.0, method="fused", world=8),  # 10x!
        KernelEvent(kind="bench", op="no_estimate", measured_us=1.0),
    ]
    rows = audit_events(events, threshold=3.0)
    assert len(rows) == 4                      # no-estimate event skipped
    assert {r.op for r in rows} == {"all_gather", "reduce_scatter",
                                    "all_reduce", "ag_gemm"}
    flagged = [r for r in rows if r.flagged]
    assert [r.op for r in flagged] == ["ag_gemm"]
    assert rows[0].op == "ag_gemm"             # worst first
    report = format_report(rows)
    assert "FLAG" in report and "ag_gemm" in report
    reg = get_registry()
    assert reg.counter("perf_audit_flags_total", op="ag_gemm").value >= 1


def test_bench_record_attaches_estimate(capsys):
    rec = bench_record({"bench": "ag_gemm", "world": 8, "M": 4096,
                        "K": 7168, "N": 7168, "method": "fused",
                        "us": 900.0, "vs_baseline": 1.1})
    assert rec["estimate_us"] > 0
    assert rec["model_deviation"] == pytest.approx(
        900.0 / rec["estimate_us"], rel=1e-2)
    line = capsys.readouterr().out.strip()
    assert json.loads(line) == json.loads(json.dumps(rec))

    # AR benches re-derive from nbytes; unknown benches pass through.
    rec2 = bench_record({"bench": "allreduce", "world": 8,
                         "nbytes": 1 << 22, "method": "ring",
                         "us": 300.0})
    assert rec2["estimate_us"] > 0
    rec3 = bench_record({"bench": "flash_decode", "us": 100.0})
    assert "estimate_us" not in rec3


# ---------------------------------------------------------------------------
# Autotuner metrics
# ---------------------------------------------------------------------------

def test_autotuner_metrics(tmp_path):
    from triton_distributed_tpu.autotuner import ContextualAutotuner

    reg = get_registry()
    miss0 = reg.counter("autotune_cache_misses_total").value
    mem0 = reg.counter("autotune_cache_hits_total", level="memory").value
    disk0 = reg.counter("autotune_cache_hits_total", level="disk").value

    def op(a, *, config):
        return a * config

    path = str(tmp_path / "cache.json")
    a = jnp.ones((8, 128))
    t1 = ContextualAutotuner(op, [2.0, 3.0], iters=1, warmup=1,
                             cache_path=path)
    with capture_events() as events:
        t1(a)
    assert reg.counter("autotune_cache_misses_total").value == miss0 + 1
    tune_events = [e for e in events if e.kind == "autotune"]
    assert len(tune_events) == 1
    assert tune_events[0].extra["n_configs"] == 2
    assert tune_events[0].config in ("2.0", "3.0")

    t1(a)   # in-memory hit
    assert reg.counter("autotune_cache_hits_total",
                       level="memory").value == mem0 + 1

    t2 = ContextualAutotuner(op, [2.0, 3.0], iters=1, warmup=1,
                             cache_path=path)
    t2(a)   # disk hit
    assert reg.counter("autotune_cache_hits_total",
                       level="disk").value == disk0 + 1


# ---------------------------------------------------------------------------
# Engine metrics
# ---------------------------------------------------------------------------

def test_engine_serve_metrics_record():
    from triton_distributed_tpu.models.engine import Engine

    cache = types.SimpleNamespace(
        ks=[np.zeros((2, 4, 1024, 8), np.float16)])
    fake = types.SimpleNamespace(_served_shapes=set())
    reg = get_registry()
    warm0 = reg.histogram("engine_decode_step_ms").snapshot()["count"]

    # First call per shape is COLD (includes jit compile): the event
    # carries cold=True and the steady-state histograms are untouched.
    with capture_events() as events:
        Engine._record_serve_metrics(
            fake, 2, 256, 64, cache, t_prefill=30.0, t_total=45.0)
    assert events[0].extra["cold"] is True
    assert reg.histogram("engine_decode_step_ms").snapshot()[
        "count"] == warm0

    with capture_events() as events:
        Engine._record_serve_metrics(
            fake, 2, 256, 64, cache, t_prefill=0.1, t_total=0.74)
    (ev,) = events
    assert ev.kind == "engine" and ev.op == "engine_serve"
    assert ev.extra["cold"] is False
    assert ev.extra["decode_ms_per_step"] == pytest.approx(
        0.64 / 63 * 1e3, rel=1e-3)
    assert ev.extra["prefill_tokens_per_s"] == pytest.approx(5120.0)
    assert ev.extra["kv_occupancy"] == pytest.approx(320 / 1024)
    reg = get_registry()
    assert reg.gauge("engine_kv_cache_occupancy").value == pytest.approx(
        320 / 1024)
    assert reg.histogram("engine_decode_step_ms").snapshot()["count"] >= 1


# ---------------------------------------------------------------------------
# MoE fused epilogue: VMEM guard + combine dtype (satellites)
# ---------------------------------------------------------------------------

def _fake_pallas(calls):
    def fake_pallas_call(kern, *, out_shape, **kw):
        calls["kern"] = kern

        def run(*operands):
            calls["operands"] = operands
            return tuple(jnp.zeros(s.shape, s.dtype) for s in out_shape)

        return run
    return fake_pallas_call


def test_moe_fused_vmem_guard_and_combine_dtype(monkeypatch):
    import triton_distributed_tpu.kernels.moe_reduce_rs as mrs
    from triton_distributed_tpu.utils.platform import COMM_VMEM_LIMIT

    world, e, cap, k = 2, 2, 128, 128
    ctx = mrs.MoEReduceRSContext(axis="tp", world_size=world,
                                 num_experts=e, topk=2)

    calls = {}
    monkeypatch.setattr(mrs.pl, "pallas_call", _fake_pallas(calls))
    # the fake pallas_call never consumes the compiler params
    monkeypatch.setattr(mrs, "comm_compiler_params",
                        lambda *a, **k: None)
    monkeypatch.setattr(mrs, "default_interpret", lambda *a, **k: True)

    from triton_distributed_tpu.kernels import moe_utils

    def run(mc, n):
        buckets = jnp.zeros((world, e, cap, k), jnp.bfloat16)
        w = jnp.zeros((e, k, n), jnp.bfloat16)
        ids = jnp.zeros((world * mc, 2), jnp.int32)
        tw = jnp.full((world * mc, 2), 0.5, jnp.float32)
        plan = moe_utils.plan_chunks(ids, tw, world, e, cap)
        out = mrs.moe_reduce_rs_fused(buckets, w, plan, ctx)
        assert out.shape == (mc, n)
        return calls["kern"].func

    # Small chunk: single-phase pipeline fits VMEM.
    assert run(128, 512) is mrs._moe_rs_fused_kernel
    # The f32 combine_blocks were cast to the activation dtype
    # (ADVICE r5) before entering the kernel.
    cmat_op = calls["operands"][2]
    assert cmat_op.dtype == jnp.bfloat16
    # The packed schedule tables ride as int32 SMEM operands.
    assert calls["operands"][3].dtype == jnp.int32   # block_expert
    assert calls["operands"][5].dtype == jnp.int32   # n_blocks

    # Oversized chunk: (4 + 2*itemsize)*mc*n exceeds COMM_VMEM_LIMIT
    # -> two-phase HBM-staged fallback instead of a compile failure.
    mc_big, n_big = 4096, 4096
    assert (4 + 2 * 2) * mc_big * n_big > COMM_VMEM_LIMIT
    assert run(mc_big, n_big) is mrs._moe_rs_fused_kernel_2p


def test_moe_two_phase_numerics(monkeypatch):
    """The two-phase fallback kernel must compute the same result as
    the staged composition — forced at a small shape by shrinking
    COMM_VMEM_LIMIT (interpret-mode harness; target toolchain)."""
    import functools

    from jax.sharding import Mesh, PartitionSpec as P

    import triton_distributed_tpu.kernels.moe_reduce_rs as mrs
    from triton_distributed_tpu.kernels import moe_utils
    from triton_distributed_tpu.kernels.matmul import MatmulConfig
    from triton_distributed_tpu.ops import shard_map_op
    from triton_distributed_tpu.utils.testing import assert_allclose

    # Force the two-phase path: any bf16/f32 scratch footprint beats 1
    # (patches only this module's selection threshold — the compiler
    # params' real VMEM limit is untouched).
    monkeypatch.setattr(mrs, "COMM_VMEM_LIMIT", 1)
    orig = mrs.moe_reduce_rs_fused

    world, e, cap, mc, k, n = 4, 4, 16, 32, 64, 48
    mesh = Mesh(np.array(jax.devices()[:world]), ("tp",))
    key = jax.random.key(11)
    buckets = jax.random.normal(key, (world, e, cap, world * k)) / 8
    wdown = jax.random.normal(jax.random.fold_in(key, 1),
                              (e, world * k, n)) / 8
    ids = jax.random.randint(jax.random.fold_in(key, 2),
                             (world * mc, 2), 0, e)
    w = jax.nn.softmax(jax.random.normal(
        jax.random.fold_in(key, 3), (world * mc, 2)), axis=-1)
    plan = moe_utils.plan_chunks(ids, w, world, e, cap)

    ctx = mrs.MoEReduceRSContext(axis="tp", world_size=world,
                                 num_experts=e, topk=2,
                                 gemm=MatmulConfig(16, 48, 64))
    with capture_events() as events:
        fused = shard_map_op(
            functools.partial(orig, plan=plan, ctx=ctx), mesh,
            in_specs=(P(None, None, None, "tp"), P(None, "tp", None)),
            out_specs=P("tp", None))
        got = jax.jit(fused)(buckets, wdown)
    assert any(ev.op == "moe_reduce_rs_fused"
               and ev.method == "two_phase" for ev in events)

    partial = jnp.einsum("wecK,eKn->wecn", buckets, wdown)
    combined = jax.vmap(moe_utils.combine_tokens)(
        partial, ids.reshape(world, mc, 2), plan.slot_of_pair,
        w.reshape(world, mc, 2))
    ref = combined.reshape(world * mc, n).astype(got.dtype)
    assert_allclose(got, ref, atol=1e-4, rtol=1e-4,
                    name="moe-rs-two-phase")


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_and_dump(tmp_path):
    fr = FlightRecorder(capacity=4)
    for i in range(7):
        fr.record(KernelEvent(kind="collective", op=f"op{i}"))
    assert len(fr) == 4
    assert [e.op for e in fr.events()] == ["op3", "op4", "op5", "op6"]

    path = str(tmp_path / "flight.json")
    written = fr.dump(path, reason="test")
    assert written == path
    payload = json.load(open(path))
    assert payload["reason"] == "test"
    assert [e["op"] for e in payload["events"]] == ["op3", "op4",
                                                    "op5", "op6"]
    assert "metrics" in payload
    # Round-trip back into events.
    back = [KernelEvent.from_dict(d) for d in payload["events"]]
    assert back[0].op == "op3"
    # No armed directory and no explicit path -> nowhere to write.
    assert FlightRecorder(capacity=2).dump() is None


def test_flight_recorder_dump_on_signal(tmp_path):
    """SIGUSR1 dumps without dying (the live-inspection path)."""
    fr = FlightRecorder(capacity=8)
    fr.record(KernelEvent(kind="collective", op="sigop"))
    assert fr.install(str(tmp_path))
    try:
        os.kill(os.getpid(), signal.SIGUSR1)
        dump = os.path.join(str(tmp_path), "flight-rank-0.json")
        assert os.path.exists(dump)
        payload = json.load(open(dump))
        assert payload["reason"].startswith("signal-")
        assert payload["events"][0]["op"] == "sigop"
    finally:
        signal.signal(signal.SIGUSR1, signal.SIG_DFL)
        signal.signal(signal.SIGTERM, signal.SIG_DFL)


# ---------------------------------------------------------------------------
# 2-process launcher flight-recorder dump (test_launcher-style)
# ---------------------------------------------------------------------------

WORKER_HANG = textwrap.dedent("""
    import os, sys, time
    from triton_distributed_tpu.observability import (
        emit_kernel_event, maybe_install_flight_recorder)

    assert maybe_install_flight_recorder()
    rank = int(os.environ["TDT_PROCESS_ID"])
    emit_kernel_event("all_gather", method="ring", world=2,
                      shape=(64, 128), dtype="float32",
                      bytes_moved=64 * 128 * 4, estimate_us=10.0)
    emit_kernel_event("dcn_collective", method="xla", world=2,
                      step=rank)
    ready_dir = sys.argv[1]
    open(os.path.join(ready_dir, f"ready-{rank}"), "w").close()
    if rank == 1:
        # Fail only after rank 0 is armed (no wall-clock race): the
        # launcher's first-failure kill then SIGTERMs rank 0, whose
        # handler must dump its ring.
        for _ in range(2400):
            if os.path.exists(os.path.join(ready_dir, "ready-0")):
                sys.exit(1)
            time.sleep(0.05)
        sys.exit(3)   # rank 0 never armed: fail loudly
    time.sleep(600)   # rank 0 plays the hung peer
""")


def test_launcher_failure_dumps_flight_record(tmp_path):
    """2-process `scripts/launch.py` run where one rank dies: the
    launcher SIGTERMs the survivor, whose flight recorder (armed via
    --flight-dir) must dump the events that preceded the kill — the
    silent-hang failure mode becomes diagnosable."""
    worker = tmp_path / "worker_hang.py"
    worker.write_text(WORKER_HANG)
    flight_dir = tmp_path / "flight"
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "launch.py"),
         "--nproc", "2", "--cpu",
         "--flight-dir", str(flight_dir),
         "--coordinator", "127.0.0.1:12397", str(worker),
         str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 1, (res.returncode, res.stdout,
                                 res.stderr)
    path = flight_dir / "flight-rank-0.json"
    assert path.exists(), (res.stdout, res.stderr,
                           list(flight_dir.iterdir())
                           if flight_dir.exists() else "no dir")
    payload = json.loads(path.read_text())
    assert payload["rank"] == 0
    assert payload["reason"].startswith("signal-")
    ops = [e["op"] for e in payload["events"]]
    assert ops == ["all_gather", "dcn_collective"]
    assert payload["events"][0]["bytes_moved"] == 64 * 128 * 4
    # Per-rank metrics snapshot rides along.
    counters = payload["metrics"]["counters"]
    assert any(k.startswith("events_total") for k in counters)


def test_launcher_timeout_watchdog(tmp_path):
    """`launch.py --timeout` reaps a wedged group and exits 124 (the
    timeout(1) convention) — the watchdog half of hang forensics."""
    worker = tmp_path / "worker_sleep.py"
    worker.write_text("import time; time.sleep(600)\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "launch.py"),
         "--nproc", "2", "--cpu", "--timeout", "5",
         "--coordinator", "127.0.0.1:12398", str(worker)],
        env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 124, (res.returncode, res.stdout,
                                   res.stderr)
