"""Doctor CLI over the seeded incident corpus, trace salvage,
ring-overflow accounting, the launcher hook, and the disabled-path
zero-allocation guarantee."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from triton_distributed_tpu.observability import doctor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORPUS = os.path.join(REPO, "tests", "data", "incidents")
SCENARIOS = ("stalled_rank", "sem_leak", "slow_link", "clean")
#: every committed `report.golden.json` but `socket_partition`'s
#: (tests/test_net.py compares that one): the four beyond SCENARIOS
#: were compared by a shell gate only, until it left the tree (PR 30)
GOLDENS = SCENARIOS + ("lossy_transport", "slow_request",
                       "replayed_fault", "fleet_alert")


def _diagnose(scenario):
    report = doctor.diagnose([os.path.join(CORPUS, scenario)])
    assert report is not None, scenario
    return report


# ---------------------------------------------------------------------------
# Corpus correctness: the acceptance criteria facts
# ---------------------------------------------------------------------------

class TestCorpus:
    def test_stalled_rank_names_rank_sem_and_link(self):
        r = _diagnose("stalled_rank")
        assert r["stall"]["stalled_ranks"] == [2]
        assert r["stall"]["first_stalled_rank"] == 2
        assert r["stall"]["pending_sem"] == "recv_sem"
        assert r["stall"]["in_flight_op"]["op"] == "all_reduce"
        # static check ran live on the mapped registry kernel, clean.
        assert r["static"]["kernel"] == "allreduce.one_shot"
        assert r["static"]["could_hang"] is False
        assert r["links"]["hot"][0]["link"].startswith("tp:")
        # the truncated trace was salvaged, not fatal
        assert r["timeline"]["truncated_ranks"] == [2]
        assert any("truncated" in n for n in r["incompleteness"])
        # serving gauges from the heartbeat surfaced per rank
        assert r["rank_table"]["2"]["serving"][
            "serving_queue_depth"] == 3.0

    def test_page_pressure_reported(self, tmp_path):
        """Heartbeats carrying the paged-KV serving gauges surface a
        page-pressure section + verdict note; artifacts WITHOUT them
        (the whole golden corpus) keep byte-identical reports."""
        import glob as _glob
        import shutil
        dst = tmp_path / "incident"
        shutil.copytree(os.path.join(CORPUS, "clean"), dst)
        for f in _glob.glob(str(dst / "heartbeat-rank-*.json")):
            with open(f) as fh:
                hb = json.load(fh)
            hb.setdefault("serving", {}).update({
                "serving_kv_page_occupancy": 0.97,
                "serving_kv_pages_free": 1,
                "serving_kv_pages_used": 31,
                "serving_prefix_cache_pages": 4})
            with open(f, "w") as fh:
                json.dump(hb, fh)
        r = doctor.diagnose([str(dst)])
        assert len(r["page_pressure"]) == 4
        assert all(e["pressure"] for e in r["page_pressure"])
        assert "KV page pressure" in r["verdict"]
        assert "31" in r["verdict"] or "1 free" in r["verdict"]
        md = doctor.render_markdown(r)
        assert "## KV page pressure" in md and "PRESSURE" in md
        # below the threshold: section present, no verdict escalation
        for f in _glob.glob(str(dst / "heartbeat-rank-*.json")):
            with open(f) as fh:
                hb = json.load(fh)
            hb["serving"]["serving_kv_page_occupancy"] = 0.5
            with open(f, "w") as fh:
                json.dump(hb, fh)
        r2 = doctor.diagnose([str(dst)])
        assert not any(e["pressure"] for e in r2["page_pressure"])
        assert "KV page pressure" not in r2["verdict"]
        # no page gauges at all -> no section key (golden stability)
        assert "page_pressure" not in _diagnose("clean")

    def test_sem_leak_blames_static_finding(self):
        r = _diagnose("sem_leak")
        assert r["stall"]["first_stalled_rank"] == 0
        assert set(r["stall"]["stalled_ranks"]) == {0, 1, 2, 3}
        # pending sem comes from the artifact's static findings file
        assert r["stall"]["pending_sem"] == "recv_sems[1]"
        assert r["static"]["source"] == "artifact"
        assert r["static"]["could_hang"] is True
        assert "sem_leak" in r["static"]["verdict"]

    def test_resource_verdict_absent_by_default(self):
        # Opt-in: golden reports must stay byte-identical, so the key
        # simply doesn't exist unless --resources / a findings file
        # asks for it.
        assert "resources" not in _diagnose("stalled_rank")

    def test_resource_verdict_on_stalled_rank(self):
        r = doctor.diagnose(
            [os.path.join(CORPUS, "stalled_rank")], resources=True)
        res = r["resources"]
        assert res["kernel"] == "allreduce.one_shot"
        assert res["source"] == "live"
        assert res["could_overflow"] is False
        assert "resource sweep is clean" in res["verdict"]
        assert res["verdict"] in r["verdict"]
        md = doctor.render_markdown(r)
        assert "## Static resource check" in md

    def test_resource_verdict_multi_axis_mesh_from_event(self):
        # Torus kernels register only at multi-axis meshes: the mesh
        # must come from extra.axes/sizes (like the comm verdict), or
        # the sweep analyzes nothing.
        stall = {"in_flight_event": {
            "op": "all_gather_torus", "method": None, "axis": "x",
            "world": 4, "extra": {"axes": ["x", "y"],
                                  "sizes": [2, 2]}}}
        out = doctor.run_resource_analysis(
            doctor.Artifacts([]), stall, enabled=True)
        assert out["kernel"] == "torus.allgather"
        assert out["mesh"] == {"x": 2, "y": 2}
        assert out["source"] == "live"
        assert out["could_overflow"] is False

    def test_resource_verdict_never_clean_when_nothing_swept(self):
        # A mesh the kernel's builder rejects must NOT read as a
        # clean sweep.
        stall = {"in_flight_event": {
            "op": "all_gather", "method": "ring", "axis": "x",
            "world": 4, "extra": {"axes": ["x", "y"],
                                  "sizes": [2, 2]}}}
        out = doctor.run_resource_analysis(
            doctor.Artifacts([]), stall, enabled=True)
        assert out["source"] == "unavailable (mesh not applicable)"
        assert "could_overflow" not in out
        assert "verdict" not in out

    def test_resource_findings_file_enables_section(self, tmp_path):
        import shutil
        dst = tmp_path / "incident"
        shutil.copytree(os.path.join(CORPUS, "stalled_rank"), dst)
        rows = {"findings": [{
            "kernel": "flash_decode.paged", "kind": "oob_block_index",
            "ref": "in1",
            "message": "block index 9 outside [0, 8] via page table",
        }]}
        (dst / "resource-findings.json").write_text(json.dumps(rows))
        r = doctor.diagnose([str(dst)])
        res = r["resources"]
        assert res["source"] == "artifact"
        assert res["could_overflow"] is True
        assert "walk off its index/page tables" in res["verdict"]

    def test_slow_link_straggler_anomaly_contention(self):
        r = _diagnose("slow_link")
        assert r["stall"]["first_stalled_rank"] is None
        assert r["stragglers"][0]["rank"] == 3
        assert r["stragglers"][0]["blamed_link"] == "tp:3>0"
        a = r["anomalies"][0]
        assert (a["rank"], a["occurrence"]) == (3, 5) and a["z"] > 3
        assert r["links"]["hot"][0]["link"] == "tp:2>3"
        assert r["links"]["contention"], "expected contention records"
        assert any("evicted from the flight ring" in n
                   for n in r["incompleteness"])

    def test_clean_run_is_clean(self):
        r = _diagnose("clean")
        assert r["stall"]["stalled_ranks"] == []
        assert r["stragglers"] == [] and r["anomalies"] == []
        assert r["verdict"].startswith("no incident detected")

    @pytest.mark.parametrize("scenario", GOLDENS)
    def test_matches_golden(self, scenario):
        golden_path = os.path.join(CORPUS, scenario,
                                   "report.golden.json")
        with open(golden_path) as f:
            golden = json.load(f)
        diffs = doctor.compare_reports(_diagnose(scenario), golden)
        assert not diffs, diffs[:10]

    def test_generator_is_deterministic(self, tmp_path, monkeypatch):
        spec = importlib.util.spec_from_file_location(
            "incident_gen", os.path.join(CORPUS, "generate.py"))
        gen = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(gen)
        monkeypatch.setattr(gen, "HERE", str(tmp_path))
        gen.generate()
        for scenario in SCENARIOS:
            for name in sorted(os.listdir(
                    os.path.join(CORPUS, scenario))):
                if name.startswith("report.golden"):
                    continue
                with open(os.path.join(CORPUS, scenario, name)) as f:
                    committed = f.read()
                with open(tmp_path / scenario / name) as f:
                    assert f.read() == committed, (scenario, name)

    def test_markdown_renders_all_sections(self):
        md = doctor.render_markdown(_diagnose("slow_link"))
        for section in ("# Incident report", "## Ranks",
                        "## Hot ICI links", "## Link contention",
                        "## Consistent stragglers", "## Anomalies",
                        "## Incomplete data"):
            assert section in md, section

    def test_cli_check_detects_drift(self, tmp_path):
        golden = os.path.join(CORPUS, "clean", "report.golden.json")
        bad = json.load(open(golden))
        bad["verdict"] = "something else"
        bad_path = tmp_path / "bad.json"
        bad_path.write_text(json.dumps(bad))
        rc = doctor.main([os.path.join(CORPUS, "clean"),
                          "--json", str(tmp_path / "r.json"),
                          "--md", str(tmp_path / "r.md"), "-q",
                          "--check", str(bad_path)])
        assert rc == 3
        rc = doctor.main([os.path.join(CORPUS, "clean"),
                          "--json", str(tmp_path / "r.json"),
                          "--md", str(tmp_path / "r.md"), "-q",
                          "--check", golden])
        assert rc == 0


# ---------------------------------------------------------------------------
# Satellite: truncated-trace salvage
# ---------------------------------------------------------------------------

class TestSalvage:
    def test_merge_tolerates_truncated_trace(self, tmp_path):
        from triton_distributed_tpu.observability import timeline as tl
        for rank in range(2):
            trace = {"traceEvents": [
                {"name": "step", "ph": "X", "ts": 1000.0 + rank,
                 "dur": 50.0, "pid": rank, "tid": 1, "args": {}},
                {"name": "step", "ph": "X", "ts": 2000.0 + rank,
                 "dur": 60.0, "pid": rank, "tid": 1, "args": {}},
            ], "metadata": {"rank": rank}}
            text = json.dumps(trace, indent=1)
            path = tmp_path / f"trace-rank-{rank}.json"
            path.write_text(text[:int(len(text) * 0.5)]
                            if rank == 1 else text)
        report = tl.merge_directory(str(tmp_path))
        assert report is not None
        assert report["timeline_truncated_ranks"] == [1]
        merged = json.load(open(tmp_path / "merged_trace.json"))
        assert merged["metadata"]["timeline_truncated_ranks"] == [1]
        # rank 1's first (complete) event was salvaged
        assert any(e.get("pid") == 1 for e in merged["traceEvents"]
                   if e.get("ph") == "X")

    def test_hopeless_truncation_raises(self, tmp_path):
        from triton_distributed_tpu.observability.timeline import (
            load_trace)
        path = tmp_path / "trace-rank-0.json"
        path.write_text('{"traceEv')
        with pytest.raises(ValueError):
            load_trace(str(path))


# ---------------------------------------------------------------------------
# Satellite: ring overflow is counted, not silent
# ---------------------------------------------------------------------------

class TestOverflowCounters:
    def test_span_ring_overflow_counts(self):
        from triton_distributed_tpu.observability.metrics import (
            get_registry)
        from triton_distributed_tpu.observability.tracing import (
            SpanTracer)
        get_registry().clear()
        tracer = SpanTracer(capacity=2)
        for _ in range(5):
            with tracer.span("s"):
                pass
        assert get_registry().peek("trace_dropped_spans_total") == 3

    def test_event_ring_overflow_counts(self):
        from triton_distributed_tpu.observability.events import (
            KernelEvent)
        from triton_distributed_tpu.observability.metrics import (
            get_registry)
        from triton_distributed_tpu.observability.recorder import (
            FlightRecorder)
        get_registry().clear()
        rec = FlightRecorder(capacity=2)
        for i in range(6):
            rec.record(KernelEvent(kind="bench", op=f"e{i}"))
        assert get_registry().peek("events_dropped_total") == 4


# ---------------------------------------------------------------------------
# Acceptance: TDT_OBSERVABILITY=0 — link/anomaly bookkeeping allocates
# nothing on the hot path
# ---------------------------------------------------------------------------

class TestDisabledHotPath:
    def test_no_allocation_from_links_or_anomaly(self, monkeypatch):
        import tracemalloc

        import triton_distributed_tpu.observability.anomaly as anomaly
        import triton_distributed_tpu.observability.links as links
        from triton_distributed_tpu.observability import (
            record_collective, span)
        from triton_distributed_tpu.observability.tracing import (
            NULL_SPAN)

        monkeypatch.setenv("TDT_OBSERVABILITY", "0")
        monkeypatch.setattr(links, "_TRACKER", None)
        monkeypatch.setattr(anomaly, "_STORE", None)

        def hot_path():
            for _ in range(50):
                record_collective(
                    "all_gather", axis="tp", world=4, method="ring",
                    shape=(8, 128), dtype="float32",
                    payload_bytes=4096, hops="ring")
                with span("engine.decode_step"):
                    pass

        hot_path()  # warm any lazy imports outside the measurement
        tracemalloc.start()
        try:
            snap0 = tracemalloc.take_snapshot()
            hot_path()
            snap1 = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        for mod in (links, anomaly):
            filt = tracemalloc.Filter(True, mod.__file__)
            blocks = sum(
                s.size for s in snap1.filter_traces([filt]).statistics(
                    "filename"))
            blocks0 = sum(
                s.size for s in snap0.filter_traces([filt]).statistics(
                    "filename"))
            assert blocks - blocks0 <= 0, (
                f"{mod.__name__} allocated on the disabled hot path")
        # the tracker/store singletons were never even constructed
        assert links._TRACKER is None
        assert anomaly._STORE is None
        assert span("x") is NULL_SPAN


# ---------------------------------------------------------------------------
# Launcher hook: nonzero rank exit produces an incident report
# ---------------------------------------------------------------------------

class TestLauncherIntegration:
    def test_launch_invokes_doctor_on_failure(self, tmp_path):
        worker = tmp_path / "worker.py"
        worker.write_text(
            "import os, sys\n"
            "from triton_distributed_tpu.observability import (\n"
            "    emit_kernel_event, get_flight_recorder)\n"
            "emit_kernel_event('all_reduce', method='one_shot',\n"
            "                  axis='tp', world=4, shape=(8, 128),\n"
            "                  dtype='float32', bytes_moved=4096,\n"
            "                  hops='all_pairs',\n"
            "                  pending_sem='recv_sem')\n"
            "get_flight_recorder().dump(reason='test')\n"
            "sys.exit(7)\n")
        flight_dir = tmp_path / "flight"
        env = dict(os.environ,
                   JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO + os.pathsep
                   + os.environ.get("PYTHONPATH", ""),
                   TDT_FLIGHT_RECORDER=str(flight_dir))
        res = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "launch.py"),
             "--nproc", "1", "--cpu", "--flight-dir",
             str(flight_dir), str(worker)],
            env=env, capture_output=True, text=True, timeout=180)
        assert res.returncode == 7, res.stderr[-2000:]
        report_path = flight_dir / "incident_report.json"
        assert report_path.exists(), res.stderr[-2000:]
        report = json.load(open(report_path))
        assert report["schema"] == 1
        assert "doctor verdict" in res.stderr
