"""Incident doctor: one command that turns a failed run's artifact
directory into a root-cause report.

Diagnosing a stall today means hand-correlating four artifact
families — per-rank Chrome traces (:mod:`.tracing`), flight-recorder
dumps (:mod:`.recorder`), heartbeat files (:mod:`.exporter`), and
metrics JSON — plus, when the failing kernel is registered with the
static sanitizer, PR 4's comm graph.  The doctor ingests all of them
and answers, in one markdown/JSON report:

- what was **in flight** on each rank (open span, last kernel event,
  logical step, serving load);
- **who stalled first** (heartbeat staleness, oldest last-activity);
- the **pending semaphore** at stall time (flight-dump annotation or
  the static analysis' finding);
- whether the **static comm graph** says that wait *could* hang
  (a finding names the defect; a clean graph means the wait is
  statically matched, so the hang has a runtime cause — peer death or
  link failure);
- which **ICI links were hot** (per-link byte attribution over the
  flight events, plus contention between overlapping collectives);
- **anomalies and stragglers** from the merged timeline
  (:mod:`.anomaly`), with the blamed link/semaphore.

Usage::

    python -m triton_distributed_tpu.observability.doctor ARTIFACT_DIR
    python -m triton_distributed_tpu.observability.doctor DIR --json -
    python -m triton_distributed_tpu.observability.doctor DIR \
        --check tests/data/incidents/stalled_rank/report.golden.json

``scripts/launch.py`` invokes it automatically when the watchdog fires
(exit 124) or a rank exits nonzero.  Reports are deterministic given
the artifacts ("now" is the newest artifact timestamp, not the wall
clock), so golden reports can gate CI (`tests/test_doctor.py`).

Exit status: 0 report written, 2 usage/no artifacts, 3 golden drift.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from triton_distributed_tpu.observability.exporter import (
    STALE_INTERVALS,
)

REPORT_SCHEMA = 1
REPORT_JSON = "incident_report.json"
REPORT_MD = "incident_report.md"

#: (op, method) -> analysis-registry kernel name, so the doctor can
#: replay the running kernel on the abstract machine.  None matches
#: any method.
_OP_TO_KERNEL = {
    ("all_gather", "ring"): "allgather.ring",
    ("all_gather", "bidir_ring"): "allgather.bidir_ring",
    ("all_gather", "push_all"): "allgather.push_all",
    ("reduce_scatter", "ring"): "reduce_scatter.ring",
    ("reduce_scatter", "scatter_reduce"):
        "reduce_scatter.scatter_reduce",
    ("all_reduce", "one_shot"): "allreduce.one_shot",
    ("all_reduce", "two_shot"): "allreduce.two_shot",
    ("all_reduce", "chain"): "allreduce.chain",
    ("ag_gemm", "fused"): "ag_gemm.fused",
    ("ag_gemm", "ll"): "ag_gemm.ll",
    ("ag_gemm_w8a8", "fused"): "ag_gemm.w8a8",
    ("gemm_rs", "fused"): "gemm_rs.fused",
    ("gemm_rs", "ll"): "gemm_rs.ll",
    ("all_gather_torus", None): "torus.allgather",
    ("reduce_scatter_torus", None): "torus.reduce_scatter",
    ("moe_reduce_rs_fused", "fused"): "moe_reduce_rs.fused",
    ("moe_reduce_rs_fused", "two_phase"): "moe_reduce_rs.two_phase",
    ("moe_reduce_rs_fused", "w8a8_fused"): "moe_reduce_rs.w8a8",
    ("moe_reduce_rs_fused", "w8a8_two_phase"):
        "moe_reduce_rs.w8a8_two_phase",
    ("all_to_all", "auto"): "all_to_all.plain",
    ("sp_ag_attention_fused", "fused"): "sp_ag_attention.fused",
    ("sp_ring_attention", "ring"): "sp_ag_attention.fused",
    ("sp_flash_decode", "push_all"): "flash_decode.partials_ag",
    ("ag_group_gemm", "ring"): "ag_group_gemm.ring",
    ("fast_allgather_packed", "push_all"): "ll_allgather.push",
    ("barrier_all", None): "common_ops.barrier",
    ("broadcast", None): "common_ops.broadcast",
}


def kernel_for_event(ev: dict) -> Optional[str]:
    op, method = ev.get("op"), ev.get("method")
    return (_OP_TO_KERNEL.get((op, method))
            or _OP_TO_KERNEL.get((op, None)))


# ---------------------------------------------------------------------------
# Artifact discovery / loading
# ---------------------------------------------------------------------------

def _rank_of(path: str) -> Optional[int]:
    m = re.search(r"rank-(\d+)\.json$", os.path.basename(path))
    return int(m.group(1)) if m else None


def _num(value, default: float = 0.0) -> float:
    """Tolerant numeric coercion for artifact fields: a hand-edited
    or version-drifted line must degrade, never crash the report."""
    try:
        return float(value)
    except (TypeError, ValueError):
        return default


def _load_json(path: str) -> Optional[dict]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _merge_router_docs(docs: Sequence[dict]) -> Optional[dict]:
    """Fold N per-process ``router-state*.json`` docs into ONE router
    view (multi-rank cluster runs: a pod's routers each write their
    own state).  One doc passes through untouched — single-router
    reports, and the goldens built on them, stay byte-identical.

    Merge discipline: the newest doc's scalars win; replicas merge by
    NAME preferring the doc with the latest ``ts`` that names them;
    failovers/readmits concatenate (deduped on (ts, replica, reason))
    in time order; wire totals (``kv_shipped_bytes``/``shipments``)
    SUM — each router counted its own transport."""
    if not docs:
        return None
    if len(docs) == 1:
        return docs[0]
    docs = sorted(docs, key=lambda d: _num(d.get("ts")))
    out = dict(docs[-1])
    by_name: Dict[str, dict] = {}
    for d in docs:                     # ascending ts: newest wins
        for r in d.get("replicas", []):
            by_name[str(r.get("name"))] = r
    out["replicas"] = [
        by_name[k] for k in sorted(
            by_name,
            key=lambda n: (_num(by_name[n].get("id"), 1e18), n))]
    for key in ("failovers", "readmits"):
        seen = set()
        rows = []
        for d in docs:
            for f in d.get(key, []):
                ident = (f.get("ts"), f.get("replica"),
                         f.get("reason"))
                if ident in seen:
                    continue
                seen.add(ident)
                rows.append(f)
        if rows:
            out[key] = sorted(rows, key=lambda f: _num(f.get("ts")))
        elif key in out:
            del out[key]
    for key in ("kv_shipped_bytes", "shipments"):
        vals = [d.get(key) for d in docs if d.get(key) is not None]
        if vals:
            out[key] = sum(vals)
    out["merged_from"] = len(docs)
    return out


class Artifacts:
    """Everything salvageable from one or more artifact directories."""

    def __init__(self, dirs: Sequence[str]):
        self.dirs = [os.path.abspath(d) for d in dirs]
        self.traces: List[dict] = []
        self.trace_files: List[str] = []
        self.flights: Dict[int, dict] = {}
        self.heartbeats: Dict[int, dict] = {}
        self.metrics: Dict[int, dict] = {}
        self.static_findings: Optional[dict] = None
        self.resource_findings: Optional[dict] = None
        self.protocol_findings: Optional[dict] = None
        self.decisions: List[dict] = []
        self.router: Optional[dict] = None
        self.faults: List[dict] = []
        self.lineage: List[dict] = []
        self.lineage_costs: List[dict] = []
        self.slo_state: Optional[dict] = None
        self.timeseries: List[dict] = []
        self.replay: List[dict] = []
        self.telemetry: List[dict] = []
        self.alerts: List[dict] = []
        self._discover()

    def _glob(self, pattern: str) -> List[str]:
        out = []
        for d in self.dirs:
            out += glob.glob(os.path.join(d, pattern))
            out += glob.glob(os.path.join(d, "heartbeats", pattern))
            # Multi-process cluster runs leave one artifact directory
            # per rank (``rank-<N>/``, `scripts/cluster_worker.py`);
            # one doctor invocation over the run root must ingest all
            # of them.
            out += glob.glob(os.path.join(d, "rank-*", pattern))
            out += glob.glob(os.path.join(d, "rank-*", "heartbeats",
                                          pattern))
        return sorted(set(out))

    def _discover(self) -> None:
        from triton_distributed_tpu.observability.timeline import (
            load_trace)
        for p in self._glob("trace-rank-*.json"):
            try:
                self.traces.append(load_trace(p))
                self.trace_files.append(p)
            except (OSError, ValueError):
                continue
        for p in self._glob("flight-rank-*.json"):
            d = _load_json(p)
            if d is not None:
                self.flights[int(d.get("rank", _rank_of(p) or 0))] = d
        for p in self._glob("heartbeat-rank-*.json"):
            d = _load_json(p)
            if d is not None:
                self.heartbeats[
                    int(d.get("rank", _rank_of(p) or 0))] = d
        for p in self._glob("metrics-rank-*.json"):
            d = _load_json(p)
            if d is not None:
                rank = d.get("meta", {}).get("rank", _rank_of(p) or 0)
                self.metrics[int(rank)] = d
        for p in self._glob("analysis-findings.json"):
            d = _load_json(p)
            if d is not None:
                self.static_findings = d
                break
        for p in self._glob("resource-findings.json"):
            d = _load_json(p)
            if d is not None:
                self.resource_findings = d
                break
        for p in self._glob("protocol-findings.json"):
            d = _load_json(p)
            if d is not None:
                self.protocol_findings = d
                break
        router_docs = []
        for p in self._glob("router-state*.json"):
            d = _load_json(p)
            if d is not None and d.get("kind") == "router":
                router_docs.append(d)
        self.router = _merge_router_docs(router_docs)
        decision_files = self._glob("decisions*.jsonl")
        if decision_files:
            from triton_distributed_tpu.observability.feedback import (
                load_decisions)
            self.decisions = load_decisions(decision_files)
        fault_files = self._glob("faults*.jsonl")
        if fault_files:
            from triton_distributed_tpu.serving.cluster.chaos import (
                load_faults)
            self.faults = load_faults(fault_files)
        lineage_files = self._glob("lineage*.jsonl")
        if lineage_files:
            from triton_distributed_tpu.observability.lineage import (
                load_lineage,
                load_lineage_costs)
            self.lineage = load_lineage(lineage_files)
            self.lineage_costs = load_lineage_costs(lineage_files)
        for p in self._glob("slo-state*.json"):
            d = _load_json(p)
            if d is not None and "classes" in d:
                self.slo_state = d
                break
        ts_files = self._glob("timeseries-rank-*.jsonl")
        if ts_files:
            from triton_distributed_tpu.observability.timeseries \
                import load_timeseries
            self.timeseries = load_timeseries(ts_files)
        replay_files = self._glob("replay.jsonl")
        if replay_files:
            from triton_distributed_tpu.observability.jsonl import (
                load_jsonl_rows)
            # File order preserved — the row stream IS the recorded
            # log (sorting would scramble the clock chunks).
            self.replay = load_jsonl_rows(replay_files)
        tel_files = self._glob("telemetry*.jsonl")
        alert_files = self._glob("alerts.jsonl")
        if tel_files or alert_files:
            from triton_distributed_tpu.observability.telemetry import (
                load_alerts, load_telemetry)
            # Per-file tolerance: a torn telemetry stream degrades the
            # Fleet section, never kills the report.
            for p in tel_files:
                try:
                    self.telemetry += load_telemetry(p)
                except (OSError, ValueError):
                    continue
            for p in alert_files:
                try:
                    self.alerts += load_alerts(p)
                except (OSError, ValueError):
                    continue
            self.alerts.sort(key=lambda e: (_num(e.get("ts")),
                                            str(e.get("rule")),
                                            str(e.get("target"))))

    def empty(self) -> bool:
        # A router artifact alone is an incident report's worth of
        # state: a virtual-clock cluster run writes router-state.json
        # without any heartbeat/trace files, and the doctor must
        # still name the failed replica from it.  Likewise a
        # faults.jsonl alone (the Chaos section must name the
        # injected fault classes from that artifact by itself) and a
        # lineage.jsonl alone (the Request-lineage section must name
        # the dominant hop from it).
        return not (self.traces or self.flights or self.heartbeats
                    or self.metrics or self.router or self.faults
                    or self.lineage or self.slo_state
                    or self.timeseries or self.replay
                    or self.telemetry or self.alerts)

    def ranks(self) -> List[int]:
        from triton_distributed_tpu.observability.timeline import (
            trace_rank)
        ranks = set(self.flights) | set(self.heartbeats) | set(
            self.metrics)
        ranks |= {trace_rank(tr, i) for i, tr in enumerate(self.traces)}
        return sorted(ranks)

    def newest_timestamp(self) -> float:
        """The report's deterministic "now": the newest timestamp any
        artifact carries (never the wall clock, so re-running the
        doctor over the same directory reproduces the report)."""
        ts = [0.0]
        for hb in self.heartbeats.values():
            ts.append(float(hb.get("unix_time", 0.0)))
        for fv in self.faults:
            ts.append(_num(fv.get("ts")))
        for lv in self.lineage:
            ts.append(_num(lv.get("ts")))
        for rv in self.replay:
            if rv.get("kind") in ("fault_injected", "hop"):
                ts.append(_num(rv.get("ts")))
        for tv in self.telemetry:
            ts.append(_num(tv.get("ts")))
        for av in self.alerts:
            ts.append(_num(av.get("ts")))
        for fl in self.flights.values():
            ts.append(float(fl.get("unix_time", 0.0)))
            for ev in fl.get("events", []):
                ts.append(float(ev.get("ts", 0.0)))
        for tr in self.traces:
            for e in tr.get("traceEvents", []):
                if e.get("ph") == "X":
                    ts.append((float(e.get("ts", 0.0))
                               + float(e.get("dur") or 0.0)) * 1e-6)
        return max(ts)

    def metrics_for(self, rank: int) -> Optional[dict]:
        """Registry snapshot for a rank: standalone export if present,
        else the one embedded in its flight dump."""
        if rank in self.metrics:
            return self.metrics[rank]
        fl = self.flights.get(rank)
        return fl.get("metrics") if fl else None


# ---------------------------------------------------------------------------
# Analysis passes
# ---------------------------------------------------------------------------

def _counter(snapshot: Optional[dict], name: str) -> float:
    if not snapshot:
        return 0.0
    total = 0.0
    for key, v in snapshot.get("counters", {}).items():
        if key == name or key.startswith(name + "{"):
            total += v
    return total


def build_rank_table(art: Artifacts, now: float,
                     interval: float) -> Dict[str, dict]:
    table: Dict[str, dict] = {}
    for rank in art.ranks():
        hb = art.heartbeats.get(rank, {})
        fl = art.flights.get(rank, {})
        snap = art.metrics_for(rank)
        age = (round(now - float(hb["unix_time"]), 3)
               if hb.get("unix_time") else None)
        events = fl.get("events", [])
        last_ev = events[-1] if events else None
        row = {
            "heartbeat_age_s": age,
            "stale": (age is not None
                      and age > STALE_INTERVALS * interval),
            "step": hb.get("step"),
            "last_span": hb.get("last_span"),
            "open_spans": hb.get("open_spans",
                                 [s.get("name") for s in
                                  fl.get("open_spans", [])]),
            "last_event": ({
                "op": last_ev.get("op"),
                "method": last_ev.get("method"),
                "age_s": round(now - float(last_ev.get("ts", 0.0)), 3),
            } if last_ev else None),
            # New names first, legacy (pre-rename) second: committed
            # incident artifacts carry the old counter names and the
            # doctor must keep reading them byte-identically.
            "dropped_spans": int(
                _counter(snap, "trace_dropped_spans_total")
                + _counter(snap, "trace_dropped_spans")),
            "dropped_events": int(
                _counter(snap, "events_dropped_total")
                + _counter(snap, "events_dropped")),
        }
        if hb.get("serving"):
            row["serving"] = hb["serving"]
        table[str(rank)] = row
    return table


def detect_stall(art: Artifacts, rank_table: Dict[str, dict]
                 ) -> dict:
    stalled = sorted(int(r) for r, row in rank_table.items()
                     if row["stale"])
    first = None
    if stalled:
        # The stalest heartbeat stopped beating first — that rank
        # wedged while its peers kept going (until they blocked on it).
        first = max(stalled,
                    key=lambda r:
                    rank_table[str(r)]["heartbeat_age_s"] or 0.0)
    pending_sem = None
    in_flight = None
    open_span = None
    if first is not None:
        row = rank_table[str(first)]
        open_span = (row["open_spans"][-1] if row.get("open_spans")
                     else row.get("last_span"))
        fl = art.flights.get(first, {})
        events = fl.get("events", [])
        if events:
            in_flight = events[-1]
            pending_sem = (in_flight.get("extra") or {}).get(
                "pending_sem")
    return {
        "stalled_ranks": stalled,
        "first_stalled_rank": first,
        "open_span": open_span,
        "pending_sem": pending_sem,
        "in_flight_op": ({"op": in_flight.get("op"),
                          "method": in_flight.get("method"),
                          "world": in_flight.get("world")}
                         if in_flight else None),
        "in_flight_event": in_flight,
    }


def run_static_analysis(art: Artifacts, stall: dict,
                        kernel: Optional[str] = None,
                        mesh: Optional[Dict[str, int]] = None,
                        enabled: bool = True) -> Optional[dict]:
    """Consult PR 4's comm-graph sanitizer for the in-flight kernel:
    a pre-computed ``analysis-findings.json`` in the artifact dir wins
    (it captures the *deployed* kernel); otherwise replay the mapped
    registry kernel live at the incident's mesh."""
    ev = stall.get("in_flight_event")
    if not enabled or (ev is None and art.static_findings is None
                       and kernel is None):
        return None
    out: dict = {"kernel": kernel, "mesh": mesh, "findings": [],
                 "source": None}
    if art.static_findings is not None:
        rows = art.static_findings.get("findings", [])
        out["findings"] = rows
        out["source"] = "artifact"
        if rows and out["kernel"] is None:
            out["kernel"] = rows[0].get("kernel")
    else:
        if out["kernel"] is None and ev is not None:
            out["kernel"] = kernel_for_event(ev)
        if out["kernel"] is None:
            return None
        if out["mesh"] is None and ev is not None:
            axis = str(ev.get("axis") or "tp")
            extra = ev.get("extra") or {}
            if extra.get("axes") and extra.get("sizes"):
                out["mesh"] = dict(zip(extra["axes"],
                                       (int(s)
                                        for s in extra["sizes"])))
            else:
                out["mesh"] = {axis: int(ev.get("world", 2) or 2)}
        try:
            from triton_distributed_tpu import analysis
            for name, axis_sizes, findings in analysis.sweep(
                    [out["kernel"]], out["mesh"]):
                out["mesh"] = axis_sizes
                out["findings"] = [{
                    "kernel": name,
                    "kind": f.kind.value,
                    "rank": list(f.rank) if f.rank is not None
                    else None,
                    "sem": f.sem,
                    "ref": f.ref,
                    "message": f.message,
                } for f in findings]
            out["source"] = "live"
        except Exception as e:
            out["source"] = f"unavailable ({type(e).__name__})"
            return out
    hangy = [f for f in out["findings"]
             if f.get("kind") in ("deadlock", "unsatisfied_wait",
                                  "sem_leak", "sem_overdrain",
                                  "barrier_mismatch")]
    if hangy:
        f = hangy[0]
        out["could_hang"] = True
        out["verdict"] = (
            f"static graph says this wait CAN hang: [{f.get('kind')}] "
            f"{f.get('message')}")
        if stall.get("pending_sem") is None and f.get("sem"):
            stall["pending_sem"] = f["sem"]
    elif out["source"] and not str(out["source"]).startswith(
            "unavailable"):
        out["could_hang"] = False
        out["verdict"] = (
            "static graph pairs every wait with a signal — a hang "
            "here implies a runtime cause (peer death, link failure, "
            "or a stale semaphore from an earlier aborted launch)")
    return out


#: Resource-finding kinds that mean "this kernel could have corrupted
#: or overflowed memory" (vs merely failing to compile).
_RESOURCE_HANGY = ("vmem_overflow", "oob_block_index", "smem_overflow",
                   "tiling_illegal")


def run_resource_analysis(art: Artifacts, stall: dict,
                          kernel: Optional[str] = None,
                          mesh: Optional[Dict[str, int]] = None,
                          enabled: bool = False) -> Optional[dict]:
    """Consult the resource sanitizer (`analysis.resources`) for the
    in-flight kernel: could it have overflowed VMEM or walked off its
    page table?  Mirrors `run_static_analysis` (PR 5's comm-graph
    verdict): a shipped ``resource-findings.json`` wins; otherwise the
    mapped registry kernel is resource-analyzed live.  Opt-in
    (``--resources`` / a findings file) so existing golden incident
    reports stay byte-identical — the section key is simply absent."""
    ev = stall.get("in_flight_event")
    if not (enabled or art.resource_findings is not None):
        return None
    if ev is None and art.resource_findings is None and kernel is None:
        return None
    out: dict = {"kernel": kernel, "mesh": mesh, "findings": [],
                 "source": None}
    if art.resource_findings is not None:
        rows = art.resource_findings.get("findings", [])
        out["findings"] = rows
        out["source"] = "artifact"
        if rows and out["kernel"] is None:
            out["kernel"] = rows[0].get("kernel")
    else:
        if out["kernel"] is None and ev is not None:
            out["kernel"] = kernel_for_event(ev)
        if out["kernel"] is None:
            return None
        if out["mesh"] is None and ev is not None:
            # Same mesh derivation as run_static_analysis: multi-axis
            # kernels (torus family) carry axes/sizes in extra — a
            # fabricated single-axis mesh would make every builder
            # reject it and a zero-pair sweep read as "clean".
            axis = str(ev.get("axis") or "tp")
            extra = ev.get("extra") or {}
            if extra.get("axes") and extra.get("sizes"):
                out["mesh"] = dict(zip(extra["axes"],
                                       (int(s)
                                        for s in extra["sizes"])))
            else:
                out["mesh"] = {axis: int(ev.get("world", 2) or 2)}
        try:
            from triton_distributed_tpu import analysis
            swept = 0
            for name, axis_sizes, findings in analysis.sweep_resources(
                    [out["kernel"]], out["mesh"]):
                swept += 1
                out["mesh"] = axis_sizes
                out["findings"] = [{
                    "kernel": name,
                    "kind": f.kind.value,
                    "ref": f.ref,
                    "message": f.message,
                } for f in findings]
            if swept == 0:
                # Builder rejected the derived mesh: nothing was
                # analyzed — never report that as "clean".
                out["source"] = "unavailable (mesh not applicable)"
                return out
            out["source"] = "live"
        except Exception as e:
            out["source"] = f"unavailable ({type(e).__name__})"
            return out
    bad = [f for f in out["findings"]
           if f.get("kind") in _RESOURCE_HANGY]
    if bad:
        f = bad[0]
        out["could_overflow"] = True
        out["verdict"] = (
            f"resource sanitizer says this kernel CAN overflow VMEM "
            f"or walk off its index/page tables: [{f.get('kind')}] "
            f"{f.get('message')}")
    elif out["source"] and not str(out["source"]).startswith(
            "unavailable"):
        out["could_overflow"] = False
        out["verdict"] = (
            "resource sweep is clean — VMEM fits, tiling is legal and "
            "every block index (including page-table indirection) "
            "stays in bounds; an overflow here implies a runtime "
            "cause (corrupted table, stale autotune config)")
    return out


#: Protocol-finding kinds that mean "a partition/crash interleaving
#: could have wedged or double-applied a request" (vs the advisory
#: resume-key drift, which corrupts output but still terminates).
_PROTOCOL_WEDGY = ("proto_wedge", "proto_double_effect",
                   "proto_dead_route", "proto_phantom_commit")


def run_protocol_analysis(art: Artifacts,
                          enabled: bool = False) -> Optional[dict]:
    """Consult the cluster protocol model checker
    (`analysis.protocol_model`): could the partition/crash pattern in
    this incident have wedged a request, double-applied a delivery or
    routed onto a dead replica?  Mirrors `run_resource_analysis`: a
    shipped ``protocol-findings.json`` wins; otherwise the standard
    scope matrix (`analysis.protocol.sweep_protocol`) runs live.
    Opt-in (``--protocol`` / a findings file) so existing golden
    incident reports stay byte-identical — the section key is simply
    absent."""
    if not (enabled or art.protocol_findings is not None):
        return None
    out: dict = {"findings": [], "source": None}
    if art.protocol_findings is not None:
        out["findings"] = art.protocol_findings.get("findings", [])
        out["source"] = "artifact"
    else:
        try:
            from triton_distributed_tpu import analysis
            rows = []
            for label, findings in analysis.sweep_protocol():
                rows += [{
                    "scope": label,
                    "kind": f.kind.value,
                    "message": f.message,
                } for f in findings]
            out["findings"] = rows
            out["source"] = "live"
        except Exception as e:
            out["source"] = f"unavailable ({type(e).__name__})"
            return out
    bad = [f for f in out["findings"]
           if f.get("kind") in _PROTOCOL_WEDGY]
    if bad:
        f = bad[0]
        out["could_wedge"] = True
        out["verdict"] = (
            f"protocol checker says a partition/crash interleaving "
            f"CAN wedge or double-apply a request: [{f.get('kind')}] "
            f"{f.get('message')}")
    elif out["source"] and not str(out["source"]).startswith(
            "unavailable"):
        out["could_wedge"] = False
        out["verdict"] = (
            "protocol sweep is clean — every in-scope interleaving of "
            "delivery, loss, duplication, corruption, crash and "
            "staleness terminates with exactly-once effects; a wedged "
            "request here implies a cause outside the modeled scope "
            "(resource exhaustion, an unmodeled fault)")
    return out


def analyze_decisions(art: Artifacts, now: float) -> Optional[dict]:
    """Replay the closed loop's control decisions into the report
    (`observability.feedback`): the ``decisions-rank-*.jsonl``
    artifact when present, else the last-N summaries the heartbeats
    carried (a hung rank's beats are often the only surviving control
    state).  None — and thus NO report key, keeping pre-feedback
    golden reports byte-identical — when neither exists."""
    rows = list(art.decisions)
    source = "artifact"
    if not rows:
        for rank, hb in sorted(art.heartbeats.items()):
            for s in hb.get("decisions") or []:
                d = dict(s)
                d.setdefault("rank", rank)
                rows.append(d)
        rows.sort(key=lambda d: (float(d.get("ts", 0.0)),
                                 int(d.get("rank", 0))))
        source = "heartbeats"
    if not rows:
        return None
    by_consumer: Dict[str, int] = {}
    fallbacks = 0
    for d in rows:
        c = str(d.get("consumer", "?"))
        by_consumer[c] = by_consumer.get(c, 0) + 1
        if d.get("fallback"):
            fallbacks += 1
    recent = [{
        "age_s": round(now - float(d.get("ts", 0.0)), 3),
        "rank": int(d.get("rank", 0)),
        "consumer": d.get("consumer"),
        "op": d.get("op"),
        "choice": d.get("choice"),
        "why": (d.get("fallback")
                or _decision_why(d.get("inputs") or {})),
    } for d in rows[-10:]]
    return {"source": source, "count": len(rows),
            "fallbacks": fallbacks,
            "by_consumer": dict(sorted(by_consumer.items())),
            "recent": recent}


def _decision_why(inputs: dict) -> Optional[str]:
    """One compact clause from a decision's inputs snapshot."""
    parts = []
    if inputs.get("predicted_step_ms") is not None:
        s = f"predicted step {inputs['predicted_step_ms']}ms"
        if inputs.get("slo_tbt_ms") is not None:
            s += f" vs SLO {inputs['slo_tbt_ms']}ms"
        parts.append(s)
    if inputs.get("cleared_by"):
        parts.append(f"cleared by {inputs['cleared_by']}")
    stale = inputs.get("stale")
    if isinstance(stale, dict) and stale.get("z") is not None:
        parts.append(f"winner z={stale['z']}")
    if inputs.get("contended_links"):
        parts.append("contended "
                     + ",".join(inputs["contended_links"][:3]))
    elif inputs.get("axis_busy"):
        busy = {a: u for a, u in inputs["axis_busy"].items() if u}
        if busy:
            parts.append("busy " + ",".join(
                f"{a}={u}" for a, u in sorted(busy.items())))
    return "; ".join(parts) or None


def analyze_cluster(art: Artifacts) -> Optional[dict]:
    """Replay the serving cluster's router artifact
    (``router-state.json``, `serving.cluster`) into the report: the
    replica health table and every executed failover, so "which
    replica died / straggled, and what happened to its requests" is
    answered by name.  None — and thus NO report key, keeping
    pre-cluster golden reports byte-identical — without the artifact.
    """
    if art.router is None:
        return None
    replicas = [{
        "id": r.get("id"), "name": r.get("name"),
        "alive": r.get("alive"), "quarantined": r.get("quarantined"),
        "fail_reason": r.get("fail_reason"),
        "hb_age_s": r.get("hb_age_s"),
        "routed": r.get("routed"),
        "queue_depth": r.get("queue_depth"),
    } for r in art.router.get("replicas", [])]
    failovers = list(art.router.get("failovers", []))
    failed = [r for r in replicas
              if not r.get("alive") or r.get("quarantined")]
    out = {
        "mode": art.router.get("mode"),
        "replicas": replicas,
        "failovers": failovers,
        "failed_replicas": [r["name"] for r in failed],
        "kv_shipped_bytes": art.router.get("kv_shipped_bytes"),
        "shipments": art.router.get("shipments"),
    }
    if art.router.get("readmits"):
        # Key absent unless a probation re-admission happened, so
        # pre-hysteresis reports stay byte-identical.
        out["readmits"] = list(art.router["readmits"])
    if art.router.get("merged_from"):
        # Key absent for single-router artifacts, so every existing
        # golden stays byte-identical; present, it says how many
        # per-rank router docs this Cluster section folds together.
        out["merged_from"] = art.router["merged_from"]
    return out


def analyze_chaos(art: Artifacts, now: float) -> Optional[dict]:
    """Replay the chaos harness's fault artifact (``faults.jsonl``,
    `serving.cluster.chaos`) into the report: which fault classes a
    seeded schedule injected, into what, when — so "was this
    incident injected, and what was injected" is answered from the
    artifact alone.  None — and thus NO report key, keeping
    pre-chaos golden reports byte-identical — without the artifact.
    """
    if not art.faults:
        return None
    by_class: Dict[str, int] = {}
    seeds = set()
    for d in art.faults:
        c = str(d.get("fault", "?"))
        by_class[c] = by_class.get(c, 0) + 1
        try:
            if d.get("seed") is not None:
                seeds.add(int(d["seed"]))
        except (TypeError, ValueError):
            pass    # malformed line: report without it, never crash
    recent = [{
        "age_s": round(now - _num(d.get("ts")), 3),
        "fault": d.get("fault"),
        "target": d.get("target"),
        "inputs": (d.get("inputs") if isinstance(d.get("inputs"),
                                                 dict) else {}),
    } for d in art.faults[-10:]]
    return {"count": len(art.faults),
            "by_class": dict(sorted(by_class.items())),
            "seeds": sorted(seeds),
            "recent": recent}


#: Slowest-request rows the lineage section keeps.
LINEAGE_SLOWEST_K = 5


def analyze_lineage(art: Artifacts, now: float) -> Optional[dict]:
    """Replay the request-lineage artifact (``lineage*.jsonl``,
    `observability.lineage`) into the report: per-request TTFT
    decomposed into hop intervals (exact on the recording clock — the
    asserted invariant, not an estimate), the slowest-K table with
    each request's dominant hop, shipment retries cross-referenced to
    the injected faults (`chaos.faults_by_shipment`), and which hop
    every still-in-flight request is stuck in.  None — and thus NO
    report key, keeping pre-lineage golden reports byte-identical —
    without the artifact."""
    if not art.lineage:
        return None
    from triton_distributed_tpu.observability.lineage import (
        TERMINAL_HOPS, group_by_request, ttft_breakdown)
    from triton_distributed_tpu.serving.cluster.chaos import (
        faults_by_shipment)
    fault_ships = faults_by_shipment(art.faults)
    by_req = group_by_request(art.lineage)
    completed: List[dict] = []
    in_flight: List[dict] = []
    hop_totals: Dict[str, float] = {}
    all_exact = True
    for rid, evs in by_req.items():
        retries = sum(1 for e in evs if e.get("hop") == "ship_retry")
        faults_hit = sorted({
            fault_ships[t] for e in evs
            if e.get("hop") in ("ship", "ship_retry")
            for t in [(e.get("detail") or {}).get("token")]
            if t in fault_ships})
        bd = ttft_breakdown(evs)
        if bd is None:
            last = evs[-1]
            if last.get("hop") not in TERMINAL_HOPS:
                in_flight.append({
                    "request_id": rid,
                    "stuck_in": last.get("hop"),
                    "age_s": round(now - _num(last.get("ts")), 6),
                })
            continue
        # The exactness the analyzer proves is relative to the
        # recorded events; the part the DOCTOR can falsify is whether
        # the lineage starts where a request starts.  A torn artifact
        # that lost its head (submit/enqueue line) would silently
        # under-report TTFT — flag it instead of calling it exact.
        head_ok = evs[0].get("hop") in ("submit", "enqueue")
        all_exact = all_exact and bd["exact"] and head_ok
        for hop, ms in bd["by_hop_ms"].items():
            hop_totals[hop] = round(hop_totals.get(hop, 0.0) + ms, 6)
        row = {
            "request_id": rid,
            "ttft_ms": bd["ttft_ms"],
            "dominant_hop": bd["dominant_hop"],
            "dominant_ms": bd["dominant_ms"],
            "by_hop_ms": bd["by_hop_ms"],
            "exact": bd["exact"] and head_ok,
        }
        if not head_ok:
            row["head_truncated"] = True
        if retries:
            row["ship_retries"] = retries
        if faults_hit:
            row["faults_absorbed"] = faults_hit
        completed.append(row)
    completed.sort(key=lambda r: (-r["ttft_ms"], str(r["request_id"])))
    slowest = completed[:LINEAGE_SLOWEST_K]
    out = {
        "events": len(art.lineage),
        "requests": len(by_req),
        "completed": len(completed),
        "exact": all_exact,
        "hop_totals_ms": dict(sorted(hop_totals.items())),
        "slowest": slowest,
    }
    if in_flight:
        in_flight.sort(key=lambda r: (-r["age_s"],
                                      str(r["request_id"])))
        out["in_flight"] = in_flight[:LINEAGE_SLOWEST_K]
    return out


def analyze_replay(art: Artifacts) -> Optional[dict]:
    """Summarize the deterministic record-&-replay artifact
    (``replay.jsonl``, `observability.replay`): completeness, what
    was captured, and any counterfactual verdicts a previous
    ``doctor --replay`` (or `replay_run` caller) appended — each
    rendered as the causality clause the verdict quotes.  This pass
    only READS the artifact; live re-execution is the CLI's
    ``--replay`` mode."""
    if not art.replay:
        return None
    from triton_distributed_tpu.observability.replay import (
        causality_clause, validate_replay)
    problems = validate_replay(art.replay)
    by_kind: Dict[str, int] = {}
    for r in art.replay:
        k = str(r.get("kind"))
        by_kind[k] = by_kind.get(k, 0) + 1
    clock_readings = sum(len(r.get("t") or []) for r in art.replay
                         if r.get("kind") == "clock")
    counterfactuals = []
    for r in art.replay:
        if r.get("kind") != "counterfactual":
            continue
        counterfactuals.append({
            "override": r.get("override"),
            "first_divergence": r.get("first_divergence"),
            "clause": causality_clause(r),
        })
    return {
        "status": "INCOMPLETE" if problems else "COMPLETE",
        "problems": problems,
        "rows": len(art.replay),
        "clock_readings": clock_readings,
        "requests": by_kind.get("submit", 0),
        "faults": by_kind.get("fault_injected", 0),
        "wire_events": by_kind.get("wire", 0),
        "counterfactuals": counterfactuals,
    }


def analyze_slo(art: Artifacts) -> Optional[dict]:
    """Ingest ``slo-state.json`` (`observability.slo`) into the
    report: per-class compliance against objective, error budget
    remaining, burn rates per window, and — via the cost join — the
    tenant dominating each burning class's breaches.  None (NO report
    key, golden reports byte-identical) without the artifact."""
    st = art.slo_state
    if not st:
        return None
    classes = []
    burning = []
    for name in sorted(st.get("classes", {})):
        c = st["classes"][name]
        row = {
            "class": name,
            "objective": c.get("objective"),
            "target_ttft_ms": c.get("target_ttft_ms"),
            "target_tbt_ms": c.get("target_tbt_ms"),
            "requests": c.get("total", 0),
            "breaches": c.get("breaches", 0),
            "compliance": c.get("compliance"),
            "budget_remaining": c.get("budget_remaining"),
            "burn": c.get("burn", {}),
            "alerting": bool(c.get("alerting")),
        }
        classes.append(row)
        if row["alerting"]:
            burning.append(name)
    out = {
        "schema": st.get("schema"),
        "alerts_fired": st.get("alerts_fired", 0),
        "burn_alert_threshold": st.get("burn_alert_threshold"),
        "windows_s": st.get("windows_s"),
        "classes": classes,
        "burning": burning,
    }
    if st.get("dominant_tenant"):
        out["dominant_tenant"] = st["dominant_tenant"]
    # Tenant bill (cost join): who the burn is attributable to, in
    # device-µs terms — carried only when cost accounting was armed.
    if isinstance(st.get("tenant_costs"), dict) and st["tenant_costs"]:
        out["tenant_costs"] = st["tenant_costs"]
    return out


def analyze_timeseries(art: Artifacts) -> Optional[dict]:
    """Replay ``timeseries-rank-*.jsonl`` (`observability.timeseries`)
    into pre-incident trends: which watched gauges were monotonically
    rising or falling into the newest sample, over how many samples
    and how much virtual time.  None without the artifact."""
    rows = art.timeseries
    if not rows:
        return None
    from triton_distributed_tpu.observability.timeseries import (
        series_trends)
    ts0 = _num(rows[0].get("ts"))
    ts1 = _num(rows[-1].get("ts"))
    return {
        "samples": len(rows),
        "span_s": round(ts1 - ts0, 6),
        "trends": series_trends(rows),
    }


def analyze_fleet(art: Artifacts, now: float) -> Optional[dict]:
    """Replay the fleet telemetry plane's artifacts
    (``telemetry*.jsonl`` + ``alerts.jsonl``,
    `observability.telemetry`) into the report: fold every frame
    through a fresh :class:`FleetCollector` (the same idempotent fold
    the live front door ran), summarize the per-source fleet table,
    and reduce the alert transition log to what was firing at the
    end.  None — and thus NO report key, keeping pre-telemetry golden
    reports byte-identical — without either artifact."""
    if not art.telemetry and not art.alerts:
        return None
    from triton_distributed_tpu.observability.telemetry import (
        FleetCollector)
    from triton_distributed_tpu.observability.watch import (
        firing_from_events)
    collector = FleetCollector()
    for frame in art.telemetry:
        collector.fold(frame)
    table = []
    for row in collector.fleet_table(now):
        table.append({k: row.get(k) for k in (
            "source", "role", "rank", "seq", "age_s", "queue_depth",
            "active_slots", "kv_page_occupancy", "step_us",
            "burn_max", "alive", "quarantined", "fail_reason")
            if k in row})
    by_rule: Dict[str, int] = {}
    for e in art.alerts:
        if e.get("state") == "firing":
            r = str(e.get("rule", "?"))
            by_rule[r] = by_rule.get(r, 0) + 1
    firing = [{
        "rule": e.get("rule"), "severity": e.get("severity"),
        "target": e.get("target"), "ts": e.get("ts"),
        "inputs": (e.get("inputs")
                   if isinstance(e.get("inputs"), dict) else {}),
    } for e in firing_from_events(art.alerts)]
    recent = [{
        "age_s": round(now - _num(e.get("ts")), 3),
        "rule": e.get("rule"), "severity": e.get("severity"),
        "target": e.get("target"), "state": e.get("state"),
    } for e in art.alerts[-10:]]
    return {
        "frames": len(art.telemetry),
        "sources": collector.sources(),
        "table": table,
        "alerts": len(art.alerts),
        "alerts_by_rule": dict(sorted(by_rule.items())),
        "firing": firing,
        "recent_alerts": recent,
    }


def analyze_links(art: Artifacts) -> dict:
    from triton_distributed_tpu.observability import links as _links
    from triton_distributed_tpu.observability.events import KernelEvent

    events = []
    for rank in sorted(art.flights):
        for ev in art.flights[rank].get("events", []):
            try:
                events.append(KernelEvent.from_dict(ev))
            except (TypeError, KeyError):
                continue
    return {
        "hot": _links.hot_links(events, top=5),
        "contention": _links.detect_contention(events)[:10],
    }


def analyze_timeline(art: Artifacts, store) -> Tuple[dict, dict]:
    """(straggler_report-with-anomalies, timeline summary)."""
    from triton_distributed_tpu.observability import timeline as tl
    if not art.traces:
        return {}, {"merged": False, "truncated_ranks": []}
    report = tl.straggler_report(art.traces, store=store)
    summary = {
        "merged": True,
        "truncated_ranks": report.get("timeline_truncated_ranks", []),
        "spans_compared": len(report.get("spans", {})),
    }
    return report, summary


# ---------------------------------------------------------------------------
# Report assembly
# ---------------------------------------------------------------------------

def diagnose(dirs: Sequence[str], *, kernel: Optional[str] = None,
             mesh: Optional[Dict[str, int]] = None,
             now: Optional[float] = None,
             interval: Optional[float] = None,
             static: bool = True,
             resources: bool = False,
             protocol: bool = False) -> Optional[dict]:
    """Build the full incident report dict (None when the directories
    hold no artifacts at all)."""
    from triton_distributed_tpu.observability.anomaly import (
        BaselineStore, straggler_ranking)

    art = Artifacts(dirs)
    if art.empty():
        return None
    if interval is None:
        try:
            interval = float(os.environ.get("TDT_HEARTBEAT_INTERVAL",
                                            "1.0"))
        except ValueError:
            interval = 1.0
    now = art.newest_timestamp() if now is None else float(now)

    rank_table = build_rank_table(art, now, interval)
    stall = detect_stall(art, rank_table)
    static_out = run_static_analysis(art, stall, kernel=kernel,
                                     mesh=mesh, enabled=static)
    resource_out = run_resource_analysis(art, stall, kernel=kernel,
                                         mesh=mesh, enabled=resources)
    protocol_out = run_protocol_analysis(art, enabled=protocol)
    link_out = analyze_links(art)
    # Baselines pinned to the artifact dir: the report must not change
    # with whatever ambient baseline file the operator's CWD holds.
    store = BaselineStore(os.path.join(
        art.dirs[0], "anomaly_baselines.json"))
    straggler_rep, timeline_summary = analyze_timeline(art, store)
    stragglers = straggler_ranking(straggler_rep, art.flights)
    anomalies = straggler_rep.get("anomalies", [])

    incompleteness = []
    for rank, row in sorted(rank_table.items(), key=lambda kv:
                            int(kv[0])):
        if row["dropped_spans"]:
            incompleteness.append(
                f"rank {rank}: {row['dropped_spans']} span(s) "
                "evicted from the trace ring — its timeline lane is "
                "incomplete")
        if row["dropped_events"]:
            incompleteness.append(
                f"rank {rank}: {row['dropped_events']} event(s) "
                "evicted from the flight ring — oldest in-flight "
                "context is lost")
    for rank in timeline_summary.get("truncated_ranks", []):
        incompleteness.append(
            f"rank {rank}: trace file truncated (killed mid-write); "
            "complete events were salvaged")

    # Paged-KV pressure (serving gauges ride the heartbeats): a rank
    # at ~full page occupancy is thrashing on eviction/preemption —
    # name it, with the prefix-cache share so "cache bloat" and "real
    # load" read differently.  Section (and verdict note) only exist
    # when the paged gauges are present, so non-serving incidents'
    # reports are byte-identical to before.
    page_pressure = []
    for rank, row in sorted(rank_table.items(),
                            key=lambda kv: int(kv[0])):
        sv = row.get("serving") or {}
        occ = sv.get("serving_kv_page_occupancy")
        if occ is None:
            continue
        page_pressure.append({
            "rank": int(rank),
            "page_occupancy": round(float(occ), 4),
            "pages_free": sv.get("serving_kv_pages_free"),
            "pages_used": sv.get("serving_kv_pages_used"),
            "prefix_cache_pages": sv.get("serving_prefix_cache_pages"),
            "pressure": float(occ) >= PAGE_PRESSURE_OCCUPANCY,
        })

    # KV cache hierarchy (the serving_kvtier_* gauges ride the
    # heartbeats, paged serving only): per-tier hit profile — where
    # prefix pages actually came from (device / host spill / peer
    # shipment / disk) — plus degraded tier reads (corrupt or lost
    # parked content that fell back to recompute).  Section (and
    # verdict note) only exist when the gauges are present, so
    # pre-tier incidents' reports are byte-identical.
    kvtier = []
    for rank, row in sorted(rank_table.items(),
                            key=lambda kv: int(kv[0])):
        sv = row.get("serving") or {}
        if sv.get("serving_kvtier_hit_device") is None:
            continue
        hits = {t: int(_num(sv.get(f"serving_kvtier_hit_{t}")))
                for t in ("device", "host", "peer", "disk")}
        missed = int(_num(sv.get("serving_kvtier_miss")))
        fallbacks = int(_num(sv.get("serving_kvtier_fallbacks")))
        warm_cfg = int(_num(sv.get("serving_kvtier_warm_tiers")))
        dropped = int(_num(sv.get("serving_kvtier_dropped_evictions")))
        served = sum(hits.values())
        # Collapse = the warm tiers stopped earning their bytes:
        # tier reads degraded to recompute (fallbacks — corrupt/lost
        # parked pages), or a CONFIGURED spill tier is letting
        # evictions destroy pages anyway (full pool under sustained
        # pressure).  Plain misses never collapse: a paged engine
        # with no warm tier configured (or a diverse-prompt workload
        # that simply has no reusable prefixes) is healthy.
        collapsed = (fallbacks > 0
                     or (warm_cfg > 0 and dropped >= 8))
        kvtier.append({
            "rank": int(rank), "hits": hits, "miss": missed,
            "fallbacks": fallbacks, "dropped_evictions": dropped,
            "warm_configured": bool(warm_cfg),
            "hit_rate": (round(served / (served + missed), 4)
                         if served + missed else None),
            "collapsed": collapsed,
        })

    # Speculative-decoding health (the accept-rate gauge rides the
    # heartbeats): a collapsed accept rate means verify dispatches
    # burn K+1 model steps to commit ~1 token — the draft source has
    # stopped predicting this workload and speculation should be
    # retuned or disabled.  Section (and verdict note) only exist
    # when the gauge is present, so non-speculative incidents'
    # reports are byte-identical to before.
    spec_health = []
    for rank, row in sorted(rank_table.items(),
                            key=lambda kv: int(kv[0])):
        sv = row.get("serving") or {}
        rate = sv.get("serving_spec_accept_rate")
        if rate is None:
            continue
        spec_health.append({
            "rank": int(rank),
            "accept_rate": round(float(rate), 4),
            "collapsed": float(rate) < SPEC_ACCEPT_COLLAPSE,
        })

    in_flight = stall.pop("in_flight_event", None)
    report = {
        "schema": REPORT_SCHEMA,
        "now_unix": round(now, 3),
        "heartbeat_interval_s": interval,
        "ranks": art.ranks(),
        "artifacts": {
            "dirs": [os.path.basename(d.rstrip("/")) or d
                     for d in art.dirs],
            "traces": len(art.traces),
            "flights": len(art.flights),
            "heartbeats": len(art.heartbeats),
            "metrics": len(art.metrics),
            "static_findings_file": art.static_findings is not None,
        },
        "rank_table": rank_table,
        "stall": stall,
        "static": static_out,
        "links": link_out,
        "stragglers": stragglers,
        "anomalies": anomalies[:10],
        "timeline": timeline_summary,
        "incompleteness": incompleteness,
    }
    if page_pressure:
        report["page_pressure"] = page_pressure
    if kvtier:
        report["kvtier"] = kvtier
    if spec_health:
        report["spec"] = spec_health
    # Key absent unless the resource consult ran (opt-in / findings
    # file) — golden incident reports stay byte-identical.
    if resource_out is not None:
        report["resources"] = resource_out
    # Protocol consult: key absent unless opted in (--protocol / a
    # protocol-findings.json artifact) — same golden discipline.
    if protocol_out is not None:
        report["protocol"] = protocol_out
    # Control decisions: key absent when no decisions artifact (and
    # no heartbeat-carried summaries) exist — same golden discipline.
    decision_out = analyze_decisions(art, now)
    if decision_out is not None:
        report["decisions"] = decision_out
    # Cluster/router state: key absent without a router-state.json
    # artifact, so non-cluster incidents stay byte-identical.
    cluster_out = analyze_cluster(art)
    if cluster_out is not None:
        report["cluster"] = cluster_out
    # Chaos harness faults: key absent without a faults.jsonl
    # artifact — same golden discipline.
    chaos_out = analyze_chaos(art, now)
    if chaos_out is not None:
        report["chaos"] = chaos_out
    # Request lineage: key absent without a lineage*.jsonl artifact —
    # same golden discipline.
    lineage_out = analyze_lineage(art, now)
    if lineage_out is not None:
        report["lineage"] = lineage_out
    # SLO error budgets: key absent without an slo-state.json
    # artifact — same golden discipline.
    slo_out = analyze_slo(art)
    if slo_out is not None:
        report["slo"] = slo_out
    # Pre-incident time series: key absent without a
    # timeseries-rank-*.jsonl artifact — same golden discipline.
    timeseries_out = analyze_timeseries(art)
    if timeseries_out is not None:
        report["timeseries"] = timeseries_out
    # Record & replay: key absent without a replay.jsonl artifact —
    # same golden discipline.
    replay_out = analyze_replay(art)
    if replay_out is not None:
        report["replay"] = replay_out
    # Fleet telemetry plane: key absent without telemetry*.jsonl /
    # alerts.jsonl artifacts — same golden discipline.
    fleet_out = analyze_fleet(art, now)
    if fleet_out is not None:
        report["fleet"] = fleet_out
    report["verdict"] = _verdict(report, in_flight)
    return report


#: Page occupancy at/above which doctor calls out KV page pressure.
PAGE_PRESSURE_OCCUPANCY = 0.9

#: Speculative accept rate below which the doctor calls out a
#: collapse: each verify dispatch then spends K+1 model steps to
#: commit barely more than 1 token.
SPEC_ACCEPT_COLLAPSE = 0.3


def _verdict(report: dict, in_flight: Optional[dict]) -> str:
    stall = report["stall"]
    static_out = report.get("static") or {}
    hot = report["links"].get("hot") or []
    hot_s = (f"; hottest link {hot[0]['link']} "
             f"({hot[0]['bytes']} bytes: "
             f"{', '.join(hot[0]['ops'])})" if hot else "")
    pressured = [e for e in report.get("page_pressure", [])
                 if e["pressure"]]
    if pressured:
        worst = max(pressured, key=lambda e: e["page_occupancy"])
        hot_s += (f"; KV page pressure on rank {worst['rank']} "
                  f"({worst['page_occupancy']:.0%} of pages in use, "
                  f"{worst['pages_free']} free)")
    tier_bad = [e for e in report.get("kvtier", [])
                if e["collapsed"]]
    if tier_bad:
        worst = max(tier_bad, key=lambda e: (e["fallbacks"],
                                             e["dropped_evictions"]))
        if worst["fallbacks"]:
            hot_s += (f"; KV tier degradation on rank "
                      f"{worst['rank']} ({worst['fallbacks']} tier "
                      f"read(s) fell back to recompute — corrupt or "
                      f"lost parked pages)")
        else:
            hot_s += (f"; KV tier overflow on rank {worst['rank']} "
                      f"({worst['dropped_evictions']} evicted "
                      f"page(s) destroyed despite a configured "
                      f"spill tier — the hierarchy is not absorbing "
                      f"evictions)")
    collapsed = [e for e in report.get("spec", [])
                 if e["collapsed"]]
    if collapsed:
        worst = min(collapsed, key=lambda e: e["accept_rate"])
        hot_s += (f"; speculative accept rate collapsed on rank "
                  f"{worst['rank']} ({worst['accept_rate']:.0%} < "
                  f"{SPEC_ACCEPT_COLLAPSE:.0%} — verify dispatches "
                  f"are burning draft steps for ~1 token; retune or "
                  f"disable the drafter)")
    # Cluster failovers: name the failed replica(s) in the verdict
    # (clause only exists when a router artifact was ingested).
    failover_s = ""
    for f in (report.get("cluster") or {}).get("failovers", []):
        failover_s += (f"; cluster: {f.get('replica')} failed over "
                       f"({f.get('reason')}), {f.get('requeued')} "
                       f"request(s) re-queued")
    hot_s += failover_s
    # Injected faults: name the fault classes (clause only exists
    # when a faults.jsonl artifact was ingested) — an incident with a
    # chaos schedule behind it must say so, by class.
    chaos = report.get("chaos")
    chaos_s = ""
    if chaos:
        chaos_s = (f"; chaos: {chaos['count']} injected fault(s) — "
                   f"classes {', '.join(sorted(chaos['by_class']))}")
    hot_s += chaos_s
    # Request lineage: the verdict NAMES the dominant hop of the
    # slowest request (clause only exists when a lineage artifact was
    # ingested) — "why was it slow" answered in one clause.
    lineage = report.get("lineage")
    if lineage and lineage.get("slowest"):
        s = lineage["slowest"][0]
        fault_s = (" absorbing a "
                   + "/".join(s["faults_absorbed"]) + " fault"
                   if s.get("faults_absorbed") else "")
        hot_s += (f"; slowest request {s['request_id']} spent "
                  f"{s['dominant_ms']}ms of its {s['ttft_ms']}ms "
                  f"TTFT in hop '{s['dominant_hop']}'{fault_s}")
    if lineage and lineage.get("in_flight"):
        f = lineage["in_flight"][0]
        hot_s += (f"; request {f['request_id']} still stuck in hop "
                  f"'{f['stuck_in']}' ({f['age_s']}s)")
    # SLO burn: the verdict NAMES the burning class — and, when the
    # cost join identified one, the tenant dominating its breaches
    # (clause only exists when an slo-state artifact was ingested).
    slo = report.get("slo")
    if slo and slo.get("burning"):
        worst = min(
            (c for c in slo["classes"] if c["class"] in slo["burning"]),
            key=lambda c: (c.get("budget_remaining")
                           if c.get("budget_remaining") is not None
                           else 0.0))
        tenant_s = (f" — dominated by tenant "
                    f"'{slo['dominant_tenant']}'"
                    if slo.get("dominant_tenant") else "")
        budget = worst.get("budget_remaining")
        budget_s = (f", {budget:.0%} of error budget left"
                    if isinstance(budget, (int, float)) else "")
        hot_s += (f"; SLO class '{worst['class']}' is burning its "
                  f"error budget{budget_s}{tenant_s}")
    # Pre-incident trends: one clause for the longest rising run
    # (what was building up before things broke).
    tser = report.get("timeseries")
    if tser and tser.get("trends"):
        rising = [t for t in tser["trends"]
                  if t["direction"] == "rising"]
        if rising:
            t = max(rising, key=lambda t: t["run"])
            hot_s += (f"; {t['metric']} rose for {t['run']} straight "
                      f"samples (+{t['delta']}) into the incident")
    # Counterfactual replay: the causality clause (clause only
    # exists when a replay.jsonl artifact was ingested) — the
    # verdict states what the incident would have looked like with
    # one recorded input overridden.  A torn recording says so
    # truthfully instead.
    rpl = report.get("replay")
    if rpl:
        if rpl["status"] == "INCOMPLETE":
            hot_s += ("; replay recording is INCOMPLETE ("
                      + "; ".join(rpl["problems"])
                      + ") — the run cannot be re-executed")
        for c in rpl.get("counterfactuals", []):
            if c.get("clause"):
                hot_s += f"; counterfactually, {c['clause']}"
    # Fleet alerts: the verdict NAMES the firing rule and its victim
    # (clause only exists when a telemetry/alerts artifact was
    # ingested) — the live plane's page and the post-mortem agree on
    # who to blame.
    fleet = report.get("fleet")
    fleet_s = ""
    if fleet and fleet.get("firing"):
        worst = fleet["firing"][0]
        more = (f" (+{len(fleet['firing']) - 1} more)"
                if len(fleet["firing"]) > 1 else "")
        fleet_s = (f"; fleet alert '{worst['rule']}' firing on "
                   f"{worst['target']}{more}")
    hot_s += fleet_s
    if stall["first_stalled_rank"] is not None:
        r = stall["first_stalled_rank"]
        what = (f" inside {stall['open_span']!r}"
                if stall.get("open_span") else "")
        op_s = ""
        if in_flight is not None:
            op_s = (f" with {in_flight.get('op')}"
                    f"[{in_flight.get('method')}] in flight")
        sem_s = (f", blocked on semaphore {stall['pending_sem']!r}"
                 if stall.get("pending_sem") else "")
        verdict = (f"rank {r} stalled first{what}{op_s}{sem_s}")
        if static_out.get("verdict"):
            verdict += f". {static_out['verdict']}"
        resource_out = report.get("resources") or {}
        if resource_out.get("verdict"):
            verdict += f". {resource_out['verdict']}"
        protocol_out = report.get("protocol") or {}
        if protocol_out.get("verdict"):
            verdict += f". {protocol_out['verdict']}"
        return verdict + hot_s + "."
    stragglers = report.get("stragglers") or []
    anomalies = report.get("anomalies") or []
    contention = report["links"].get("contention") or []
    if stragglers or anomalies or contention:
        parts = ["no rank stalled"]
        if stragglers:
            s = stragglers[0]
            link_s = (f" (blamed link {s['blamed_link']})"
                      if s.get("blamed_link") else "")
            parts.append(
                f"rank {s['rank']} is the consistent straggler — it "
                f"charged peers {s['barrier_wait_charged_us']:.0f}us "
                f"of barrier wait over {', '.join(s['spans'])}"
                f"{link_s}")
        if anomalies:
            a = anomalies[0]
            parts.append(
                f"slowest anomaly: {a['name']}#{a['occurrence']} on "
                f"rank {a['rank']} (z={a['z']:+.1f})")
        if contention:
            c = contention[0]
            parts.append(
                f"contention between {' and '.join(c['ops'])} on "
                f"link(s) {', '.join(c['links'])}")
        return "; ".join(parts) + hot_s + "."
    if failover_s:
        # A failover IS the incident — it must never read as "no
        # incident detected" with the dead replica in a subclause.
        return "cluster incident" + hot_s + "."
    if fleet_s:
        # Same discipline for a firing fleet alert: the page IS the
        # incident.
        return "fleet alert firing" + hot_s + "."
    if chaos_s:
        # Faults were injected and everything absorbed them: that is
        # the headline (the run was a chaos schedule, not an
        # organic incident).
        return "chaos schedule absorbed" + hot_s + "."
    return ("no incident detected: heartbeats fresh, no anomalies, "
            "no link contention" + hot_s + ".")


# ---------------------------------------------------------------------------
# Markdown rendering
# ---------------------------------------------------------------------------

def render_markdown(report: dict) -> str:
    lines = ["# Incident report", ""]
    lines += [f"**Verdict:** {report['verdict']}", ""]
    a = report["artifacts"]
    lines += [
        f"Ranks {report['ranks']} — {a['traces']} trace(s), "
        f"{a['flights']} flight dump(s), {a['heartbeats']} "
        f"heartbeat(s), {a['metrics']} metrics export(s)"
        + (", static findings file" if a["static_findings_file"]
           else "") + ".", ""]

    lines += ["## Ranks", "",
              "| rank | beat age (s) | state | step | last span | "
              "in-flight op | dropped |",
              "|---|---|---|---|---|---|---|"]
    for rank, row in sorted(report["rank_table"].items(),
                            key=lambda kv: int(kv[0])):
        ev = row.get("last_event") or {}
        dropped = (f"{row['dropped_spans']}s/"
                   f"{row['dropped_events']}e"
                   if (row["dropped_spans"] or row["dropped_events"])
                   else "-")
        lines.append(
            f"| {rank} "
            f"| {row['heartbeat_age_s'] if row['heartbeat_age_s'] is not None else '-'} "
            f"| {'STALLED' if row['stale'] else 'ok'} "
            f"| {row['step'] if row['step'] is not None else '-'} "
            f"| {row['last_span'] or '-'} "
            f"| {ev.get('op', '-')}"
            f"{'[' + ev['method'] + ']' if ev.get('method') else ''} "
            f"| {dropped} |")
    lines.append("")

    pressure = report.get("page_pressure")
    if pressure:
        lines += ["## KV page pressure", "",
                  "| rank | occupancy | used | free | prefix-cache "
                  "| state |", "|---|---|---|---|---|---|"]
        for e in pressure:
            lines.append(
                f"| {e['rank']} | {e['page_occupancy']:.0%} "
                f"| {e['pages_used'] if e['pages_used'] is not None else '-'} "
                f"| {e['pages_free'] if e['pages_free'] is not None else '-'} "
                f"| {e['prefix_cache_pages'] if e['prefix_cache_pages'] is not None else '-'} "
                f"| {'PRESSURE' if e['pressure'] else 'ok'} |")
        lines.append("")

    kvtier = report.get("kvtier")
    if kvtier:
        lines += ["## KV tier", "",
                  "| rank | device | host | peer | disk | miss "
                  "| degraded | dropped | hit rate | state |",
                  "|---|---|---|---|---|---|---|---|---|---|"]
        for e in kvtier:
            h = e["hits"]
            rate = (f"{e['hit_rate']:.0%}"
                    if e["hit_rate"] is not None else "-")
            lines.append(
                f"| {e['rank']} | {h['device']} | {h['host']} "
                f"| {h['peer']} | {h['disk']} | {e['miss']} "
                f"| {e['fallbacks']} | {e['dropped_evictions']} "
                f"| {rate} "
                f"| {'COLLAPSED' if e['collapsed'] else 'ok'} |")
        lines.append("")

    spec = report.get("spec")
    if spec:
        lines += ["## Speculative decoding", "",
                  "| rank | accept rate | state |", "|---|---|---|"]
        for e in spec:
            lines.append(
                f"| {e['rank']} | {e['accept_rate']:.0%} "
                f"| {'COLLAPSED' if e['collapsed'] else 'ok'} |")
        lines.append("")

    stall = report["stall"]
    if stall["first_stalled_rank"] is not None:
        lines += ["## Stall", ""]
        lines += [f"- stalled ranks: {stall['stalled_ranks']}",
                  f"- first to stall: rank "
                  f"{stall['first_stalled_rank']}",
                  f"- open span at stall: {stall['open_span'] or '-'}",
                  f"- pending semaphore: "
                  f"{stall['pending_sem'] or 'unknown'}"]
        if stall.get("in_flight_op"):
            op = stall["in_flight_op"]
            lines.append(f"- in flight: {op['op']}[{op['method']}] "
                         f"world={op['world']}")
        lines.append("")

    static_out = report.get("static")
    if static_out:
        lines += ["## Static comm-graph check", ""]
        lines += [f"- kernel: {static_out.get('kernel') or '-'} "
                  f"(mesh {static_out.get('mesh') or '-'}, source "
                  f"{static_out.get('source')})"]
        for f in static_out.get("findings", [])[:5]:
            lines.append(f"- [{f.get('kind')}] sem={f.get('sem')} "
                         f"{f.get('message')}")
        if static_out.get("verdict"):
            lines.append(f"- **{static_out['verdict']}**")
        lines.append("")

    resource_out = report.get("resources")
    if resource_out:
        lines += ["## Static resource check", ""]
        lines += [f"- kernel: {resource_out.get('kernel') or '-'} "
                  f"(mesh {resource_out.get('mesh') or '-'}, source "
                  f"{resource_out.get('source')})"]
        for f in resource_out.get("findings", [])[:5]:
            lines.append(f"- [{f.get('kind')}] ref={f.get('ref')} "
                         f"{f.get('message')}")
        if resource_out.get("verdict"):
            lines.append(f"- **{resource_out['verdict']}**")
        lines.append("")

    protocol_out = report.get("protocol")
    if protocol_out:
        lines += ["## Static protocol check", ""]
        lines += [f"- source: {protocol_out.get('source')}"]
        for f in protocol_out.get("findings", [])[:5]:
            lines.append(f"- [{f.get('kind')}] "
                         f"scope={f.get('scope') or '-'} "
                         f"{f.get('message')}")
        if protocol_out.get("verdict"):
            lines.append(f"- **{protocol_out['verdict']}**")
        lines.append("")

    dec = report.get("decisions")
    if dec:
        lines += ["## Control decisions", "",
                  f"{dec['count']} decision(s) "
                  f"({dec['source']}; {dec['fallbacks']} static "
                  "fallback(s)): "
                  + ", ".join(f"{c}×{n}" for c, n in
                              dec["by_consumer"].items()) + ".", "",
                  "| age (s) | rank | consumer | op | choice | why |",
                  "|---|---|---|---|---|---|"]
        for d in dec["recent"]:
            lines.append(
                f"| {d['age_s']} | {d['rank']} | {d['consumer']} "
                f"| {d['op']} | {d['choice']} | {d['why'] or '-'} |")
        lines.append("")

    cluster = report.get("cluster")
    if cluster:
        lines += ["## Cluster", "",
                  f"Router mode `{cluster.get('mode')}`; "
                  f"{len(cluster.get('replicas', []))} replica(s), "
                  f"{len(cluster.get('failovers', []))} failover(s)"
                  + (f", {cluster['kv_shipped_bytes']} KV bytes "
                     f"shipped over {cluster['shipments']} "
                     "shipment(s)"
                     if cluster.get("shipments") else "") + ".", "",
                  "| replica | state | reason | beat age (s) "
                  "| routed | queued |", "|---|---|---|---|---|---|"]
        for r in cluster.get("replicas", []):
            state = ("QUARANTINED" if r.get("quarantined")
                     else ("DEAD" if not r.get("alive") else "ok"))
            lines.append(
                f"| {r.get('name')} | {state} "
                f"| {r.get('fail_reason') or '-'} "
                f"| {r.get('hb_age_s') if r.get('hb_age_s') is not None else '-'} "
                f"| {r.get('routed')} | {r.get('queue_depth')} |")
        lines.append("")
        for f in cluster.get("failovers", []):
            lines.append(f"- {f.get('replica')}: {f.get('reason')} "
                         f"at t={f.get('ts')} — {f.get('requeued')} "
                         "in-flight request(s) drained and re-queued")
        for r in cluster.get("readmits", []):
            lines.append(f"- {r.get('replica')}: re-admitted at "
                         f"t={r.get('ts')} after recovery probation "
                         f"(was {r.get('was')})")
        if cluster.get("failovers") or cluster.get("readmits"):
            lines.append("")

    chaos = report.get("chaos")
    if chaos:
        lines += ["## Chaos", "",
                  f"{chaos['count']} fault(s) injected by seeded "
                  "schedule"
                  + (f" (seed(s) {', '.join(str(s) for s in chaos['seeds'])})"
                     if chaos.get("seeds") else "")
                  + ": "
                  + ", ".join(f"{c}×{n}" for c, n in
                              chaos["by_class"].items()) + ".", "",
                  "| age (s) | fault | target | inputs |",
                  "|---|---|---|---|"]
        for d in chaos["recent"]:
            inp = ", ".join(f"{k}={v}" for k, v in
                            sorted(d["inputs"].items())) or "-"
            lines.append(f"| {d['age_s']} | {d['fault']} "
                         f"| {d['target']} | {inp} |")
        lines.append("")

    lineage = report.get("lineage")
    if lineage:
        lines += ["## Request lineage", "",
                  f"{lineage['requests']} request(s), "
                  f"{lineage['completed']} with a first token "
                  f"({lineage['events']} hop event(s)); TTFT hop "
                  "decomposition "
                  + ("sums exactly to the measured TTFT on every "
                     "request." if lineage["exact"] else
                     "is INCOMPLETE on some request (lineage head "
                     "truncated — torn artifact?): its TTFT is "
                     "under-reported."), "",
                  "| request | TTFT (ms) | dominant hop | (ms) "
                  "| retries | faults absorbed |",
                  "|---|---|---|---|---|---|"]
        for s in lineage["slowest"]:
            lines.append(
                f"| {s['request_id']} | {s['ttft_ms']} "
                f"| {s['dominant_hop']} | {s['dominant_ms']} "
                f"| {s.get('ship_retries', '-')} "
                f"| {', '.join(s['faults_absorbed']) if s.get('faults_absorbed') else '-'} |")
        lines.append("")
        if lineage.get("in_flight"):
            lines += ["In flight (stuck-in hop):", ""]
            lines += [f"- request {f['request_id']}: "
                      f"'{f['stuck_in']}' for {f['age_s']}s"
                      for f in lineage["in_flight"]]
            lines.append("")

    slo = report.get("slo")
    if slo:
        burn_note = (f"{len(slo['burning'])} class(es) burning: "
                     f"{', '.join(slo['burning'])}."
                     if slo.get("burning")
                     else "No class is burning its budget.")
        lines += ["## SLO", "",
                  f"{slo.get('alerts_fired', 0)} burn alert(s) "
                  f"fired (threshold "
                  f"{slo.get('burn_alert_threshold')}x). {burn_note}",
                  "",
                  "| class | requests | compliance | objective "
                  "| budget left | burn |",
                  "|---|---|---|---|---|---|"]
        for c in slo["classes"]:
            comp = c.get("compliance")
            budget = c.get("budget_remaining")
            burn = c.get("burn") or {}
            burn_s = ", ".join(
                f"{w}={burn[w]:.2f}x" for w in sorted(burn)
                if isinstance(burn[w], (int, float))) or "-"
            def pct(x):
                return "-" if x is None else format(x, ".1%")
            lines.append(
                f"| {c['class']} | {c['requests']} "
                f"| {pct(comp)} | {pct(c.get('objective'))} "
                f"| {pct(budget)} | {burn_s} |")
        lines.append("")
        if slo.get("dominant_tenant"):
            lines += [f"Breaches dominated by tenant "
                      f"'{slo['dominant_tenant']}'.", ""]
        costs = slo.get("tenant_costs")
        if isinstance(costs, dict) and costs:
            lines += ["Tenant bill (cost join):", "",
                      "| tenant | device µs | KV page-s | wire bytes "
                      "| wasted spec | re-prefill |",
                      "|---|---|---|---|---|---|"]
            for t in sorted(costs):
                v = costs[t]
                lines.append(
                    f"| {t} | {v.get('device_us')} "
                    f"| {v.get('kv_page_seconds')} "
                    f"| {v.get('wire_bytes')} "
                    f"| {v.get('wasted_spec_tokens')} "
                    f"| {v.get('reprefill_tokens')} |")
            lines.append("")

    tser = report.get("timeseries")
    if tser:
        lines += ["## Time series", "",
                  f"{tser['samples']} retained sample(s) spanning "
                  f"{tser['span_s']}s before the incident."]
        if tser.get("trends"):
            lines += ["", "| metric | trend | samples | delta "
                      "| last |", "|---|---|---|---|---|"]
            lines += [f"| {t['metric']} | {t['direction']} "
                      f"| {t['run']} | {t['delta']} | {t['last']} |"
                      for t in tser["trends"]]
        lines.append("")

    rpl = report.get("replay")
    if rpl:
        lines += ["## Replay", "",
                  f"Recording {rpl['status']}: {rpl['rows']} row(s) "
                  f"— {rpl['clock_readings']} clock reading(s), "
                  f"{rpl['requests']} request(s), "
                  f"{rpl['wire_events']} wire event(s), "
                  f"{rpl['faults']} fault injection(s)."]
        if rpl.get("problems"):
            lines += [f"- {p}" for p in rpl["problems"]]
        for c in rpl.get("counterfactuals", []):
            lines.append(f"- counterfactually, {c['clause']}")
        lines.append("")

    fleet = report.get("fleet")
    if fleet:
        firing = fleet.get("firing") or []
        head = (f"{len(firing)} alert(s) firing at end of run"
                if firing else "No alert firing at end of run")
        lines += ["## Fleet alerts", "",
                  f"{fleet['frames']} telemetry frame(s) from "
                  f"{len(fleet.get('sources', []))} source(s); "
                  f"{fleet['alerts']} alert transition(s)"
                  + (" — "
                     + ", ".join(f"{r}×{n}" for r, n in
                                 fleet["alerts_by_rule"].items())
                     if fleet.get("alerts_by_rule") else "")
                  + f". {head}.", ""]
        for e in firing:
            inp = ", ".join(f"{k}={v}" for k, v in
                            sorted(e.get("inputs", {}).items()))
            lines.append(f"- [{e.get('severity')}] {e.get('rule')} "
                         f"on {e.get('target')}"
                         + (f" ({inp})" if inp else ""))
        if firing:
            lines.append("")
        if fleet.get("table"):
            lines += ["| source | role | seq | queue | slots "
                      "| kv occ | burn | state |",
                      "|---|---|---|---|---|---|---|---|"]
            for row in fleet["table"]:
                state = ("DEAD" if row.get("alive") is False
                         else "QUARANTINED" if row.get("quarantined")
                         else "ok")
                def v(key):
                    x = row.get(key)
                    return "-" if x is None else x
                lines.append(
                    f"| {row.get('source')} | {row.get('role')} "
                    f"| {v('seq')} | {v('queue_depth')} "
                    f"| {v('active_slots')} "
                    f"| {v('kv_page_occupancy')} | {v('burn_max')} "
                    f"| {state} |")
            lines.append("")

    hot = report["links"].get("hot") or []
    if hot:
        lines += ["## Hot ICI links", "",
                  "| link | bytes | ops |", "|---|---|---|"]
        lines += [f"| {h['link']} | {h['bytes']} "
                  f"| {', '.join(h['ops'])} |" for h in hot]
        lines.append("")
    contention = report["links"].get("contention") or []
    if contention:
        lines += ["## Link contention", ""]
        lines += [f"- {' vs '.join(c['ops'])} shared "
                  f"{', '.join(c['links'])} for {c['overlap_s']}s"
                  for c in contention]
        lines.append("")

    if report.get("stragglers"):
        lines += ["## Consistent stragglers", ""]
        for s in report["stragglers"]:
            blame = []
            if s.get("blamed_link"):
                blame.append(f"link {s['blamed_link']}")
            if s.get("blamed_sem"):
                blame.append(f"sem {s['blamed_sem']!r}")
            lines.append(
                f"- rank {s['rank']}: charged peers "
                f"{s['barrier_wait_charged_us']:.0f}us over "
                f"{', '.join(s['spans'])}"
                + (f" — blamed {', '.join(blame)}" if blame else ""))
        lines.append("")
    if report.get("anomalies"):
        lines += ["## Anomalies", ""]
        lines += [f"- {a['name']}#{a['occurrence']} rank {a['rank']}: "
                  f"{a['dur_us']:.0f}us (z={a['z']:+.1f}, "
                  f"{a['source']})" for a in report["anomalies"]]
        lines.append("")
    if report.get("incompleteness"):
        lines += ["## Incomplete data", ""]
        lines += [f"- {note}" for note in report["incompleteness"]]
        lines.append("")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Golden comparison (CI)
# ---------------------------------------------------------------------------

def compare_reports(report: dict, golden: dict) -> List[str]:
    """Structural diff (path-labelled) between a fresh report and a
    golden one; empty = no drift."""
    diffs: List[str] = []

    def walk(a, b, path):
        if type(a) is not type(b):
            diffs.append(f"{path}: type {type(a).__name__} != "
                         f"{type(b).__name__}")
        elif isinstance(a, dict):
            for k in sorted(set(a) | set(b)):
                if k not in a:
                    diffs.append(f"{path}.{k}: missing in fresh")
                elif k not in b:
                    diffs.append(f"{path}.{k}: missing in golden")
                else:
                    walk(a[k], b[k], f"{path}.{k}")
        elif isinstance(a, list):
            if len(a) != len(b):
                diffs.append(f"{path}: length {len(a)} != {len(b)}")
            for i, (x, y) in enumerate(zip(a, b)):
                walk(x, y, f"{path}[{i}]")
        elif a != b:
            diffs.append(f"{path}: {a!r} != {b!r}")

    walk(report, golden, "report")
    return diffs


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _parse_mesh(text):
    axes = {}
    for part in text.split(","):
        axis, _, size = part.partition("=")
        if not size:
            raise argparse.ArgumentTypeError(
                f"mesh spec {text!r} must look like tp=4 or x=2,y=2")
        axes[axis] = int(size)
    return axes


def _replay_mode(dirs: Sequence[str]) -> Optional[int]:
    """``--replay``: live re-execution of the first directory's
    recording.  Asserts bit-exact parity; when the recording carries
    injected faults, additionally re-executes with the first fault
    suppressed and APPENDS the counterfactual verdict to the
    artifact — the subsequent `diagnose` pass (and every later one
    over the same directory) then quotes the causality clause.

    Returns an exit code to stop with (4 = the replay itself
    diverged, so no counterfactual is trustworthy), or None to
    continue into the normal report."""
    from triton_distributed_tpu.observability.replay import (
        REPLAY_FILE, append_counterfactual, load_replay, replay_run)
    target = next((d for d in dirs
                   if os.path.exists(os.path.join(d, REPLAY_FILE))),
                  None)
    if target is None:
        print(f"doctor: --replay found no {REPLAY_FILE} under "
              f"{list(dirs)}", file=sys.stderr)
        return 2
    base = replay_run(target)
    print(f"doctor: replay of {target} is {base['status']} "
          f"({base['levels']})", file=sys.stderr)
    if base["status"] == "INCOMPLETE":
        return None          # diagnose reports the torn artifact
    if base["status"] != "EXACT":
        print("doctor: recorded run did not replay exactly — "
              "counterfactuals would not be attributable "
              f"(first divergence: {base['first_divergence']})",
              file=sys.stderr)
        return 4
    faults = [r for r in load_replay(target)
              if r.get("kind") == "fault_injected"]
    if not faults:
        return None
    idx = int(faults[0].get("index", 0))
    cf_run = replay_run(target, override={"suppress_fault": idx})
    append_counterfactual(target, cf_run["counterfactual"])
    return None


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m triton_distributed_tpu.observability.doctor",
        description="Turn a failed run's artifact directory into one "
                    "incident report (markdown + JSON).")
    ap.add_argument("dirs", nargs="+",
                    help="artifact directories (traces, flight dumps, "
                         "heartbeats, metrics, analysis findings)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the JSON report here (- for stdout); "
                         "default <dir>/incident_report.json")
    ap.add_argument("--md", default=None, metavar="PATH",
                    help="write the markdown report here (- for "
                         "stdout); default <dir>/incident_report.md")
    ap.add_argument("--kernel", default=None,
                    help="override the analysis-registry kernel to "
                         "statically check")
    ap.add_argument("--mesh", type=_parse_mesh, default=None,
                    help="override the static-check mesh (tp=4)")
    ap.add_argument("--now", type=float, default=None,
                    help="override the report clock (default: newest "
                         "artifact timestamp, for determinism)")
    ap.add_argument("--no-static", action="store_true",
                    help="skip the static comm-graph consult")
    ap.add_argument("--resources", action="store_true",
                    help="also consult the static resource sanitizer "
                         "(VMEM/tiling/bounds) for the in-flight "
                         "kernel; a shipped resource-findings.json "
                         "enables this automatically")
    ap.add_argument("--protocol", action="store_true",
                    help="also consult the cluster protocol model "
                         "checker (wire/routing/failover "
                         "interleavings); a shipped "
                         "protocol-findings.json enables this "
                         "automatically")
    ap.add_argument("--check", default=None, metavar="GOLDEN",
                    help="compare against a golden report JSON; exit "
                         "3 on drift (CI gate)")
    ap.add_argument("--replay", action="store_true",
                    help="re-execute the recorded run from "
                         "replay.jsonl before diagnosing: assert "
                         "bit-exact parity, then (when faults were "
                         "recorded) counterfactually suppress the "
                         "first one and append the causality verdict "
                         "to the artifact, so the report's verdict "
                         "names who to blame")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="suppress the markdown on stdout")
    args = ap.parse_args(argv)

    if args.replay:
        rc = _replay_mode(args.dirs)
        if rc is not None:
            return rc

    report = diagnose(args.dirs, kernel=args.kernel, mesh=args.mesh,
                      now=args.now, static=not args.no_static,
                      resources=args.resources,
                      protocol=args.protocol)
    if report is None:
        print(f"doctor: no artifacts found under {args.dirs}",
              file=sys.stderr)
        return 2

    md = render_markdown(report)
    json_path = args.json or os.path.join(args.dirs[0], REPORT_JSON)
    md_path = args.md or os.path.join(args.dirs[0], REPORT_MD)
    if json_path == "-":
        print(json.dumps(report, indent=1))
    else:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=1)
            f.write("\n")
    if md_path == "-":
        print(md)
    else:
        with open(md_path, "w") as f:
            f.write(md + "\n")
        if not args.quiet:
            print(md)

    if args.check:
        golden = _load_json(args.check)
        if golden is None:
            print(f"doctor: cannot read golden {args.check}",
                  file=sys.stderr)
            return 2
        diffs = compare_reports(report, golden)
        if diffs:
            print(f"doctor: report drifted from golden {args.check}:",
                  file=sys.stderr)
            for d in diffs[:20]:
                print(f"  {d}", file=sys.stderr)
            return 3
        print(f"doctor: report matches golden {args.check}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
