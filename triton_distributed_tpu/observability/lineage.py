"""Request lineage: per-hop tracing of every serving request, and the
critical-path analyzer that turns a blown TTFT into a named hop.

PRs 1-10 instrumented kernels, links, replicas and control decisions —
but the unit a user experiences, the *request*, recorded only its
endpoints (`t_first_token`, `t_finish`).  A TTFT blown under the chaos
grid could not be attributed to queue wait vs routing vs prefill vs
shipment-retry backoff vs decode admission.  This module closes that:

- :class:`LineageEvent` (schema v1): one record per **hop** a request
  crosses — cluster submit, route stage/commit, prefill-worker
  start/end, transport ship/retry/NACK/deliver, decode admission
  (local / shipped / suffix-only / in chunks), preempt, failover,
  first token, retire/reject (:data:`HOPS`).  Events carry the request id
  (`ClusterRequest.record_id` in a cluster, so they JOIN the router's
  DecisionEvents — ``op == "request:<id>"`` — and the chaos harness's
  FaultEvents — shipment ids ride in ``detail``), the emitting actor,
  and the scheduler-clock timestamp (virtual-clock runs are therefore
  bit-deterministic).
- :class:`LineageRecorder`: the process-global sink.  Every hop lands
  in a bounded per-request ring, the flight-recorder ring (a hung
  rank's dump shows which hop each in-flight request was stuck in),
  the ``cluster_hop_ms{hop=...}`` histograms (the interval from hop X
  to the next hop is charged to X), and — when armed via
  ``TDT_LINEAGE_DIR`` / :func:`set_lineage_log` — a per-rank
  ``lineage-rank-<N>.jsonl``.  `ServingCluster.write_artifact` also
  drops a ``lineage.jsonl`` beside ``router-state.json`` /
  ``faults.jsonl`` for the doctor.
- :func:`ttft_breakdown`: the deterministic critical-path analyzer.
  TTFT decomposes into the intervals between consecutive hops, summed
  per hop in EXACT rational arithmetic (`fractions.Fraction`), so the
  decomposition sums *exactly* — not approximately — to the measured
  ``t_first_token - t_arrival`` on the same clock; ``exact`` is an
  asserted invariant, not an estimate.  The interval after hop X is
  charged to X ("what the request was doing since X"), so the
  dominant hop names the bottleneck: ``enqueue`` = engine queue wait,
  ``ship``/``ship_retry`` = wire time + retry backoff, ``admit`` =
  prefill-to-first-decode, and so on.
- :func:`attribute_tbt`: TBT-tail attribution — inter-token gaps that
  spike past the median are attributed to the lineage interval they
  overlap (``preempt`` / ``failover`` / ``ship_retry``), or to
  ``step_time`` when no lifecycle event explains them.

Opt-out: ``TDT_OBSERVABILITY=0`` turns :func:`record_hop` into an
immediate no-op — no event objects, no histogram updates, nothing in
the ring — so the disabled serving hot path is bit-identical to the
pre-lineage tree (call sites additionally sit behind the scheduler's
existing ``if reg:`` registry guard, which is None exactly when
observability is off).

See docs/observability.md "Request lineage" for the event schema
table, the hop diagram and a worked why-was-it-slow walkthrough.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import threading
from fractions import Fraction
from typing import Any, Dict, List, Optional, Sequence, Tuple

from triton_distributed_tpu.observability.metrics import (
    observability_enabled,
)

LINEAGE_SCHEMA = 1
LINEAGE_FILE = "lineage.jsonl"

#: Directory for the per-rank streaming ``lineage-rank-<N>.jsonl``.
ENV_LINEAGE_DIR = "TDT_LINEAGE_DIR"

#: Every hop a request can cross, in rough lifecycle order.  The
#: validator rejects anything else — the vocabulary IS the schema.
HOPS = (
    "submit",        # cluster front door accepted the record
    "enqueue",       # a scheduler's bounded queue accepted an attempt
    "route_stage",   # router staged a placement (commit-on-accept)
    "route_commit",  # the placement's dispatch actually landed
    "prefill_start",  # dedicated prefill worker began the prompt
    "prefill_end",   # worker finished; KV ready to ship
    "ship",          # shipment put on the wire (first send)
    "ship_retry",    # retransmission (timeout / corrupt NACK)
    "ship_nack",     # delivery failed its checksum (receiver NACK)
    "ship_deliver",  # shipment claimed intact at the decode replica
    "reroute",       # bounded retry exhausted; back to the router
    "admit",         # decode admission (detail.mode: local |
                     #   shipped | suffix; detail.resumed on resume)
    "spec_verify",   # speculative verify dispatch (detail.proposed /
                     #   detail.accepted) — names draft/verify cost in
                     #   ttft_breakdown / TBT attribution
    "preempt",       # page pool dry: evicted mid-stream (resumes)
    "failover",      # replica drained; record re-queued with resume
    "first_token",   # the TTFT endpoint
    "retire",        # finished (detail.reason)
    "reject",        # rejected (detail.reason)
)

#: Hops that end a request's lineage (anything after them means the
#: record moved on — e.g. an attempt-level ``retire[stopped]`` during
#: a failover drain, followed by the record's ``failover`` hop).
TERMINAL_HOPS = ("retire", "reject")

#: Hops that explain a TBT spike when they land inside the gap.
_STALL_HOPS = ("preempt", "failover", "ship_retry", "reroute",
               "ship_nack")

#: Second-tier explanation: a verify round inside the gap (spec mode
#: records one per dispatch, so it only names a spike no lifecycle
#: stall explains — "the draft/verify dispatch itself was the cost").
_SPEC_HOPS = ("spec_verify",)

#: Fields every lineage.jsonl line must carry (doctor/CI validation).
LINEAGE_FIELDS = ("schema", "kind", "ts", "rank", "request_id", "hop",
                  "actor", "detail")


@dataclasses.dataclass
class LineageEvent:
    """One hop crossing (schema v1).  ``request_id`` is the join key:
    the `ClusterRequest.record_id` for cluster traffic (DecisionEvents
    use ``op="request:<record_id>"``), an ``"eng-<n>"`` string for a
    standalone scheduler's requests."""

    request_id: Any
    hop: str
    ts: float
    actor: str = ""
    detail: Dict[str, Any] = dataclasses.field(default_factory=dict)
    rank: int = 0
    schema: int = LINEAGE_SCHEMA
    kind: str = "lineage"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "LineageEvent":
        kw = {f.name: d[f.name] for f in dataclasses.fields(cls)
              if f.name in d}
        return cls(**kw)


def validate_lineage(d: dict) -> List[str]:
    """Schema-v1 check for one lineage.jsonl line; empty = valid."""
    problems = []
    for f in LINEAGE_FIELDS:
        if f not in d:
            problems.append(f"missing field {f!r}")
    if d.get("schema") != LINEAGE_SCHEMA:
        problems.append(f"schema {d.get('schema')!r} != "
                        f"{LINEAGE_SCHEMA}")
    if d.get("kind") != "lineage":
        problems.append(f"kind {d.get('kind')!r} != 'lineage'")
    if d.get("hop") not in HOPS:
        problems.append(f"unknown hop {d.get('hop')!r}")
    if not isinstance(d.get("detail"), dict):
        problems.append("detail not a dict")
    return problems


def load_lineage(paths) -> List[dict]:
    """Parse lineage lines from jsonl file(s), skipping torn lines (a
    rank killed mid-write must not break the doctor).  Rows sort by
    (ts, stable input order)."""
    from triton_distributed_tpu.observability.jsonl import (
        load_jsonl_rows, tolerant_ts)
    return load_jsonl_rows(paths, kind="lineage",
                           sort_key=tolerant_ts)


# ---------------------------------------------------------------------------
# Recorder
# ---------------------------------------------------------------------------

class LineageRecorder:
    """Bounded per-request event store (process-global singleton via
    :func:`get_lineage_recorder`).

    ``record`` appends under a lock, charges the just-closed interval
    to the previous hop's ``cluster_hop_ms`` histogram, mirrors the
    event into the flight-recorder ring, and streams it to the armed
    jsonl log.  Eviction is oldest-request-first past
    ``max_requests``; a single request is capped at ``max_events``
    hops (overflow counted, never silent)."""

    def __init__(self, max_requests: int = 4096,
                 max_events: int = 512):
        self._lock = threading.RLock()
        self.max_requests = int(max_requests)
        self.max_events = int(max_events)
        #: request_id -> [LineageEvent] in append order (insertion
        #: order of the dict is request recency for eviction).
        self._by_req: "collections.OrderedDict[Any, List[LineageEvent]]" \
            = collections.OrderedDict()
        self.dropped_events = 0
        self.evicted_requests = 0

    def __len__(self) -> int:
        with self._lock:
            return sum(len(v) for v in self._by_req.values())

    def clear(self) -> None:
        with self._lock:
            self._by_req.clear()
            self.dropped_events = 0
            self.evicted_requests = 0

    def record(self, event: LineageEvent) -> LineageEvent:
        from triton_distributed_tpu.observability.metrics import (
            _process_index, count_metric, observe_metric)
        event.rank = _process_index()
        with self._lock:
            evs = self._by_req.get(event.request_id)
            if evs is None:
                while len(self._by_req) >= self.max_requests:
                    self._by_req.popitem(last=False)
                    self.evicted_requests += 1
                evs = self._by_req[event.request_id] = []
            if len(evs) >= self.max_events:
                self.dropped_events += 1
                count_metric("lineage_events_dropped_total")
                return event
            if evs:
                # The interval since the previous hop belongs to that
                # hop — the same charging rule ttft_breakdown uses, so
                # the histograms and the analyzer agree.  Observed
                # only for RETAINED events: a request past its event
                # cap must not keep re-charging overlapping intervals
                # from the same retained tail.
                observe_metric("cluster_hop_ms",
                               max(event.ts - evs[-1].ts, 0.0) * 1e3,
                               hop=evs[-1].hop)
            evs.append(event)
        # The flight ring: a hung rank's dump then carries the last
        # hops next to its last kernel events and control decisions.
        from triton_distributed_tpu.observability.recorder import (
            get_flight_recorder)
        get_flight_recorder().record(event)
        _append_log(event)
        return event

    # -- views -----------------------------------------------------------

    def events_for(self, request_id) -> List[LineageEvent]:
        with self._lock:
            return list(self._by_req.get(request_id, ()))

    def request_ids(self) -> List:
        with self._lock:
            return list(self._by_req)

    def all_events(self) -> List[LineageEvent]:
        """Every retained event, grouped by request in insertion
        order (what :func:`write_lineage_artifact` serialises)."""
        with self._lock:
            return [e for evs in self._by_req.values() for e in evs]

    def in_flight_summaries(self, n: int = 5) -> List[dict]:
        """The newest ``n`` requests with no terminal hop yet — each
        with the hop it is currently stuck in.  This is what
        heartbeats and flight dumps carry."""
        out: List[dict] = []
        with self._lock:
            for rid in reversed(self._by_req):
                evs = self._by_req[rid]
                if not evs or evs[-1].hop in TERMINAL_HOPS:
                    continue
                last = evs[-1]
                out.append({"request_id": rid, "hop": last.hop,
                            "ts": round(last.ts, 6),
                            "hops": len(evs)})
                if len(out) >= n:
                    break
        return out

    def request_table(self, n: int = 50) -> List[dict]:
        """Last ``n`` requests (any state) with their lifecycle
        summary — the ``/requests`` endpoint body."""
        rows: List[dict] = []
        with self._lock:
            items = list(self._by_req.items())[-n:]
        for rid, evs in items:
            if not evs:
                continue
            last = evs[-1]
            row = {
                "request_id": rid,
                "state": ("done" if last.hop in TERMINAL_HOPS
                          else "in_flight"),
                "last_hop": last.hop,
                "ts": round(last.ts, 6),
                "hops": len(evs),
            }
            bd = ttft_breakdown(evs)
            if bd is not None:
                row["ttft_ms"] = bd["ttft_ms"]
                row["dominant_hop"] = bd["dominant_hop"]
            # Cost join (observability.costs): what this request
            # BILLED, next to where its time WENT.  Absent-key: only
            # requests that were ever charged (accounting armed)
            # carry the key, so untenanted tables are byte-identical.
            from triton_distributed_tpu.observability.costs import (
                cost_summary)
            cost = cost_summary(rid)
            if cost is not None:
                row["cost"] = cost
            rows.append(row)
        return rows


_RECORDER: Optional[LineageRecorder] = None
_RECORDER_LOCK = threading.Lock()


def get_lineage_recorder() -> LineageRecorder:
    global _RECORDER
    with _RECORDER_LOCK:
        if _RECORDER is None:
            _RECORDER = LineageRecorder()
        return _RECORDER


def record_hop(request_id, hop: str, ts: float, actor: str = "",
               **detail) -> Optional[LineageEvent]:
    """Record one hop crossing; no-op (None) when observability is
    off.  Hot call sites sit behind the scheduler's existing registry
    guard so the disabled path does not even reach here."""
    if not observability_enabled():
        return None
    assert hop in HOPS, hop
    return get_lineage_recorder().record(LineageEvent(
        request_id=request_id, hop=hop, ts=float(ts), actor=actor,
        detail=detail))


def lineage_summaries(n: int = 5) -> List[dict]:
    """In-flight request summaries for heartbeats/dumps ([] when
    observability is off or nothing is in flight)."""
    if not observability_enabled():
        return []
    return get_lineage_recorder().in_flight_summaries(n)


# ---------------------------------------------------------------------------
# jsonl artifact
# ---------------------------------------------------------------------------

_LOG_PATH: Optional[str] = None
_LOG_EXPLICIT = False
_LOG_LOCK = threading.Lock()


def set_lineage_log(path: Optional[str]) -> None:
    """Point the streaming lineage writer at ``path`` (None disarms
    and re-enables the ``TDT_LINEAGE_DIR`` default)."""
    global _LOG_PATH, _LOG_EXPLICIT
    with _LOG_LOCK:
        _LOG_PATH = path
        _LOG_EXPLICIT = path is not None


def lineage_log_path() -> Optional[str]:
    with _LOG_LOCK:
        if _LOG_EXPLICIT:
            return _LOG_PATH
    directory = os.environ.get(ENV_LINEAGE_DIR)
    if not directory:
        return None
    from triton_distributed_tpu.observability.metrics import (
        _process_index)
    return os.path.join(directory,
                        f"lineage-rank-{_process_index()}.jsonl")


def _append_log(event: LineageEvent) -> None:
    path = lineage_log_path()
    if not path:
        return
    try:
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with _LOG_LOCK:
            with open(path, "a") as f:
                f.write(json.dumps(event.to_dict(), default=str)
                        + "\n")
    except OSError:
        pass   # the artifact is forensics; it must never break serving


def write_lineage_artifact(directory: str,
                           request_ids: Optional[Sequence] = None
                           ) -> Optional[str]:
    """Write ``lineage.jsonl`` from the retained events — the
    artifact `ServingCluster.write_artifact` drops beside
    ``router-state.json`` and the doctor's "Request lineage" section
    replays.  ``request_ids`` filters to one cluster's own records
    (the recorder is process-global and may also hold a reference
    scheduler's lineage).  None when there is nothing to write."""
    rec = get_lineage_recorder()
    events = rec.all_events()
    if request_ids is not None:
        wanted = set(request_ids)
        events = [e for e in events if e.request_id in wanted]
    if not events:
        return None
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, LINEAGE_FILE)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        for e in events:
            f.write(json.dumps(e.to_dict(), default=str) + "\n")
        # Cost join: one ``kind="cost"`` row per charged request at
        # the tail (same file, same filter discipline —
        # `load_lineage` keeps only ``kind="lineage"`` so existing
        # readers never see these; `load_lineage_costs` reads them
        # back).  Absent-key: untenanted runs write no cost rows and
        # the artifact is byte-identical to the pre-cost tree.
        from triton_distributed_tpu.observability.costs import (
            cost_summary)
        for rid in sorted({e.request_id for e in events},
                          key=lambda r: str(r)):
            cost = cost_summary(rid)
            if cost is not None:
                f.write(json.dumps(
                    {"kind": "cost", "request_id": rid, **cost},
                    default=str) + "\n")
    os.replace(tmp, path)
    return path


def load_lineage_costs(paths) -> List[dict]:
    """The ``kind="cost"`` join rows `write_lineage_artifact` appends
    (empty for pre-cost artifacts), torn-line tolerant like
    `load_lineage`."""
    from triton_distributed_tpu.observability.jsonl import (
        load_jsonl_rows)
    return load_jsonl_rows(paths, kind="cost")


# ---------------------------------------------------------------------------
# Critical-path analysis
# ---------------------------------------------------------------------------

def _ts_of(e) -> float:
    """Tolerant timestamp: a hand-edited or torn artifact row must
    degrade (sort to 0) rather than crash the doctor (the same
    hardening faults.jsonl ingest got in PR 10)."""
    if isinstance(e, LineageEvent):
        return float(e.ts)
    try:
        return float(e.get("ts", 0.0))
    except (TypeError, ValueError):
        return 0.0


def _hop_of(e) -> str:
    return str(e.hop if isinstance(e, LineageEvent)
               else e.get("hop"))


def ttft_breakdown(events, arrival: Optional[float] = None,
                   measured_ttft: Optional[float] = None
                   ) -> Optional[dict]:
    """Decompose one request's TTFT into per-hop intervals.

    ``events``: the request's :class:`LineageEvent`\\ s or their
    dicts, in any order (sorted stably by ``ts`` here).  Returns None
    when no ``first_token`` hop exists yet.

    The interval between consecutive hops is charged to the EARLIER
    hop and summed per hop in exact rational arithmetic
    (`fractions.Fraction`), so the per-hop sums telescope to
    ``t_first_token - t0`` with no float drift: ``exact`` asserts
    ``float(Σ hops) == (t_first_token - t0)`` (IEEE subtraction and
    Fraction→float conversion both round the same exact value), and —
    when the caller supplies them — that ``t0`` equals the request's
    ``arrival`` and the total equals its ``measured_ttft``.  This is
    the invariant `tests/test_lineage.py` enforces on
    every request."""
    evs = sorted(events, key=_ts_of)
    if not evs:
        return None
    t_ft = None
    for e in evs:
        if _hop_of(e) == "first_token":
            t_ft = _ts_of(e)
            break
    if t_ft is None:
        return None
    t0 = _ts_of(evs[0])
    by_hop: Dict[str, Fraction] = {}
    segments: List[dict] = []
    prev_ts, prev_hop = t0, _hop_of(evs[0])
    for e in evs[1:]:
        ts, hop = _ts_of(e), _hop_of(e)
        if prev_ts >= t_ft:
            break
        dur = Fraction(min(ts, t_ft)) - Fraction(prev_ts)
        by_hop[prev_hop] = by_hop.get(prev_hop, Fraction(0)) + dur
        if dur:
            segments.append({"hop": prev_hop,
                             "start": round(prev_ts, 9),
                             "dur_ms": round(float(dur) * 1e3, 6)})
        prev_ts, prev_hop = ts, hop
        if hop == "first_token":
            break
    total = sum(by_hop.values(), Fraction(0))
    ttft_s = t_ft - t0
    exact = (float(total) == ttft_s
             and (arrival is None or t0 == float(arrival))
             and (measured_ttft is None
                  or ttft_s == float(measured_ttft)))
    if by_hop:
        dominant = max(by_hop.items(),
                       key=lambda kv: (kv[1], kv[0]))[0]
        dominant_ms = float(by_hop[dominant]) * 1e3
    else:
        dominant, dominant_ms = None, 0.0
    return {
        "t0": t0,
        "t_first_token": t_ft,
        "ttft_s": ttft_s,
        "ttft_ms": round(ttft_s * 1e3, 6),
        "by_hop_ms": {h: round(float(f) * 1e3, 6)
                      for h, f in sorted(by_hop.items())},
        "segments": segments,
        "dominant_hop": dominant,
        "dominant_ms": round(dominant_ms, 6),
        "exact": exact,
    }


def attribute_tbt(events, token_times: Sequence[float],
                  spike_ratio: float = 3.0) -> dict:
    """Attribute TBT-tail spikes to lifecycle stalls.

    ``token_times``: the request's per-token timestamps (the caller
    captures them from its ``on_token`` stream on the same clock the
    lineage rides).  A gap larger than ``spike_ratio`` × the median
    gap is a spike; it is attributed to the stall hop (preempt /
    failover / ship_retry / reroute / ship_nack) whose event lands
    inside it, else — speculative mode — to a ``spec_verify`` round
    inside it (the draft/verify dispatch itself was the cost; verify
    hops are second-tier because every spec dispatch records one),
    else to ``step_time`` (the decode step itself got slow).
    Deterministic given the inputs."""
    gaps: List[Tuple[int, float, float, float]] = []
    for i in range(1, len(token_times)):
        a, b = float(token_times[i - 1]), float(token_times[i])
        gaps.append((i, b - a, a, b))
    if not gaps:
        return {"gaps": 0, "median_gap_s": 0.0, "spikes": []}
    durs = sorted(g[1] for g in gaps)
    median = durs[(len(durs) - 1) // 2]
    stalls = [(_ts_of(e), _hop_of(e)) for e in events
              if _hop_of(e) in _STALL_HOPS]
    verifies = [(_ts_of(e), _hop_of(e)) for e in events
                if _hop_of(e) in _SPEC_HOPS]
    spikes = []
    for i, dur, a, b in gaps:
        if median > 0 and dur <= spike_ratio * median:
            continue
        if median == 0 and dur == 0:
            continue
        cause = "step_time"
        for ts, hop in stalls:
            if a < ts <= b:
                cause = hop
                break
        else:
            for ts, hop in verifies:
                if a < ts <= b:
                    cause = hop
                    break
        spikes.append({"token": i, "gap_ms": round(dur * 1e3, 6),
                       "cause": cause})
    return {"gaps": len(gaps),
            "median_gap_s": round(median, 9),
            "spikes": spikes}


def group_by_request(rows: Sequence[dict]) -> Dict[Any, List[dict]]:
    """{request_id: [rows sorted by (tolerant) ts]} from loaded
    jsonl rows."""
    out: Dict[Any, List[dict]] = {}
    for d in rows:
        out.setdefault(d.get("request_id"), []).append(d)
    for evs in out.values():
        evs.sort(key=_ts_of)
    return out
