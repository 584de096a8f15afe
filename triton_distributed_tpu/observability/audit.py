"""Perf-model audit: hold measured kernel times against the analytic
estimates (`kernels/comm_perf_model.py`, `kernels/gemm_perf_model.py`)
and flag deviations — the perf models as a standing regression
detector.

The models carry published-peak tables with a fixed efficiency derate,
so they are trustworthy to a *factor*, not a percent: the default
threshold flags measurements slower than ``threshold ×`` the estimate
(a kernel that regressed or a topology assumption that broke) and
faster than ``1/threshold ×`` (a model that went stale and is now
mis-steering method auto-selection — just as actionable).
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, List, Optional

DEFAULT_THRESHOLD = 3.0


@dataclasses.dataclass
class AuditRow:
    op: str
    method: Optional[str]
    shape: Optional[tuple]
    world: int
    estimate_us: float
    measured_us: float
    deviation: float          # measured / estimate
    flagged: bool

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["shape"] = list(self.shape) if self.shape else None
        return d


def audit_events(events: Iterable, threshold: float = DEFAULT_THRESHOLD
                 ) -> List[AuditRow]:
    """One row per event that carries both a measurement and an
    estimate; rows outside [1/threshold, threshold] are flagged.
    Updates the ``perf_audit_checks_total`` / ``perf_audit_flags_total``
    counters on the global registry."""
    from triton_distributed_tpu.observability.metrics import get_registry
    assert threshold > 1.0, threshold
    reg = get_registry()
    rows = []
    for ev in events:
        dev = ev.deviation
        if dev is None:
            continue
        flagged = not (1.0 / threshold <= dev <= threshold)
        rows.append(AuditRow(
            op=ev.op, method=ev.method, shape=ev.shape, world=ev.world,
            estimate_us=float(ev.estimate_us),
            measured_us=float(ev.measured_us),
            deviation=dev, flagged=flagged))
        reg.counter("perf_audit_checks_total", op=ev.op).inc()
        if flagged:
            reg.counter("perf_audit_flags_total", op=ev.op).inc()
    rows.sort(key=lambda r: max(r.deviation, 1 / r.deviation),
              reverse=True)
    return rows


def audit_recorded(threshold: float = DEFAULT_THRESHOLD
                   ) -> List[AuditRow]:
    """Audit everything currently in the flight-recorder ring."""
    from triton_distributed_tpu.observability.recorder import (
        get_flight_recorder)
    return audit_events(get_flight_recorder().events(), threshold)


def format_report(rows: List[AuditRow],
                  threshold: float = DEFAULT_THRESHOLD) -> str:
    if not rows:
        return "perf audit: no events carried both measurement and estimate"
    lines = [f"perf audit ({len(rows)} checks, threshold {threshold}x):"]
    for r in rows:
        mark = "FLAG" if r.flagged else " ok "
        lines.append(
            f" [{mark}] {r.op:<16} method={r.method or '-':<14} "
            f"world={r.world} shape={r.shape} "
            f"measured={r.measured_us:9.1f}us "
            f"estimate={r.estimate_us:9.1f}us "
            f"dev={r.deviation:6.2f}x")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Bench integration: one helper gives a bench driver's JSON lines
# the registry-backed schema (no driver is left in the tree: PERF.md).
# ---------------------------------------------------------------------------

#: bench name -> (op, fields needed to re-derive a model estimate).
_BENCH_OPS = {
    "ag_gemm": "ag_gemm",
    "gemm_rs": "gemm_rs",
    "allreduce": "all_reduce",
    "allgather": "all_gather",
    "reduce_scatter": "reduce_scatter",
}


def _estimate_for_bench(rec: dict) -> Optional[float]:
    """Re-derive the analytic estimate from a bench record's fields
    (M/K/N/world for the overlap GEMMs, nbytes/world for AR)."""
    import jax.numpy as jnp

    from triton_distributed_tpu.observability.instrument import (
        estimate_collective_us, estimate_overlap_gemm_us)

    op = _BENCH_OPS.get(rec.get("bench"))
    world = int(rec.get("world", 1))
    if op is None:
        return None
    try:
        if op in ("ag_gemm", "gemm_rs"):
            # Per-rank dims as the kernel sees them inside shard_map:
            # both benches shard M over tp; ag_gemm also shards N
            # (B's columns), gemm_rs shards K (the contraction).
            m = int(rec["M"]) // world
            n = int(rec["N"]) // (world if op == "ag_gemm" else 1)
            k = int(rec["K"]) // (1 if op == "ag_gemm" else world)
            return estimate_overlap_gemm_us(
                op, m, n, k, world, jnp.bfloat16, rec.get("method"))
        payload = int(rec.get("nbytes") or rec.get("payload_bytes"))
        return estimate_collective_us(op, payload, world,
                                      rec.get("method"))
    except (KeyError, TypeError, ValueError):
        return None


def percentile(samples, q: float) -> float:
    """Nearest-rank percentile of raw samples (q in [0, 100]).  With
    the drivers' handful of per-repeat slopes, p99 degenerates to the
    max — still the right tail bound to gate on."""
    xs = sorted(samples)
    if not xs:
        raise ValueError("percentile of no samples")
    rank = max(int(len(xs) * q / 100.0 + 0.999999) - 1, 0)
    return xs[min(rank, len(xs) - 1)]


def bench_record(rec: dict, *, print_line: bool = True) -> dict:
    """Route one bench measurement through the registry.

    ``rec`` is the driver's JSON-line dict (must carry "bench" and a
    measured "us"); the estimate/deviation are attached when the
    bench maps onto a perf model, the event lands in the recorder and
    metrics, and the (augmented) line is printed — so stdout
    and the registry export
    carry the same record.

    ``samples_us`` (optional, consumed): per-iteration latencies.
    Each lands in the ``bench_iteration_us{bench=...}`` registry
    histogram, and the line gains ``p50_us``/``p99_us`` — tails, not
    just the mean, so a reader can tell
    a kernel that got jittery without moving its median.
    """
    import json

    from triton_distributed_tpu.observability.events import (
        emit_kernel_event)
    from triton_distributed_tpu.observability.metrics import (
        get_registry, observability_enabled)

    rec = dict(rec)
    samples = rec.pop("samples_us", None)
    us = rec.get("us")
    if observability_enabled() and samples:
        hist = get_registry().histogram("bench_iteration_us",
                                        bench=str(rec.get("bench")))
        for s in samples:
            hist.observe(float(s))
        rec.setdefault("p50_us", round(percentile(samples, 50), 1))
        rec.setdefault("p99_us", round(percentile(samples, 99), 1))
    if observability_enabled() and us is not None:
        est = _estimate_for_bench(rec)
        if est is not None:
            rec["estimate_us"] = round(est, 1)
            rec["model_deviation"] = round(float(us) / est, 3)
        # Empirical twin of the analytic audit: score against the
        # rolling baseline for this (bench, shape, method, world) and
        # roll the measurement in (persisted beside the autotune
        # cache — see observability/anomaly.py).
        from triton_distributed_tpu.observability.anomaly import (
            Z_THRESHOLD, observe_bench)
        z = observe_bench(rec, float(us))
        if z is not None:
            rec["anomaly_z"] = round(z, 2)
            if abs(z) > Z_THRESHOLD:
                rec["anomaly"] = True
        ev = emit_kernel_event(
            _BENCH_OPS.get(rec.get("bench"), rec.get("bench", "bench")),
            kind="bench", method=rec.get("method"),
            world=int(rec.get("world", 1)),
            shape=tuple(int(rec[f]) for f in ("M", "K", "N")
                        if f in rec) or None,
            measured_us=float(us), estimate_us=est, bench=rec["bench"],
            vs_baseline=rec.get("vs_baseline"))
        if ev is not None and est is not None:
            audit_events([ev])
    if print_line:
        print(json.dumps(rec), flush=True)
    return rec
