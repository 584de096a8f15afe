"""SLO error budgets: per-class latency objectives, rolling burn-rate
accounting, and budget-breach alerts wired into the decision log.

PR 8's single global ``slo_tbt_ms`` gate answers "is this request
late?"; it cannot answer the operator's question — "is the *fleet*
eating its error budget, which class, and how fast?".  This module
adds the standard SRE machinery on the repo's injectable clocks:

- :class:`SLOClass`: one service class — TTFT/TBT p99 targets plus a
  compliance objective (e.g. 0.99 = at most 1% of requests may miss
  either target).
- :class:`SLOPolicy`: the set of classes, the tenant→class mapping
  (`Request.tenant` is the join key — `observability.costs` bills the
  same label), and the burn-alert rule: alert when the burn rate
  exceeds ``burn_alert_threshold`` over **every** configured window
  (the classic fast+slow multi-window confirmation: the short window
  proves it is happening now, the long window proves it is not a
  blip).
- :class:`SLOTracker`: per-class rolling outcome rings keyed by the
  caller's clock timestamps (virtual-clock runs are therefore
  bit-deterministic).  Burn rate over a window is
  ``bad_fraction / (1 - objective)`` — burn 1.0 consumes the budget
  exactly as fast as the objective allows; burn 2.0 halves the
  horizon.  Breaches fire once per excursion (edge-triggered,
  re-armed when the burn drops back under threshold) as schema-v1
  ``slo.burn_alert`` :class:`DecisionEvents
  <triton_distributed_tpu.observability.feedback.DecisionEvent>`, so
  the flight ring / ``/decisions`` / doctor all see them with zero
  new plumbing.

Golden discipline: nothing exists until an `SLOPolicy` is configured
— no tracker, no gauges (the heartbeat mirrors
``serving_slo_burn_max`` / ``serving_slo_budget_min`` only once they
are set), no ``slo-state.json`` artifact — so policy-free runs are
byte-identical to the pre-SLO tree.

See docs/serving.md "Accounting & SLOs" for window semantics.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

SLO_SCHEMA = 1

#: Artifact file `ServingCluster.write_artifact` drops when a policy
#: is armed (absent otherwise — the doctor's SLO section keys off it).
SLO_STATE_FILE = "slo-state.json"


@dataclasses.dataclass(frozen=True)
class SLOClass:
    """One service class: latency targets + compliance objective."""

    name: str
    ttft_p99_ms: float
    tbt_p99_ms: float
    #: Fraction of requests that must meet BOTH targets (the error
    #: budget is ``1 - objective``).
    objective: float = 0.99

    def compliant(self, ttft_ms: Optional[float],
                  tbt_ms: Optional[float]) -> bool:
        """A request complies when every *measured* latency meets its
        target (an unmeasured dimension — e.g. a single-token reply
        has no TBT — cannot breach)."""
        if ttft_ms is not None and ttft_ms > self.ttft_p99_ms:
            return False
        if tbt_ms is not None and tbt_ms > self.tbt_p99_ms:
            return False
        return True


@dataclasses.dataclass(frozen=True)
class SLOPolicy:
    """The fleet's SLO contract: classes, tenant mapping, alert rule."""

    classes: Tuple[SLOClass, ...]
    #: tenant label -> class name; unmapped tenants land in
    #: ``default_class`` (the first class when unset).
    tenant_class: Mapping[str, str] = dataclasses.field(
        default_factory=dict)
    default_class: Optional[str] = None
    #: Rolling windows (seconds, ascending) burn rates are computed
    #: over; an alert needs the threshold exceeded over ALL of them.
    windows: Tuple[float, ...] = (60.0, 300.0)
    burn_alert_threshold: float = 2.0

    def __post_init__(self):
        if not self.classes:
            raise ValueError("SLOPolicy needs at least one class")
        names = [c.name for c in self.classes]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate class names: {names}")
        default = self.default_class or names[0]
        if default not in names:
            raise ValueError(f"default_class {default!r} not in "
                             f"{names}")
        object.__setattr__(self, "default_class", default)
        for t, c in self.tenant_class.items():
            if c not in names:
                raise ValueError(f"tenant {t!r} maps to unknown "
                                 f"class {c!r}")

    def class_of(self, tenant: str) -> SLOClass:
        name = self.tenant_class.get(tenant, self.default_class)
        for c in self.classes:
            if c.name == name:
                return c
        raise AssertionError(name)  # __post_init__ validated


def _p99(values: Sequence[float]) -> Optional[float]:
    """Deterministic nearest-rank p99 (index ``ceil(0.99 n) - 1`` of
    the sorted sample) — no interpolation, so replays are bit-stable."""
    if not values:
        return None
    s = sorted(values)
    idx = max(0, -(-99 * len(s) // 100) - 1)
    return s[idx]


def evaluate_outcomes(policy: SLOPolicy,
                      outcomes: Sequence[Tuple[str, Optional[float],
                                               Optional[float]]]
                      ) -> Dict[str, dict]:
    """Batch compliance for a finished trace: ``outcomes`` are
    ``(tenant, ttft_ms, tbt_ms)`` tuples.  Returns per-class
    compliance + nearest-rank p99s — a pure scoring function,
    deterministic given its inputs."""
    per: Dict[str, dict] = {}
    for c in policy.classes:
        per[c.name] = {"total": 0, "compliant": 0,
                       "ttft_ms": [], "tbt_ms": []}
    for tenant, ttft_ms, tbt_ms in outcomes:
        c = policy.class_of(tenant)
        row = per[c.name]
        row["total"] += 1
        row["compliant"] += int(c.compliant(ttft_ms, tbt_ms))
        if ttft_ms is not None:
            row["ttft_ms"].append(float(ttft_ms))
        if tbt_ms is not None:
            row["tbt_ms"].append(float(tbt_ms))
    out: Dict[str, dict] = {}
    for c in policy.classes:
        row = per[c.name]
        total = row["total"]
        compliance = (row["compliant"] / total) if total else None
        out[c.name] = {
            "total": total,
            "compliant": row["compliant"],
            "compliance": (round(compliance, 6)
                           if compliance is not None else None),
            "objective": c.objective,
            # A class with no traffic holds its SLO vacuously.
            "ok": compliance is None or compliance >= c.objective,
            "p99_ttft_ms": _p99(row["ttft_ms"]),
            "p99_tbt_ms": _p99(row["tbt_ms"]),
            "target_ttft_ms": c.ttft_p99_ms,
            "target_tbt_ms": c.tbt_p99_ms,
        }
    return out


class SLOTracker:
    """Rolling per-class outcome store + burn-rate alerting.

    All timestamps come from the caller (the cluster's virtual clock
    in tests/smokes, wall time in production) — the tracker never
    reads a clock itself."""

    def __init__(self, policy: SLOPolicy):
        self.policy = policy
        self._lock = threading.RLock()
        #: class -> deque[(ts, ok, tenant)] in observation order.
        self._outcomes: Dict[str, collections.deque] = {
            c.name: collections.deque() for c in policy.classes}
        #: class -> lifetime totals (windows forget; budgets don't).
        self._lifetime: Dict[str, List[int]] = {
            c.name: [0, 0] for c in policy.classes}   # [total, bad]
        #: (class) currently in alert — edge-triggered re-fire guard.
        self._alerting: Dict[str, bool] = {}
        self.alerts_fired = 0

    # -- ingest ----------------------------------------------------------

    def observe(self, tenant: str, ttft_ms: Optional[float],
                tbt_ms: Optional[float], ts: float) -> bool:
        """Record one finished request's outcome; returns compliance.
        Mirrors into ``serving_slo_requests_total`` /
        ``serving_slo_breach_total`` (class+tenant labelled)."""
        c = self.policy.class_of(tenant)
        ok = c.compliant(ttft_ms, tbt_ms)
        from triton_distributed_tpu.observability.metrics import (
            count_metric)
        count_metric("serving_slo_requests_total", cls=c.name,
                     tenant=tenant)
        if not ok:
            count_metric("serving_slo_breach_total", cls=c.name,
                         tenant=tenant)
        with self._lock:
            self._outcomes[c.name].append((float(ts), ok, tenant))
            life = self._lifetime[c.name]
            life[0] += 1
            life[1] += 0 if ok else 1
            self._prune(c.name, float(ts))
        return ok

    def _prune(self, cls: str, now: float) -> None:
        horizon = now - max(self.policy.windows)
        dq = self._outcomes[cls]
        while dq and dq[0][0] < horizon:
            dq.popleft()

    # -- burn math -------------------------------------------------------

    def burn_rate(self, cls: str, window: float, now: float
                  ) -> Optional[float]:
        """``bad_fraction / (1 - objective)`` over the trailing
        ``window`` seconds; None when the window saw no traffic."""
        c = next(k for k in self.policy.classes if k.name == cls)
        budget = 1.0 - c.objective
        with self._lock:
            rows = [(ts, ok) for ts, ok, _ in self._outcomes[cls]
                    if ts >= now - window]
        if not rows or budget <= 0:
            return None
        bad = sum(1 for _, ok in rows if not ok)
        return (bad / len(rows)) / budget

    def budget_remaining(self, cls: str) -> float:
        """Lifetime error budget left, as a fraction of the allowance
        (1.0 = untouched, 0.0 = spent, negative = overdrawn)."""
        c = next(k for k in self.policy.classes if k.name == cls)
        budget = 1.0 - c.objective
        with self._lock:
            total, bad = self._lifetime[cls]
        if total == 0 or budget <= 0:
            return 1.0
        return 1.0 - (bad / total) / budget

    def dominant_tenant(self, cls: Optional[str] = None
                        ) -> Optional[str]:
        """The tenant with the most breaches (ties break by name) —
        the "who is burning my budget" answer the doctor prints."""
        counts: Dict[str, int] = {}
        with self._lock:
            for name, dq in self._outcomes.items():
                if cls is not None and name != cls:
                    continue
                for _, ok, tenant in dq:
                    if not ok:
                        counts[tenant] = counts.get(tenant, 0) + 1
        if not counts:
            return None
        return min(counts, key=lambda t: (-counts[t], t))

    # -- alerting --------------------------------------------------------

    def check(self, now: float) -> List[dict]:
        """Evaluate the multi-window alert rule and refresh the burn
        gauges.  Fires at most one ``slo.burn_alert`` DecisionEvent
        per class per excursion; returns the alerts fired."""
        from triton_distributed_tpu.observability.metrics import (
            get_registry, observability_enabled)
        fired: List[dict] = []
        enabled = observability_enabled()
        reg = get_registry() if enabled else None
        burn_max = 0.0
        budget_min = 1.0
        for c in self.policy.classes:
            burns = {w: self.burn_rate(c.name, w, now)
                     for w in self.policy.windows}
            remaining = self.budget_remaining(c.name)
            budget_min = min(budget_min, remaining)
            if reg is not None:
                for w, b in burns.items():
                    if b is not None:
                        reg.gauge("serving_slo_burn_rate",
                                  cls=c.name,
                                  window=f"{int(w)}s").set(b)
                        burn_max = max(burn_max, b)
                reg.gauge("serving_slo_budget_remaining",
                          cls=c.name).set(remaining)
            alerting = all(
                b is not None and b > self.policy.burn_alert_threshold
                for b in burns.values())
            was = self._alerting.get(c.name, False)
            self._alerting[c.name] = alerting
            if alerting and not was:
                alert = self._fire(c, burns, remaining, now)
                fired.append(alert)
        if reg is not None and self._ever_observed():
            reg.gauge("serving_slo_burn_max").set(burn_max)
            reg.gauge("serving_slo_budget_min").set(budget_min)
        return fired

    def _ever_observed(self) -> bool:
        with self._lock:
            return any(t for t, _ in self._lifetime.values())

    def _fire(self, c: SLOClass, burns: Dict[float, Optional[float]],
              remaining: float, now: float) -> dict:
        from triton_distributed_tpu.observability.feedback import (
            DecisionEvent, record_decision)
        self.alerts_fired += 1
        dominant = self.dominant_tenant(c.name)
        inputs = {
            "class": c.name,
            "objective": c.objective,
            "target_ttft_ms": c.ttft_p99_ms,
            "target_tbt_ms": c.tbt_p99_ms,
            "threshold": self.policy.burn_alert_threshold,
            "burn": {f"{int(w)}s": round(b, 6) for w, b in
                     burns.items() if b is not None},
            "budget_remaining": round(remaining, 6),
        }
        if dominant is not None:
            inputs["dominant_tenant"] = dominant
        record_decision(DecisionEvent(
            consumer="slo.burn_alert", op=f"class:{c.name}",
            choice="alert",
            candidates=[{"name": "alert"}, {"name": "within_budget"}],
            inputs=inputs, ts=now))
        return {"class": c.name, "ts": now, **inputs}

    # -- artifact --------------------------------------------------------

    def state_dict(self, now: float) -> dict:
        """The ``slo-state.json`` body: per-class compliance +
        burn/budget numbers, per-tenant breach attribution, and the
        per-tenant cost join (`observability.costs`) when armed."""
        classes = {}
        for c in self.policy.classes:
            with self._lock:
                total, bad = self._lifetime[c.name]
            burns = {f"{int(w)}s": self.burn_rate(c.name, w, now)
                     for w in self.policy.windows}
            classes[c.name] = {
                "target_ttft_ms": c.ttft_p99_ms,
                "target_tbt_ms": c.tbt_p99_ms,
                "objective": c.objective,
                "total": total,
                "breaches": bad,
                "compliance": (round(1.0 - bad / total, 6)
                               if total else None),
                "budget_remaining": round(
                    self.budget_remaining(c.name), 6),
                "burn": {w: (round(b, 6) if b is not None else None)
                         for w, b in burns.items()},
                "alerting": self._alerting.get(c.name, False),
            }
        tenants: Dict[str, dict] = {}
        with self._lock:
            for name, dq in self._outcomes.items():
                for _, ok, tenant in dq:
                    row = tenants.setdefault(
                        tenant, {"total": 0, "breaches": 0})
                    row["total"] += 1
                    row["breaches"] += 0 if ok else 1
        out: Dict[str, Any] = {
            "schema": SLO_SCHEMA,
            "ts": now,
            "windows_s": list(self.policy.windows),
            "burn_alert_threshold": self.policy.burn_alert_threshold,
            "alerts_fired": self.alerts_fired,
            "classes": classes,
            "tenants": dict(sorted(tenants.items())),
        }
        dominant = self.dominant_tenant()
        if dominant is not None:
            out["dominant_tenant"] = dominant
        from triton_distributed_tpu.observability.costs import (
            tenant_cost_table)
        costs = tenant_cost_table()
        if costs is not None:
            out["tenant_costs"] = costs
        return out
