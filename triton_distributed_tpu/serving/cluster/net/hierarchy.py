"""Pod-scale hierarchical routing: a front door over per-cell routers.

A flat `ClusterRouter` does O(replicas) score evaluations per request
and grows one prefix-affinity map plus one `PrefixDirectory` over the
whole fleet — fine at 2-8 replicas, the wrong shape at pod scale.
The hierarchy splits placement into two O(small) decisions:

- the **pod front door** (`PodFrontDoor`) picks a CELL from cached
  per-cell aggregate signals — O(cells) work per request, with the
  exact PR-8 degradation contract (any absent/stale cell aggregate
  degrades the whole cell choice to round-robin, bit-identically,
  on the same rotation counter);
- the chosen **cell** (`Cell`) owns its replicas, its own
  `ClusterRouter` (scoring only cell members — O(cell) evaluations),
  its own `PrefixDirectory` (chains registered only for prompts the
  cell actually accepted) and its own ``decisions.jsonl`` — so every
  piece of per-request state is bounded by the cell, not the pod.

Aggregate refresh (`PodFrontDoor.refresh`) is the only O(pod) walk
and runs at heartbeat cadence, not per request — the same
amortization the flat router already applies to beats.  Cell scores
are per-replica EXPECTED work ``(n + queue + slots) * eff_step / n``
so a big cell is not penalized for having more members.

Affinity composes across the levels: the front door keys a
prefix -> home-CELL map (bounded LRU), the cell router keys its own
prefix -> home-REPLICA map, both written at route COMMIT only.
`tests/test_net.py::TestHierarchy` pins the
O(cell) claims: per-request score evaluations and per-cell directory
size must stay flat as the pod grows.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

from triton_distributed_tpu.serving.cluster.peer_cache import (
    PrefixDirectory)
from triton_distributed_tpu.serving.cluster.router import (
    LINK_CAP, ClusterRouter, RouterConfig)

#: Decision-schema consumer label for front-door (cell-level) picks.
POD_CONSUMER = "cluster.pod"


class CellRouter(ClusterRouter):
    """A cell's `ClusterRouter`, mirroring every committed route into
    a cell-local decision list so the cell can write its OWN
    ``decisions.jsonl`` (the global feedback log interleaves all
    consumers of the process; a pod has one file per cell)."""

    def __init__(self, config, replicas, cell_name: str):
        super().__init__(config, replicas)
        self.cell_name = cell_name
        self.decisions: List[dict] = []

    def _record_route(self, op, choice, candidates, inputs, fallback,
                      n_alive: int) -> None:
        self.decisions.append({
            "schema": 1, "consumer": "cluster.router",
            "ts": round(time.time(), 6), "rank": 0,  # noqa: W001 (decision-log wall-stamp, not routing state)
            "op": op, "choice": choice.name,
            "candidates": list(candidates),
            "inputs": dict(inputs, alive=n_alive,
                           cell=self.cell_name),
            "fallback": fallback})
        super()._record_route(op, choice, candidates, inputs,
                              fallback, n_alive)


class Cell:
    """One routing cell: a slice of the fleet, scored and cached
    independently of every other cell."""

    def __init__(self, cell_id: int, replicas,
                 router_cfg: Optional[RouterConfig] = None,
                 page_size: int = 16, directory_max: int = 1024):
        self.id = int(cell_id)
        self.name = f"cell-{cell_id}"
        self.router = CellRouter(router_cfg, replicas, self.name)
        self.router.directory = PrefixDirectory(
            page_size, max_entries=directory_max)
        #: Cached aggregate signal snapshot (None = absent -> the
        #: front door degrades to round-robin over cells).
        self._agg: Optional[dict] = None

    @property
    def replicas(self) -> List:
        return self.router.replicas

    @property
    def directory(self) -> PrefixDirectory:
        return self.router.directory

    def routable(self) -> List:
        return [r for r in self.replicas if r.routable]

    def refresh(self, now: float) -> Optional[dict]:
        """Re-aggregate this cell's replica signals into one cached
        snapshot.  O(cell); the front door calls it for every cell at
        heartbeat cadence (the one amortized O(pod) walk).  Any
        member with an absent snapshot voids the whole aggregate —
        partial information would bias against the quiet cell."""
        reps = self.routable()
        if not reps:
            self._agg = None
            return None
        sigs = []
        for r in reps:
            fn = getattr(r, "signals", None)
            sig = fn(now) if fn is not None else None
            if sig is None:
                self._agg = None
                return None
            sigs.append(sig)
        n = len(sigs)
        self._agg = {
            # The OLDEST member timestamp gates staleness: a cell is
            # only as fresh as its least-recently-heard replica.
            "ts": min(s["ts"] for s in sigs),
            "queue_depth": float(sum(s["queue_depth"] for s in sigs)),
            "active_slots": float(sum(s["active_slots"]
                                      for s in sigs)),
            "kv_occupancy": sum(s["kv_occupancy"] for s in sigs) / n,
            "step_us": sum(s["step_us"] for s in sigs) / n,
            "link_busy": sum(s["link_busy"] for s in sigs) / n,
            "n_routable": n,
        }
        return self._agg

    def signals(self) -> Optional[dict]:
        return self._agg

    def table_row(self, now: float) -> dict:
        agg = self._agg or {}
        return {
            "name": self.name,
            "replicas": len(self.replicas),
            "routable": len(self.routable()),
            "routed": sum(r.routed_total for r in self.replicas),
            "queue_depth": agg.get("queue_depth", 0.0),
            "directory_chains": len(self.directory),
            "affinity_prefixes": len(self.router._affinity),
            "score_evals": self.router.score_evals,
        }


class PodFrontDoor:
    """Two-level placement for a pod of cells.

    ``route`` picks a cell (O(cells) against cached aggregates, or
    the shared-rotation round-robin fallback), then delegates to the
    cell's router (O(cell)); ``commit_route`` commits BOTH levels —
    the cell-level affinity map and decision record land only once
    the dispatch really stuck, the same commit-on-accept contract as
    the flat router."""

    def __init__(self, cells: Sequence[Cell],
                 config: Optional[RouterConfig] = None):
        self.cells = list(cells)
        self.config = config or RouterConfig()
        self._rr = 0
        #: Cell score evaluations — the front door's share of the
        #: per-request work (`evals` adds the cells' shares).
        self.cell_evals = 0
        self._affinity: Dict[Tuple[int, ...], int] = {}
        self._staged: Optional[tuple] = None
        self.decisions: List[dict] = []

    # -- signal upkeep (heartbeat cadence, not per request) --------------

    def refresh(self, now: float) -> None:
        for c in self.cells:
            c.refresh(now)

    # -- placement -------------------------------------------------------

    def route(self, tokens: Sequence[int], op: str, now: float):
        """Pick ``(cell, replica)`` for one request; either may be
        None when nothing is routable.  A cell whose own router
        declines (all members drained since the aggregate refresh)
        falls through to the next cell along the rotation — the front
        door must steer around a dead cell, not wedge on it."""
        self._staged = None
        alive = [c for c in self.cells if c.routable()]
        if not alive:
            return None, None
        k = self._rr % len(alive)
        self._rr += 1
        fallback = None
        key = None
        candidates: List[dict] = []
        if self.config.mode != "signal_aware":
            order = [alive[(k + i) % len(alive)]
                     for i in range(len(alive))]
            fallback = "round_robin"
        else:
            aggs = {c.id: c.signals() for c in alive}
            stale = [a is None
                     or (now - a["ts"]) > self.config.staleness_s
                     for a in aggs.values()]
            if any(stale):
                order = [alive[(k + i) % len(alive)]
                         for i in range(len(alive))]
                fallback = ("signals_absent"
                            if any(a is None for a in aggs.values())
                            else "signals_stale")
            else:
                self.cell_evals += len(alive)
                scores = {c.id: self._score(aggs[c.id])
                          for c in alive}
                order = sorted(
                    alive,
                    key=lambda c: (scores[c.id],
                                   (alive.index(c) - k) % len(alive)))
                key = self._affinity_key(tokens)
                if key is not None:
                    home_id = self._affinity.get(key)
                    home = next((c for c in alive
                                 if c.id == home_id), None)
                    if (home is not None
                            and scores[home.id] <= (
                                self.config.affinity_slack
                                * scores[order[0].id])):
                        order = ([home]
                                 + [c for c in order if c is not home])
                candidates = [
                    {"name": c.name,
                     "score_us": round(scores[c.id], 3)}
                    for c in alive]
        for cell in order:
            rep = cell.router.route(tokens, op, now)
            if rep is not None:
                self._staged = (op, cell, candidates, fallback,
                                len(alive), key)
                return cell, rep
        return None, None

    def _score(self, agg: dict) -> float:
        """Per-replica EXPECTED work in the cell: total queued work
        derated by link load, normalized by member count so cell size
        does not masquerade as cell load."""
        derate = max(1.0 - min(agg["link_busy"], LINK_CAP), 0.1)
        eff = agg["step_us"] / derate
        n = max(agg["n_routable"], 1)
        return (n + agg["queue_depth"] + agg["active_slots"]) \
            * eff / n

    def _affinity_key(self, tokens: Sequence[int]):
        n = self.config.affinity_tokens
        if n <= 0 or len(tokens) < n:
            return None
        return tuple(int(t) for t in tokens[:n])

    def commit_route(self, now: Optional[float] = None) -> None:
        """Commit both levels of the last `route()` (no-op when
        nothing is staged)."""
        staged, self._staged = self._staged, None
        if staged is None:
            return
        op, cell, candidates, fallback, n_alive, key = staged
        cell.router.commit_route(now)
        if key is not None:
            self._affinity.pop(key, None)
            self._affinity[key] = cell.id
            while len(self._affinity) > self.config.affinity_max:
                del self._affinity[next(iter(self._affinity))]
        event = {
            "schema": 1, "consumer": POD_CONSUMER,
            "ts": round(time.time(), 6), "rank": 0,  # noqa: W001 (decision-log wall-stamp, not routing state)
            "op": op, "choice": cell.name,
            "candidates": list(candidates),
            "inputs": {"alive": n_alive,
                       "affinity": key is not None
                       and self._affinity.get(key) == cell.id},
            "fallback": fallback}
        self.decisions.append(event)
        from triton_distributed_tpu.observability import feedback
        from triton_distributed_tpu.observability.metrics import (
            observability_enabled)
        if observability_enabled():
            feedback.record_decision(feedback.DecisionEvent(
                consumer=POD_CONSUMER, op=op, choice=cell.name,
                candidates=candidates,
                inputs=dict(event["inputs"]), fallback=fallback))

    # -- accounting / introspection --------------------------------------

    def evals(self) -> int:
        """Total score evaluations across both levels — the work the
        bench compares against a flat router's O(pod)/request."""
        return self.cell_evals + sum(c.router.score_evals
                                     for c in self.cells)

    def table(self, now: float) -> dict:
        return {
            "schema": 1, "kind": "pod",
            "ts": round(now, 6),
            "cells": [c.table_row(now) for c in self.cells],
            "affinity_prefixes": len(self._affinity),
            "cell_evals": self.cell_evals,
        }

    def write_decisions(self, root: str) -> List[str]:
        """One ``decisions.jsonl`` per level: the pod's cell choices
        at ``<root>/decisions.jsonl`` and each cell's placements at
        ``<root>/<cell>/decisions.jsonl`` — every line schema-v1
        (`observability.feedback.validate_decision`)."""
        os.makedirs(root, exist_ok=True)
        paths = []

        def dump(path: str, events: List[dict]) -> None:
            with open(path, "w") as f:
                for e in events:
                    f.write(json.dumps(e, default=str) + "\n")
            paths.append(path)

        dump(os.path.join(root, "decisions.jsonl"), self.decisions)
        for c in self.cells:
            d = os.path.join(root, c.name)
            os.makedirs(d, exist_ok=True)
            dump(os.path.join(d, "decisions.jsonl"),
                 c.router.decisions)
        return paths


def make_pod(replicas, n_cells: int,
             router_cfg: Optional[RouterConfig] = None,
             page_size: int = 16,
             directory_max: int = 1024) -> PodFrontDoor:
    """Partition ``replicas`` into ``n_cells`` contiguous cells and
    return the front door over them (the bench/test constructor)."""
    replicas = list(replicas)
    n_cells = max(1, min(int(n_cells), len(replicas) or 1))
    per = (len(replicas) + n_cells - 1) // n_cells
    cells = [Cell(i, replicas[i * per:(i + 1) * per],
                  router_cfg=router_cfg, page_size=page_size,
                  directory_max=directory_max)
             for i in range(n_cells)]
    return PodFrontDoor([c for c in cells if c.replicas],
                        config=router_cfg)
