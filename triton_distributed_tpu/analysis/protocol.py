"""The cluster-protocol sweep: the scope matrix tier-1 pins.

`analysis.protocol_model` is the engine (one exhaustive exploration
of one `ProtocolScope`); this module fixes the MATRIX the CLI, the
tier-1 tests (``tests/test_protocol_analysis.py``) and
the doctor's protocol consult all share: both transport contracts
(in-process `VirtualTransport` and the `SocketTransport`+`WireHost`
networked claim/partition discipline), flat and hierarchical
routing, plus one single-request scope with a deeper fault budget
(chained faults on one shipment need budget more than they need
peers).

Each scope carries its own state cap: the two smallest explore to
exhaustion; the two-request and hierarchical scopes are bounded
(the small-scope hypothesis says the interesting interleavings are
shallow — BFS covers every interleaving up to the cap's horizon).
The whole sweep is sized to stay well inside the tier-1 time budget
on CPU (~15-25 s).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from triton_distributed_tpu.analysis.model import Finding
from triton_distributed_tpu.analysis.protocol_model import (
    ProtocolScope, check_protocol_model)

#: One deep-fault single-request prompt (shared-prefix tokens keep
#: the affinity map and prefix directory engaged even solo).
_SOLO = ((7, 7, 7, 7, 1, 2, 3, 4),)


def protocol_scopes() -> List[Tuple[str, ProtocolScope, int]]:
    """``(label, scope, max_states)`` for every scope the tier-1
    sweep must hold clean."""
    return [
        # Two requests, two replicas, flat routing over the virtual
        # wire: the commit-on-accept / idempotence / resume core.
        ("virtual.flat", ProtocolScope(), 12000),
        # One request, deeper fault budget: chained drop/corrupt/
        # dup/reorder/stale on a single shipment (explores to
        # exhaustion).
        ("virtual.deep_fault",
         ProtocolScope(prompts=_SOLO, targets=(2,), max_faults=2),
         20000),
        # The networked contract: claim as RPC, a crashed peer's
        # channel closing mid-flight, partition folding into NACK.
        ("socket.flat",
         ProtocolScope(transport="socket", prompts=_SOLO,
                       targets=(2,), max_faults=2),
         20000),
        # Two-level pod routing: cell aggregates going absent, dead
        # cells, the front door's degrade-around contract.
        ("virtual.hierarchical",
         ProtocolScope(hierarchical=True, n_replicas=3, n_cells=2),
         8000),
    ]


def sweep_protocol(max_depth: int = 26,
                   stats: Optional[Dict[str, dict]] = None
                   ) -> List[Tuple[str, List[Finding]]]:
    """Run every scope in the matrix; returns ``[(label, findings)]``
    (tier-1 asserts every findings list is empty).  ``stats``, when
    given, collects per-label exploration counters."""
    out = []
    for label, scope, max_states in protocol_scopes():
        st: dict = {}
        findings = check_protocol_model(
            scope, max_states=max_states, max_depth=max_depth,
            stats=st)
        if stats is not None:
            stats[label] = st
        out.append((label, findings))
    return out
