"""Analytic communication performance model for method auto-selection.

Reference: `python/triton_dist/kernels/nvidia/comm_perf_model.py` (114
LoC) — `estimate_reduce_scatter_time_ms` / `estimate_all_gather_time_ms`
(`:93-114`), NIC bandwidth tables (`:34-80`).

TPU tables: per-generation ICI link bandwidth (per direction, per
link), links per chip, and DCN bandwidth for inter-slice.  Numbers are
the published per-chip figures; they parameterize the same
latency-vs-bandwidth decisions the reference makes with NVLink/PCIe/NIC
probes (SURVEY.md §2.3).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from triton_distributed_tpu.kernels.gemm_perf_model import (
    lookup_device_kind,
)


@dataclasses.dataclass(frozen=True)
class IciSpec:
    link_gbps: float        # per link, per direction (GB/s)
    num_links: int          # torus links per chip
    latency_us: float       # per-hop latency


# Published per-chip interconnect characteristics, keyed by the exact
# `device_kind` string (see `gemm_perf_model._CHIP_TABLE`).
_V5E_ICI = IciSpec(link_gbps=50.0, num_links=4, latency_us=1.0)
_ICI_TABLE = {
    "TPU v4": IciSpec(link_gbps=50.0, num_links=6, latency_us=1.0),
    "TPU v5 lite": _V5E_ICI,
    "TPU v5": IciSpec(link_gbps=100.0, num_links=6, latency_us=1.0),
    "TPU v6 lite": IciSpec(link_gbps=100.0, num_links=4, latency_us=1.0),
    "cpu": _V5E_ICI,    # interpret mode simulates the v5e
}

_DCN_GBPS = 25.0  # per host, typical


def get_ici_spec(device=None) -> IciSpec:
    return lookup_device_kind(_ICI_TABLE, device)


# Cache keyed on the visible device set: a process whose backend grows
# (e.g. jax.distributed.initialize after a premature local query) gets
# a fresh answer instead of a stale single-host sub-grid verdict.
_topo_cache: dict = {}


def rings_closed() -> bool:
    """Whether the attached slice's torus dimensions wrap around (from
    `parallel.mesh.node_topology` device-coords discovery).  On an
    open mesh (no wraparound) the ring schedule's wrap edge shares
    every link along the line, roughly doubling the busiest link's
    load; unknown topologies (CPU simulation) assume closed."""
    from triton_distributed_tpu.parallel.mesh import node_topology
    devices = jax.devices()
    key = (len(devices), devices[0].device_kind, jax.process_count())
    if key not in _topo_cache:
        rc = node_topology(devices).rings_closed
        _topo_cache[key] = True if rc is None else rc
    return _topo_cache[key]


def estimate_all_gather_time_us(nbytes_per_shard: int, world: int,
                                spec: IciSpec = None,
                                closed_ring: bool = None) -> float:
    """Ring AG: (world-1) steps, each shipping one shard one hop along
    the axis ring — every directed link carries each shard exactly
    once, the bandwidth-optimal schedule.  On an open line (no
    wraparound) the wrap hop routes through every link, ~doubling the
    busiest link's traffic."""
    spec = spec or get_ici_spec()
    closed = rings_closed() if closed_ring is None else closed_ring
    bw = spec.link_gbps * 1e9
    load = 1.0 if closed else 2.0
    return (world - 1) * (load * nbytes_per_shard / bw * 1e6
                          + spec.latency_us)


def estimate_reduce_scatter_time_us(nbytes_per_shard: int, world: int,
                                    spec: IciSpec = None) -> float:
    return estimate_all_gather_time_us(nbytes_per_shard, world, spec)


def estimate_all_reduce_time_us(nbytes: int, world: int,
                                spec: IciSpec = None,
                                closed_ring: bool = None) -> float:
    """ring AR = RS + AG over chunks of nbytes/world."""
    return 2 * estimate_all_gather_time_us(nbytes // world, world, spec,
                                           closed_ring=closed_ring)


def estimate_chain_allreduce_time_us(nbytes: int, world: int,
                                     spec: IciSpec = None) -> float:
    """Pipelined line (chain) AllReduce: partials flow toward rank 0
    on one link direction while the broadcast streams back on the
    other — per directed link ~nbytes once, NO wrap hop, so the open-
    topology penalty never applies.  Latency: the first chunk crosses
    the line twice (2(w-1) hops); bandwidth: reduce and broadcast ride
    opposite directions and overlap, so ~nbytes/bw once the pipe
    fills.  The TPU analogue of the reference's double-tree
    (`kernels/nvidia/allreduce.py:418`) — latency-optimal at mid
    sizes, open topologies, where one-shot's fan-out congests and the
    ring pays the wrap."""
    spec = spec or get_ici_spec()
    bw = spec.link_gbps * 1e9
    return (nbytes / bw * 1e6 + 2 * (world - 1) * spec.latency_us)


def estimate_one_shot_time_us(nbytes: int, world: int,
                              spec: IciSpec = None,
                              closed_ring: bool = None) -> float:
    """One-shot push: world-1 concurrent direct puts on the axis ring.

    Unlike a ring schedule (single-hop transfers only), a direct put
    to a peer at distance d occupies d links; summed over both ring
    directions the busiest directed link carries ~world²/8 payload
    transits (~world²/4 on an open line, where the far half cannot
    route the short way).  That link is the bottleneck, so one-shot
    loses to the ring for large payloads at scale but wins the latency
    race (1 hop vs world-1 serialized hops) for small ones — the same
    topology-awareness as the reference's
    `get_auto_all_gather_method`."""
    spec = spec or get_ici_spec()
    closed = rings_closed() if closed_ring is None else closed_ring
    bw = spec.link_gbps * 1e9
    denom = 8.0 if closed else 4.0
    link_transits = max(1.0, world * world / denom)
    # Farthest put crosses world/2 hops on a closed ring, world-1 on a
    # line — the latency term is the longest path, not a single hop.
    far = world / 2.0 if closed else float(world - 1)
    lat = max(1.0, far) * spec.latency_us
    return link_transits * nbytes / bw * 1e6 + lat


def estimate_torus_ag_time_us(nbytes_per_shard: int, sizes,
                              spec: IciSpec = None,
                              closed_ring: bool = None) -> float:
    """Multi-lane torus AG (`kernels/torus.py`, 2 or 3 axes): with the
    cyclic-rotation lane schedule, axis ``ax`` appears at phase p in
    exactly one lane per direction, whose slab there is
    nbytes/L · prod(sizes of the p axes cyclically preceding ax).
    Per-directed-link load along ax:
    (w_ax - 1) · nbytes/L · Σ_p Π_{j=1..p} w_{(ax-j) mod nd} — the
    busiest axis decides.  For a square 2-axis torus that is
    (w²-1)·nbytes/4 (HALF a bidirectional single-axis ring's load);
    for a cubic 3-axis torus (w³-1)·nbytes/6 — a THIRD."""
    sizes = tuple(int(s) for s in sizes)
    nd = len(sizes)
    L = 2 * nd
    spec = spec or get_ici_spec()
    closed = rings_closed() if closed_ring is None else closed_ring
    bw = spec.link_gbps * 1e9
    load = 1.0 if closed else 2.0
    per_axis = []
    for ai, w in enumerate(sizes):
        tot = 0.0
        for p in range(nd):
            prod = 1
            for j in range(1, p + 1):
                prod *= sizes[(ai - j) % nd]
            tot += prod
        per_axis.append((w - 1) * tot * nbytes_per_shard / L)
    hops = sum(w - 1 for w in sizes)   # serialized per-phase steps
    return (load * max(per_axis) / bw * 1e6
            + hops * spec.latency_us)


def _consult_bus(bus):
    """Resolve the feedback bus a chooser should act on.

    Returns ``(signals, fallback, record)``: ``signals`` is a fresh
    snapshot carrying link heat (None otherwise — the STATIC path),
    ``fallback`` the truthful reason signals were unusable, ``record``
    whether a DecisionEvent should be emitted.  An explicitly-passed
    bus always records (even its fallbacks — that IS the
    explainability contract); the ambient bus records only when live
    signals actually influenced the choice, so bus-less programs keep
    today's exact event streams."""
    explicit = bus is not None
    if bus is None:
        from triton_distributed_tpu.observability import feedback
        bus = feedback.ambient_bus()
        if bus is None:
            return None, None, False
    sig = bus.read()
    if not (sig.link_utilization or sig.contended_links):
        return None, "signals_absent", explicit
    if not sig.fresh(bus.clock(), bus.staleness_s):
        return None, "signals_stale", explicit
    return sig, None, True


def _record_method_decision(op, choice, candidates, sig, fallback,
                            axes=None):
    from triton_distributed_tpu.observability import feedback
    inputs = sig.to_inputs(axes=axes) if sig is not None else {}
    feedback.record_decision(feedback.DecisionEvent(
        consumer="comm.method_select", op=op, choice=choice,
        candidates=[{"name": name, "score_us": round(t, 3)}
                    for name, t in candidates],
        inputs=inputs, fallback=fallback))


def _derated(spec: IciSpec, busy: float):
    """Residual-bandwidth spec under background load ``busy`` — the
    identical object when there is nothing to derate, so the
    empty-bus path cannot perturb a single bit."""
    from triton_distributed_tpu.observability.feedback import (
        effective_spec)
    return effective_spec(spec, busy)


def torus_beats_single_axis(nbytes_per_shard: int, sizes,
                            spec: IciSpec = None,
                            margin: float = 0.7, *,
                            axes=None, bus=None,
                            op: str = "all_gather_torus") -> bool:
    """Crossover for the multi-axis torus schedule vs the best
    single-axis method over the flattened world: the torus wins on
    bandwidth (~nd× a bidir ring) once payloads amortize its extra
    latency (nd serialized ring phases + 2·nd-way chunk split).
    ``margin`` is the same hysteresis convention as
    `choose_ll_or_fused`: the torus kernel's un-modeled fixed costs
    (per-axis entry barrier, 2·nd× strided-DMA issue) mean a marginal
    modeled win is not a real one, so the simple path is kept unless
    the win is decisive.

    Closed loop (``bus``/ambient — see `observability.feedback`): a
    single-axis schedule serializes all traffic through the busiest
    lane, so it sees the WORST background utilization over ``axes``;
    the 2·nd-lane torus spreads over every axis and sees the MEAN —
    live contention on one axis (a concurrent decode allreduce)
    therefore shifts the crossover toward the schedule that avoids
    the hot links.  Empty/stale signals keep the static choice
    bit-identically."""
    sizes = tuple(int(s) for s in sizes)
    world = 1
    for s in sizes:
        world *= s
    sig, fallback, record = _consult_bus(bus)
    spec_t = spec_1 = spec
    if sig is not None:
        names = list(axes) if axes else [None]
        spec0 = spec or get_ici_spec()
        u_single = max(sig.busy_fraction(a) for a in names)
        u_torus = (sig.mean_busy_fraction(names) if axes
                   else u_single)
        spec_t = _derated(spec0, u_torus)
        spec_1 = _derated(spec0, u_single)
    t_torus = estimate_torus_ag_time_us(nbytes_per_shard, sizes,
                                        spec_t)
    t_1axis = min(
        estimate_all_gather_time_us(nbytes_per_shard, world, spec_1),
        estimate_one_shot_time_us(nbytes_per_shard, world, spec_1))
    wins = t_torus < margin * t_1axis
    if record:
        _record_method_decision(
            op, "torus" if wins else "single_axis",
            [("torus", t_torus), ("single_axis", t_1axis)],
            sig, fallback, axes=axes)
    return wins


def estimate_two_shot_time_us(nbytes: int, world: int,
                              spec: IciSpec = None) -> float:
    """Two-shot AR: scatter partial chunks to their owners, then
    broadcast reduced chunks — two serialized one-shot rounds on
    1/world-size payloads."""
    return 2 * estimate_one_shot_time_us(max(nbytes // world, 1), world,
                                         spec)


def one_shot_beats_ring(nbytes: int, world: int,
                        spec: IciSpec = None, *,
                        axis: Optional[str] = None, bus=None,
                        op: str = "collective") -> bool:
    """Shared crossover decision for AG/RS method auto-selection, so
    all collectives agree on the same perf-model comparison.

    Closed loop: background utilization on the axis' links derates
    the residual bandwidth both methods see — one-shot's busiest link
    carries ~world²/8 payload transits vs the ring's exactly one, so
    under live contention its bandwidth term inflates ~world²/8×
    faster and the crossover shifts toward the ring earlier.
    Empty/stale signals keep the static choice bit-identically."""
    sig, fallback, record = _consult_bus(bus)
    spec_eff = spec
    if sig is not None:
        spec_eff = _derated(spec or get_ici_spec(),
                            sig.busy_fraction(axis))
    t_one = estimate_one_shot_time_us(nbytes, world, spec_eff)
    t_ring = estimate_all_gather_time_us(nbytes, world, spec_eff)
    wins = t_one <= t_ring
    if record:
        _record_method_decision(
            op, "one_shot" if wins else "ring",
            [("one_shot", t_one), ("ring", t_ring)], sig, fallback,
            axes=[axis] if axis else None)
    return wins


def choose_ll_or_fused(chunk_bytes: int, m_rows: int, n: int, k: int,
                       world: int, dtype,
                       margin: float = 0.7, *,
                       axis: Optional[str] = None, bus=None,
                       op: str = "ag_gemm") -> str:
    """Shared fused-ring vs one-shot-ll chooser for the overlap GEMMs
    (ag_gemm / gemm_rs): the ring wins when each chunk's matmul hides
    its DMA; ll wins when the GEMM is B-streaming-bound (a per-chunk
    matmul loop re-reads B `world` times).

    ``margin`` is hysteresis protecting the hardware-validated regime:
    the fused ring (real-TPU autotuned, vs_baseline 1.0-1.15) is only
    abandoned when the analytic model predicts a DECISIVE ll win
    (t_ll < margin * t_fused) — published-peak tables with a fixed
    efficiency derate cannot be trusted to call a 1% margin.

    Closed loop: background utilization on the axis derates the comm
    terms only (the MXU is not the contended resource).  The fused
    ring hides its per-step DMA under the chunk matmul until the
    derated comm outgrows it, while ll's one-shot comm is serial and
    ~world²/8 link-transits heavy — so live contention (e.g. a decode
    allreduce sharing the axis) pushes the choice toward the fused
    schedule that keeps overlapping.  Empty/stale signals keep the
    static choice bit-identically.
    """
    from triton_distributed_tpu.kernels.gemm_perf_model import (
        estimate_gemm_time_us)

    sig, fallback, record = _consult_bus(bus)
    spec_eff = None
    if sig is not None:
        spec_eff = _derated(get_ici_spec(), sig.busy_fraction(axis))
    step_comm = (estimate_all_gather_time_us(chunk_bytes, world,
                                             spec_eff)
                 / max(world - 1, 1))
    t_fused = world * max(
        estimate_gemm_time_us(m_rows, n, k, dtype), step_comm)
    t_ll = (estimate_one_shot_time_us(chunk_bytes, world, spec_eff)
            + estimate_gemm_time_us(world * m_rows, n, k, dtype))
    choice = "ll" if t_ll < margin * t_fused else "fused"
    if record:
        _record_method_decision(
            op, choice, [("ll", t_ll), ("fused", t_fused)], sig,
            fallback, axes=[axis] if axis else None)
    return choice
