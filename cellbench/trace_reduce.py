"""From a profiler trace (`.xplane.pb`) to the few numbers the
per-layer metrics read: how long each device was busy, how long each
program ran, which operations took most time, and what the host was
doing in the longest idle gaps.

Planes `/device:TPU:<n>` carry the lines "XLA Modules" (one event per
executed program, named e.g. `jit_step(<fingerprint>)`) and "XLA Ops"
(one event per operation).  Busy time is the UNION of the "XLA Ops"
intervals, so nested or overlapping events are not counted twice.
Host spans the harness wrote with `jax.profiler.TraceAnnotation` (names
starting `cellbench.`) are on the same clock and attribute the gaps.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
HOST_SPAN_PREFIX = "cellbench."


@dataclasses.dataclass
class Reduced:
    #: device planes found (chips traced)
    devices: int
    #: seconds an operation ran, per device, and their mean
    busy_s_per_device: List[float]
    busy_s: float
    #: program name (fingerprint stripped) -> durations in seconds, in
    #: time order, on the busiest device
    modules: Dict[str, List[float]]
    #: operation name (`op_name`) -> seconds on the busiest device,
    #: EVERY operation: what the kernel metrics read
    per_op: Dict[str, float]
    #: [name, seconds] of the ten that took most time: what the
    #: breakdown prints
    top_ops: List[Tuple[str, float]]
    #: [host span name, seconds] idle time of the busiest device by
    #: what the host was doing
    idle_gaps: List[Tuple[str, float]]


def merge_intervals(iv: Sequence[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    """Union of half-open intervals, sorted, overlaps merged."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def union_seconds(iv: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in merge_intervals(iv))


def program_name(event_name: str) -> str:
    """`jit_step(123456)` -> `jit_step`."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """A short, stable name for an "XLA Ops" event, whose own name is
    the whole HLO instruction: `%fusion.12 = ... fusion(...)` ->
    `fusion fusion`, a Pallas kernel -> `<name> custom-call
    tpu_custom_call`.  Instances that differ only in their trailing
    number (one a layer) share a name, so their times add up."""
    lhs, _, rhs = event_name.partition(" = ")
    base = re.sub(r"[.\d]+$", "", lhs.strip().lstrip("%"))
    if not rhs:
        return base[:80]
    m = re.search(r"\s([a-z][a-z\-]*)\(", " " + rhs)
    parts = [base, m.group(1) if m else ""]
    t = re.search(r'custom_call_target=\\?"([\w.]+)', rhs)
    if t:
        parts.append(t.group(1))
    return " ".join(p for p in parts if p)[:80]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line):
    return [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for e in line.events]


def reduce_planes(planes: dict, top: int = 10) -> Reduced:
    """``planes``: plane name -> {line name -> [(event name, start s,
    duration s)]} — what `read` extracts, and what a test can build by
    hand."""
    dev = {n: ls for n, ls in planes.items()
           if n.startswith(DEVICE_PLANE)}
    if not dev:
        raise ValueError(f"trace has no {DEVICE_PLANE}* plane: "
                         f"{sorted(planes)}")
    busy = {}
    for name, lines in dev.items():
        ops = lines.get("XLA Ops", [])
        busy[name] = union_seconds([(s, s + d) for _, s, d in ops])
    busiest = max(busy, key=busy.get)
    modules: Dict[str, List[float]] = {}
    for n, s, d in sorted(dev[busiest].get("XLA Modules", []),
                          key=lambda e: e[1]):
        modules.setdefault(program_name(n), []).append(d)
    per_op: Dict[str, float] = {}
    ops = dev[busiest].get("XLA Ops", [])
    for n, _, d in ops:
        n = op_name(n)
        per_op[n] = per_op.get(n, 0.0) + d
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]

    # idle gaps of the busiest device, by overlapping host span
    merged = merge_intervals([(s, s + d) for _, s, d in ops])
    gaps = [(a2 - b1, b1, a2)
            for (_, b1), (a2, _) in zip(merged, merged[1:]) if a2 > b1]
    spans = [(s, s + d, n) for name, lines in planes.items()
             if not name.startswith(DEVICE_PLANE)
             for evs in lines.values() for n, s, d in evs
             if n.startswith(HOST_SPAN_PREFIX)]
    spans.sort()
    span_starts = [s0 for s0, _, _ in spans]
    by_host: Dict[str, float] = {}
    for length, g0, g1 in gaps:
        # the harness's spans come from one thread and do not overlap:
        # a gap goes to the span that holds its midpoint
        mid = 0.5 * (g0 + g1)
        i = bisect.bisect_right(span_starts, mid) - 1
        name = "host:unannotated"
        if i >= 0 and spans[i][1] >= mid:
            name = spans[i][2]
        by_host[name] = by_host.get(name, 0.0) + length
    idle = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
    vals = list(busy.values())
    return Reduced(devices=len(dev), busy_s_per_device=vals,
                   busy_s=sum(vals) / len(vals), modules=modules,
                   per_op=per_op, top_ops=[(n, s) for n, s in top_ops],
                   idle_gaps=[(n, s) for n, s in idle])


def count_events(planes: dict) -> int:
    """Events `read` kept: what a trace's cost goes with."""
    return sum(len(evs) for lines in planes.values()
               for evs in lines.values())


def read(path: str) -> dict:
    """The planes of an `.xplane.pb`, as `reduce_planes` takes them.
    Host planes keep only the harness's own spans."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            if plane.name.startswith(DEVICE_PLANE):
                if line.name in ("XLA Modules", "XLA Ops"):
                    lines[line.name] = _events(line)
            else:
                evs = [e for e in _events(line)
                       if e[0].startswith(HOST_SPAN_PREFIX)]
                if evs:
                    lines.setdefault(line.name, []).extend(evs)
        if lines:
            planes[plane.name] = lines
    return planes

