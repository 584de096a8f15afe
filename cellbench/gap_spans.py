"""Every token gap put down to what made it: the scheduler's own
records of its reads and its enqueues, cut to the run's window.

The program leaves on the `serving.sync` span of every read what that
read knew (`docs/observability.md`): `interval_ms` (commit to commit:
the token gap every committed row sees), `rows`, `first_tokens` (rows
whose request had no token yet: they see no gap), `prefills` and
`prefill_tokens` (the prefills the device ran in front), `early` (the
read an admitting call makes between or ahead of its admission's
halves), `landed` (the tokens were finished before the host asked: the
tokens waited, not the host).  On `serving.admit.prefill` and
`serving.dispatch` it leaves `starved` (the program enqueued last had
finished: the chip stood idle for this enqueue) and `idle` (the server
had nothing to do: it stood idle for want of arrivals, not of the
host), on the first also `flight`, `bucket` and `queue_wait_ms`; a
pause of Python's collector over a millisecond is a `runtime.gc` span.

Nothing here imports the program: the tracer comes through
`span_reader.tracer_of`, spans are put on `time.monotonic` by `Span.t0
+ tracer.monotonic_offset`.  A program without these records (the
parent of the PR that added them, `TDT_OBSERVABILITY=0`), a ring that
dropped spans, a window with none: None, and why on stdout.

A `--trace 1` run's window holds the profiler's stop, during which the
drive loop stands still for seconds (`Drive.trace_span[1]`,
`Drive.stop_trace_s`): the ONE read whose interval spans that
standstill and the first enqueue after it are the harness's own pause
and are left out (`dropped_at_trace_stop`).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from cellbench import span_reader, stats
from cellbench.clock import say

READ = "serving.sync"
FRONT = "serving.admit.prefill"
DISPATCH = "serving.dispatch"
STEP = "serving.step"
REQUEST = "serving.request"       # a lifetime, not host work
HARNESS = "harness"               # between two calls of `step()`


@dataclasses.dataclass
class Read:
    end: float            # the tokens on the host, time.monotonic
    interval_ms: float
    rows: int
    gaps: int             # rows that saw a gap: rows - first_tokens
    prefills: int
    prefill_tokens: int
    early: bool
    landed: bool
    step: Optional[int]   # id of the `serving.step` that read


@dataclasses.dataclass
class Enqueue:
    start: float
    end: float
    program: str          # "prefill" | "step"
    starved: bool
    idle: bool
    span: object


def standstill(drive) -> Optional[Tuple[float, float]]:
    """Where the drive loop stood still to write the trace."""
    if not drive.trace_span or not drive.stop_trace_s:
        return None
    return drive.trace_span[1], drive.trace_span[1] + drive.stop_trace_s


def reads_of(tracer, a: float, b: float, still=None):
    """(reads that landed in [a, b) and carry the record, how many the
    trace's stop took out)."""
    off = tracer.monotonic_offset
    out, dropped = [], 0
    for s in tracer.finished():
        at = s.attrs
        if s.name != READ or "interval_ms" not in at:
            continue
        end = s.t0 + off + s.dur
        if not a <= end < b:
            continue
        if still and end > still[0] and (
                end - at["interval_ms"] / 1e3 < still[1]):
            dropped += 1
            continue
        out.append(Read(
            end, at["interval_ms"], at["rows"],
            at["rows"] - at.get("first_tokens", 0), at["prefills"],
            at["prefill_tokens"], bool(at["early"]), bool(at["landed"]),
            s.parent))
    out.sort(key=lambda r: r.end)
    return out, dropped


def enqueues_of(tracer, a: float, b: float, still=None):
    """(enqueues of a prefill or a step that began in [a, b) and say
    whether they found the chip idle, how many the trace's stop took
    out: the first one after it)."""
    off = tracer.monotonic_offset
    out = []
    for s in tracer.finished():
        if s.name in (FRONT, DISPATCH) and "starved" in s.attrs:
            t = s.t0 + off
            if a <= t < b:
                out.append(Enqueue(
                    t, t + s.dur,
                    "prefill" if s.name == FRONT else "step",
                    bool(s.attrs["starved"]), bool(s.attrs.get("idle")),
                    s))
    out.sort(key=lambda e: e.start)
    dropped = 0
    if still:
        for i, e in enumerate(out):
            if e.start >= still[0]:
                del out[i]
                dropped = 1
                break
    return out, dropped


def _absent(metric: str, why: str) -> None:
    say(event="layer_metric_absent", metric=metric, why=why)


def reads(run, metric: str):
    """The window's reads and how many were dropped at the trace's
    stop — or None, said why."""
    tracer = span_reader.tracer_of(run, metric)
    if tracer is None:
        return None
    out, dropped = reads_of(tracer, run.drive.start, run.drive.end,
                            standstill(run.drive))
    if not out:
        _absent(metric, f"no {READ} span with a read's record "
                        f"(`interval_ms`) in the window")
        return None
    return out, dropped


def enqueues(run, metric: str):
    tracer = span_reader.tracer_of(run, metric)
    if tracer is None:
        return None
    out, dropped = enqueues_of(tracer, run.drive.start, run.drive.end,
                               standstill(run.drive))
    if not out:
        _absent(metric, f"no {FRONT} / {DISPATCH} span that says "
                        f"`starved` in the window")
        return None
    return out, dropped


def count_by(spans, key: str) -> Dict[str, int]:
    """How many of ``spans`` carry each value of the attribute."""
    out: Dict[str, int] = {}
    for s in spans:
        k = str(s.attrs.get(key))
        out[k] = out.get(k, 0) + 1
    return dict(sorted(out.items()))


# -- arithmetic on reads ----------------------------------------------------

def weighted_percentile(values, weights, q: float) -> Optional[float]:
    """The smallest value at or below which ``q`` percent of the
    weight lies; None where there is no weight."""
    rows = sorted((v, w) for v, w in zip(values, weights) if w > 0)
    total = sum(w for _, w in rows)
    if not total:
        return None
    need, seen = total * q / 100.0, 0.0
    for v, w in rows:
        seen += w
        if seen >= need:
            return v
    return rows[-1][0]


def admit_gap_share(rs: List[Read]) -> Optional[float]:
    """Percent of the token gaps that hold an admission."""
    gaps = sum(r.gaps for r in rs)
    if not gaps:
        return None
    return 100.0 * sum(r.gaps for r in rs if r.prefills) / gaps


def plain_ms(rs: List[Read]) -> Optional[float]:
    return stats.percentile(
        [r.interval_ms for r in rs if not r.prefills and r.gaps], 50)


def admit_gap_ms(rs: List[Read]) -> Optional[float]:
    """What an admission adds to the gap of the rows that are running:
    the median interval of the reads behind a prefill that committed a
    running row, less the median plain interval."""
    held = [r.interval_ms for r in rs if r.prefills and r.gaps]
    plain = plain_ms(rs)
    if not held or plain is None:
        return None
    return stats.percentile(held, 50) - plain


def composition(rs: List[Read], pairs: bool = False) -> dict:
    """The gap's composition: percentiles of `interval_ms` weighted by
    the rows that saw it, and the percentile at which the gaps that
    hold an admission begin.  ``pairs``: also over sums of consecutive
    reads (a model whose pass yields several tokens a row, or none)."""
    share = admit_gap_share(rs)
    out = {
        "reads": len(rs), "gaps": sum(r.gaps for r in rs),
        "reads_with_prefills": sum(bool(r.prefills) for r in rs),
        "admission_begins_at_percentile":
            None if share is None else 100.0 - share,
        "plain_ms_p50": plain_ms(rs)}
    for q in (50, 95, 99):
        out[f"interval_ms_p{q}"] = weighted_percentile(
            [r.interval_ms for r in rs], [r.gaps for r in rs], q)
    if pairs:
        two = [(x.interval_ms + y.interval_ms, y.gaps)
               for x, y in zip(rs, rs[1:])]
        for q in (50, 95, 99):
            out[f"pair_ms_p{q}"] = weighted_percentile(
                [v for v, _ in two], [w for _, w in two], q)
    return out


# -- who held the host ------------------------------------------------------

def held_by(tracer, a: float, b: float,
            es: List[Enqueue]) -> Dict[int, Tuple[str, float]]:
    """For each starved enqueue of ``es`` (all the window's, in time
    order; by the id of its span): the host work that took longest
    between the start of the enqueue before it — the chip had work
    from there — and its own end, as (name, ms): a span's SELF time
    (its duration less its children's; never a `serving.sync`, which
    waits for the device), or the time between two calls of `step()`
    (`harness`).  A first half that ran after the read says so:
    `...[read]`."""
    off = tracer.monotonic_offset
    spans = [s for s in tracer.finished()
             if s.name != REQUEST and a - 1.0 <= s.t0 + off < b]
    kids: Dict[int, float] = {}
    for s in spans:
        if s.parent is not None:
            kids[s.parent] = kids.get(s.parent, 0.0) + s.dur
    work = [(s.t0 + off, s.t0 + off + s.dur,
             s.dur - kids.get(s.id, 0.0),
             f"{s.name}[{s.attrs['flight']}]"
             if s.name == FRONT and "flight" in s.attrs else s.name)
            for s in spans if s.name != READ]
    calls = sorted((t0, t1) for t0, t1, _, name in work if name == STEP)
    work += [(x[1], y[0], y[0] - x[1], HARNESS)
             for x, y in zip(calls, calls[1:])]
    out = {}
    for before, e in zip([None] + es, es):
        if not e.starved or e.idle:
            continue
        since = a if before is None else before.start
        best = max(((self_s, name) for t0, t1, self_s, name in work
                    if t1 > since and t0 < e.end), default=None)
        if best is not None:
            out[e.span.id] = (best[1], best[0] * 1e3)
    return out
