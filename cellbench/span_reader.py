"""From the program's span ring to what a step's host time went on.

The program keeps a bounded ring of finished spans, each with an `id`,
the `parent` that caused it, its start on `time.perf_counter`'s clock
(`t0`) and its duration; its tracer gives the one offset from that
clock to `time.monotonic`'s, the clock `run.drive` reads.  A
`serving.step` span wraps one scheduler iteration and its children
are the step's phases (`PHASES`); what no child covers is the step's
self time.

Nothing here imports the program: the tracer is reached through the
adapter's object (`run.system.sched.tracer`).  Where the program has
no such tracer (an older program, or `TDT_OBSERVABILITY=0`), or its
ring has dropped spans — a window cut short must not be read as a
whole one — `steps_of` returns None and says so on stdout; the
readers built on it then return None too.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from cellbench import stats
from cellbench.clock import say

STEP = "serving.step"
SYNC = "serving.sync"
PAGES = "serving.pages"
ADMIT_ONE = "serving.admit.request"
PREFILL_BLOCK = "serving.prefill.block"
#: The children of a `serving.step`, in the order they run.
PHASES = ("serving.admit", PAGES, "serving.dispatch", SYNC,
          "serving.commit", "serving.gauges")


@dataclasses.dataclass
class Step:
    start: float                 # on time.monotonic's clock
    dur: float                   # seconds
    attrs: dict
    phases: Dict[str, float]     # child span name -> seconds
    phase_attrs: Dict[str, dict]

    @property
    def host_s(self) -> float:
        """The step less the wait for the device."""
        return self.dur - self.phases.get(SYNC, 0.0)

    @property
    def self_s(self) -> float:
        return self.dur - sum(self.phases.values())

    def split_ms(self) -> dict:
        out = {k.split(".", 1)[1]: v * 1e3 for k, v in self.phases.items()}
        out["self"] = self.self_s * 1e3
        return out


def tracer_of(run, metric: str):
    """The program's tracer, or None (and why, on stdout)."""
    sched = getattr(run.system, "sched", None)
    tracer = getattr(sched, "tracer", None)
    if tracer is None or not hasattr(tracer, "monotonic_offset"):
        say(event="layer_metric_absent", metric=metric,
            why="the program exposes no span tracer")
        return None
    dropped = getattr(tracer, "dropped", 0)
    if dropped:
        say(event="layer_metric_absent", metric=metric,
            why=f"the span ring dropped {dropped} spans during the run: "
                f"a truncated window is not read")
        return None
    return tracer


def spans_in(tracer, a: float, b: float, name: str) -> list:
    """Finished spans called ``name`` that started in [a, b) on
    `time.monotonic`'s clock."""
    off = tracer.monotonic_offset
    return [s for s in tracer.finished()
            if s.name == name and a <= s.t0 + off < b]


def steps_of(run, metric: str, a: Optional[float] = None,
             b: Optional[float] = None) -> Optional[List[Step]]:
    """The scheduler steps that DISPATCHED (they have a `serving.sync`)
    and started in [a, b) — the run's window unless given — each with
    its phases; None where the program left no spans to read."""
    tracer = tracer_of(run, metric)
    if tracer is None:
        return None
    if a is None:
        a, b = run.drive.start, run.drive.end
    off = tracer.monotonic_offset
    spans = tracer.finished()
    steps = {s.id: Step(s.t0 + off, s.dur, s.attrs, {}, {})
             for s in spans
             if s.name == STEP and a <= s.t0 + off < b}
    for s in spans:
        st = steps.get(s.parent)
        if st is not None and s.name in PHASES:
            st.phases[s.name] = st.phases.get(s.name, 0.0) + s.dur
            st.phase_attrs[s.name] = s.attrs
    out = [st for st in steps.values() if SYNC in st.phases]
    if not out:
        say(event="layer_metric_absent", metric=metric,
            why="no serving.step span with a serving.sync in the window")
        return None
    return sorted(out, key=lambda st: st.start)


def phase_report(steps: List[Step], longest: int = 3) -> dict:
    """Median milliseconds of each phase over ``steps`` and the
    longest steps with their own split: what the host did in a step,
    and which phase held the steps that ran long."""
    names = [p.split(".", 1)[1] for p in PHASES] + ["self"]
    splits = [st.split_ms() for st in steps]
    t0 = steps[0].start
    top = sorted(steps, key=lambda st: -st.dur)[:longest]
    return {
        "steps": len(steps),
        "step_ms_p50": stats.percentile([st.dur for st in steps], 50) * 1e3,
        "host_ms_p50": stats.percentile(
            [st.host_s for st in steps], 50) * 1e3,
        "phase_ms_p50": {n: stats.percentile(
            [sp.get(n, 0.0) for sp in splits], 50) for n in names},
        "phase_ms_mean": {n: sum(sp.get(n, 0.0) for sp in splits)
                          / len(splits) for n in names},
        "longest": [{"at_s": st.start - t0, "step_ms": st.dur * 1e3,
                     "step": st.attrs.get("step"),
                     "admitted": st.attrs.get("admitted"),
                     "phases_ms": st.split_ms()} for st in top],
    }


def device_ms_per_decode_step(run, metric: str, prefixes) -> Optional[float]:
    """Device milliseconds a traced decode step spends in operations
    whose reduced name starts with one of ``prefixes``: their rows of
    `trace.per_op` (every operation of the busiest chip, not only the
    ten the breakdown prints: a kernel stays read once it is fast)
    summed, over the decode program's events on the same chip."""
    if run.trace is None:
        say(event="layer_metric_absent", metric=metric,
            why="no device trace (--trace 0, or a rehearsal)")
        return None
    rows = sorted(((n, s) for n, s in run.trace.per_op.items()
                   if n.startswith(tuple(prefixes))),
                  key=lambda row: -row[1])
    n_steps = len(run.module("decode"))
    if not rows or not n_steps:
        say(event="layer_metric_absent", metric=metric,
            why=f"no operation named {'|'.join(prefixes)}* among the "
                f"{len(run.trace.per_op)} device operations",
            top_ops=[n for n, _ in run.trace.top_ops])
        return None
    say(event="layer_metric_rows", metric=metric, decode_steps=n_steps,
        rows=[[n, s] for n, s in rows])
    return sum(s for _, s in rows) / n_steps * 1e3
