"""What the program's expert layers counted, step by step: the attrs of
its `serving.moe` spans (`pairs`, `experts_hit`, `expert_load_max`;
`docs/observability.md`), cut to the run's window or to its traced
steps.  None — and why, on stdout — where the program left none (a
program without the span, `TDT_OBSERVABILITY=0`, a ring that dropped
spans)."""

from __future__ import annotations

from typing import List, Optional

from cellbench import span_reader
from cellbench.clock import say

MOE = "serving.moe"


def counted(run, metric: str, traced: bool = False) -> Optional[List[dict]]:
    tracer = span_reader.tracer_of(run, metric)
    if tracer is None:
        return None
    if traced:
        if run.drive.trace_span is None:
            say(event="layer_metric_absent", metric=metric,
                why="no traced steps (--trace 0)")
            return None
        a, b = run.drive.trace_span
    else:
        a, b = run.drive.start, run.drive.end
    out = [s.attrs for s in span_reader.spans_in(tracer, a, b, MOE)
           if "experts_hit" in s.attrs]
    if not out:
        say(event="layer_metric_absent", metric=metric,
            why=f"no {MOE} span with counters in the "
                f"{'traced steps' if traced else 'window'}")
        return None
    return out


def mean(rows: List[dict], key: str) -> float:
    return sum(r[key] for r in rows) / len(rows)
