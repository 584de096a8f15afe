"""What the program said of its two kinds of attention state, step by
step: the attrs of its `serving.window` spans (`window_pages_live`,
`full_pages_live`, `window_pages_released`, `window_tokens_live`,
`full_tokens_live`; `docs/observability.md`), cut to the run's window
or to its traced steps.  None — and why, on stdout — where the program
left none (a program without the span, a model without window layers,
`TDT_OBSERVABILITY=0`, a ring that dropped spans)."""

from __future__ import annotations

from typing import List, Optional

from cellbench import span_reader
from cellbench.clock import say

WINDOW = "serving.window"
FRONT = "serving.admit.prefill"


def _bounds(run, metric: str, traced: bool):
    if not traced:
        return run.drive.start, run.drive.end
    if run.drive.trace_span is None:
        say(event="layer_metric_absent", metric=metric,
            why="no traced steps (--trace 0)")
        return None
    return run.drive.trace_span


def counted(run, metric: str, traced: bool = False) -> Optional[List[dict]]:
    tracer = span_reader.tracer_of(run, metric)
    bounds = _bounds(run, metric, traced) if tracer is not None else None
    if bounds is None:
        return None
    out = [s.attrs for s in span_reader.spans_in(tracer, *bounds, WINDOW)
           if "window_tokens_live" in s.attrs]
    if not out:
        say(event="layer_metric_absent", metric=metric,
            why=f"no {WINDOW} span with counters in the "
                f"{'traced steps' if traced else 'window'}")
        return None
    return out


def pieces(run, metric: str) -> Optional[List[tuple]]:
    """(start, tokens) of each prefill program ENQUEUED in the traced
    steps: the program's own `serving.admit.prefill` spans — the
    programs' tokens, not the admissions'."""
    tracer = span_reader.tracer_of(run, metric)
    bounds = _bounds(run, metric, True) if tracer is not None else None
    if bounds is None:
        return None
    out = [(int(s.attrs["start"]), int(s.attrs["tokens"]))
           for s in span_reader.spans_in(tracer, *bounds, FRONT)
           if "start" in s.attrs and "tokens" in s.attrs]
    if not out:
        say(event="layer_metric_absent", metric=metric,
            why=f"no {FRONT} span that says where its piece starts in "
                f"the traced steps")
        return None
    return out


def mean(rows: List[dict], key: str) -> float:
    return sum(r[key] for r in rows) / len(rows)
