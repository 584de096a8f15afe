"""What the program's block passes were, pass by pass: the attrs of its
`serving.diffusion` spans (`rows_denoise`, `rows_commit`,
`positions_fed`, `tokens_revealed`, `tokens_delivered`,
`blocks_committed`; `docs/observability.md`), cut to the run's window
or to its traced steps.  None — and why, on stdout — where the program
left none (a program without the span: one that does not generate by
blocks, or the parent of the PR that added it)."""

from __future__ import annotations

from typing import List, Optional

from cellbench import span_reader
from cellbench.clock import say

DIFFUSION = "serving.diffusion"


def passes(run, metric: str, traced: bool = False) -> Optional[List[dict]]:
    tracer = span_reader.tracer_of(run, metric)
    if tracer is None:
        return None
    if traced and run.drive.trace_span is None:
        say(event="layer_metric_absent", metric=metric,
            why="no traced steps (--trace 0)")
        return None
    a, b = (run.drive.trace_span if traced
            else (run.drive.start, run.drive.end))
    out = [s.attrs for s in span_reader.spans_in(tracer, a, b, DIFFUSION)
           if "positions_fed" in s.attrs]
    if not out:
        say(event="layer_metric_absent", metric=metric,
            why=f"no {DIFFUSION} span in the "
                f"{'traced steps' if traced else 'window'}")
        return None
    return out
