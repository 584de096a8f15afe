"""KV: peak share of the state pool's slots that hold a live sequence —
`live_slots` of the program's `serving.state` spans (the rows whose
recurrent state a decode step updated) over the slots, the largest
over the window's steps.  A slot's state is held whole from its insert
to its release whatever the sequence's length.

`counted` hands the span's attributes to the other readers of this
family (`kda_decode_roofline`, `hybrid_decode_step_roofline`)."""

from cellbench import span_reader
from cellbench.clock import say

STATE = "serving.state"


def counted(run, metric: str, traced: bool = False):
    """The attrs of the `serving.state` spans in the window (or the
    traced steps); None — and why, on stdout — where there are none (a
    program without the span, a model without a recurrent state)."""
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
    out = [s.attrs for s in span_reader.spans_in(tracer, a, b, STATE)
           if "live_slots" in s.attrs]
    if not out:
        say(event="layer_metric_absent", metric=metric,
            why=f"no {STATE} span with counters in the "
                f"{'traced steps' if traced else 'window'}")
        return None
    return out


def read(run):
    rows = counted(run, "state_pool_live_peak")
    if rows is None:
        return None
    return (100.0 * max(r["live_slots"] for r in rows)
            / run.system.num_slots)
