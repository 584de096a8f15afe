"""The program's starvation beside the trace's idleness: on the traced
steps of one `--trace 1` run, the enqueues the scheduler called
`starved` (the program enqueued last had finished: `serving.dispatch`,
`serving.admit.prefill`) against the idle gaps of the busiest chip in
the profiler's own trace.  `scripts/admission_gaps.py --cell` prints
it (`starved_vs_idle`) whenever its run made a trace:

    python scripts/admission_gaps.py --cell -- --workload <cell> \\
        --seed <n> --seconds 40 --trace 1
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from cellbench import gap_spans, span_reader          # noqa: E402
from cellbench import trace_reduce as tr              # noqa: E402


def compare(tracer, drive, planes, over_s=100e-6) -> dict:
    """On the traced steps alone: the idle gaps over ``over_s`` of the
    busiest chip — each with the program's span that was running when
    the gap ENDED (the enqueue that ended it) — beside the starved
    enqueues.  The clocks are tied by the trace's first `cellbench.step`
    annotation and `serving.step` span; gap and enqueue MEET when the
    gap ends during the enqueue or up to 2 ms after it.  An
    enqueue before the trace's first device operation is left out: the
    trace cannot show the gap in front of it."""
    a, b = drive.trace_span
    off = tracer.monotonic_offset
    dev = {n: ls["XLA Ops"] for n, ls in planes.items()
           if n.startswith(tr.DEVICE_PLANE)}
    ops = max(dev.values(), key=lambda o: tr.union_seconds(
        [(s, s + d) for _, s, d in o]))
    merged = tr.merge_intervals([(s, s + d) for _, s, d in ops])
    calls = min(s for n, ls in planes.items() if n not in dev
                for evs in ls.values() for name, s, _ in evs
                if name == "cellbench.step")
    steps = min(s.t0 for s in span_reader.spans_in(
        tracer, a, b, gap_spans.STEP))
    shift = steps + off - calls
    gaps = [(g0 + shift, g1 + shift) for (_, g0), (g1, _)
            in zip(merged, merged[1:]) if g1 - g0 > over_s]
    enq, _ = gap_spans.enqueues_of(tracer, a, b)
    enq = [e for e in enq if e.end > merged[0][0] + shift]
    starved = [e for e in enq if e.starved and not e.idle]
    spans = [s for s in tracer.finished() if s.name != gap_spans.REQUEST
             and a <= s.t0 + off < b]

    def during(t):
        inside = [s for s in spans if s.t0 + off <= t <= s.t0 + off + s.dur]
        return min(inside, key=lambda s: s.dur).name if inside else None

    def meet(e, g):
        return e.start <= g[1] <= e.end + 2e-3
    return {"enqueues": len(enq), "idle_enqueues": sum(e.idle for e in enq),
            "starved": [[e.program, round(e.start - a, 4),
                         any(meet(e, g) for g in gaps)] for e in starved],
            "idle_gaps": [[round(g[0] - a, 4), round((g[1] - g[0]) * 1e3, 3),
                           during(g[1]), any(meet(e, g) for e in starved)]
                          for g in gaps]}
