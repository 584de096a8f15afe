"""Scheduler: host time of an admission's FIRST half — prefix match,
pad, the prefill's enqueue: the half the chip waits for where it does
not fit into the step in flight — the median `serving.admit.prefill`
of the window.  Prints `admit_front`: how many were enqueued behind
the step in flight, after its read or with none (`flight`, what
`serving_admit_overlapped_total` counts), how many found the chip idle
(`starved`), the early reads and how many of them came late
(`landed`), and the queue wait."""

from cellbench import gap_spans, span_reader, stats
from cellbench.clock import say


def read(run):
    tracer = span_reader.tracer_of(run, "admit_front_ms")
    if tracer is None:
        return None
    a, b = run.drive.start, run.drive.end
    fronts = span_reader.spans_in(tracer, a, b, gap_spans.FRONT)
    if not fronts:
        say(event="layer_metric_absent", metric="admit_front_ms",
            why=f"no {gap_spans.FRONT} in the window")
        return None

    rs, _ = gap_spans.reads_of(tracer, a, b,
                               gap_spans.standstill(run.drive))
    early = [r for r in rs if r.early]
    waits = [s.attrs["queue_wait_ms"] for s in fronts
             if "queue_wait_ms" in s.attrs]
    ms = [s.dur * 1e3 for s in fronts]
    say(event="admit_front", admissions=len(fronts),
        front_ms_p50=stats.percentile(ms, 50), front_ms_max=max(ms),
        flight=gap_spans.count_by(fronts, "flight"),
        starved=gap_spans.count_by(fronts, "starved"),
        idle=sum(bool(s.attrs.get("idle")) for s in fronts),
        early_reads=len(early),
        early_reads_late=sum(r.landed for r in early),
        reads_late=sum(r.landed for r in rs),
        queue_wait_ms_p50=stats.percentile(waits, 50),
        queue_wait_ms_p95=stats.percentile(waits, 95))
    return stats.percentile(ms, 50)
