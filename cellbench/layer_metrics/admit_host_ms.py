"""Scheduler: host time of one admission — prefix match, prefill
dispatch, the wait on the prefill that feeds `serving_prefill_ms`,
insert dispatch — the median `serving.admit.request` of the window.
How much of it is that wait (`serving.prefill.block`) goes to stdout."""

from cellbench import span_reader, stats


def read(run):
    tracer = span_reader.tracer_of(run, "admit_host_ms")
    if tracer is None:
        return None
    a, b = run.drive.start, run.drive.end
    ones = span_reader.spans_in(tracer, a, b, span_reader.ADMIT_ONE)
    if not ones:
        span_reader.say(event="layer_metric_absent",
                        metric="admit_host_ms",
                        why="no serving.admit.request in the window")
        return None
    blocks = span_reader.spans_in(tracer, a, b,
                                  span_reader.PREFILL_BLOCK)
    ms = [s.dur * 1e3 for s in ones]
    span_reader.say(
        event="admit_phases", admissions=len(ones),
        admit_ms_p50=stats.percentile(ms, 50),
        admit_ms_max=max(ms),
        prefill_block_ms_p50=stats.percentile(
            [s.dur * 1e3 for s in blocks], 50),
        prefill_block_ms_sum=sum(s.dur for s in blocks) * 1e3,
        cached_tokens=sum(s.attrs.get("cached_tokens", 0)
                          for s in ones))
    return stats.percentile(ms, 50)
