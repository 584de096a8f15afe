"""Scheduler: what an admission adds to the token gap of the rows that
are running — the median `interval_ms` of the window's reads that stood
behind one or more prefills and committed a running row, less the
median of the plain reads (`gap_spans`): the prefills' device time, the
insert and whatever of the admission's host work the chip waited for.
Chunked prefill is to shrink it."""

from cellbench import gap_spans, stats
from cellbench.clock import say


def read(run):
    got = gap_spans.reads(run, "admit_gap_ms")
    if got is None:
        return None
    rs, dropped = got
    held = [r for r in rs if r.prefills and r.gaps]
    value = gap_spans.admit_gap_ms(rs)
    if value is None:
        say(event="layer_metric_absent", metric="admit_gap_ms",
            why="no read behind a prefill that committed a running row "
                "(or no plain read) in the window")
        return None
    by_n = {}
    for r in held:
        by_n.setdefault("1" if r.prefills == 1 else "2+", []).append(r)
    say(event="admit_gaps", reads=len(held),
        plain_ms_p50=gap_spans.plain_ms(rs),
        dropped_at_trace_stop=dropped,
        by_prefills={k: {
            "reads": len(v),
            "prefill_tokens_mean": sum(r.prefill_tokens for r in v)
            / len(v),
            "interval_ms_p50": stats.percentile(
                [r.interval_ms for r in v], 50)}
            for k, v in sorted(by_n.items())})
    return value
