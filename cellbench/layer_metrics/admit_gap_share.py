"""Scheduler: the share of the window's token gaps that hold an
admission — of the reads' `rows` less their `first_tokens` (the rows
that saw a gap), the share committed by a read with `prefills` >= 1
(`gap_spans`).  Read it against 5%: under it `itl_p95_ms` is the plain
step, over it a step plus a prefill.  Prints `gap_composition`: the
gap-weighted percentiles of the program's own commit-to-commit
interval (the 95th IS its `itl_p95_ms` where a step yields one token a
row) and the percentile at which the admission-carrying gaps begin.
What a read's record cannot see is said beside it: a commit that
retires a row delivers the rows behind it in its loop that much later
(`retiring_commit_ms_p50`, from the `serving.commit` spans) — gaps the
harness counts as long and the program does not."""

from cellbench import gap_spans, span_reader, stats
from cellbench.clock import say


def read(run):
    got = gap_spans.reads(run, "admit_gap_share")
    if got is None:
        return None
    rs, dropped = got
    blocks = run.spec.config.get("generation", {}).get("block_length", 0)
    retiring = [s.dur * 1e3 for s in span_reader.spans_in(
        run.system.sched.tracer, run.drive.start, run.drive.end,
        "serving.commit") if s.attrs.get("retired")]
    say(event="gap_composition", dropped_at_trace_stop=dropped,
        commits_retiring=len(retiring),
        retiring_commit_ms_p50=stats.percentile(retiring, 50),
        **gap_spans.composition(rs, pairs=blocks > 1))
    return gap_spans.admit_gap_share(rs)
