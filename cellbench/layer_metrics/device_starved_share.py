"""Scheduler: the share of the window's enqueues — of a prefill or a
decode step — that found the chip standing idle: the program enqueued
last had finished when the host came with the next (`starved`; the
first enqueue of a server that had nothing to do, `idle`, is left out
of both counts: it waited for arrivals).  The idle share's attribution
from inside the program, over the whole window.  Prints `starved_by`:
the starved enqueues by program and by the host work that took longest
between the enqueue before them and their own end (a span's self time,
or `harness`: the time between two calls of `step()`)."""

from cellbench import gap_spans
from cellbench.clock import say


def read(run):
    got = gap_spans.enqueues(run, "device_starved_share")
    if got is None:
        return None
    es, dropped = got
    busy = [e for e in es if not e.idle]
    if not busy:
        say(event="layer_metric_absent", metric="device_starved_share",
            why="every enqueue of the window found an idle server")
        return None
    starved = [e for e in busy if e.starved]
    held = gap_spans.held_by(run.system.sched.tracer, run.drive.start,
                             run.drive.end, es)
    by = {}
    for e in starved:
        name, ms = held.get(e.span.id, ("unknown", 0.0))
        row = by.setdefault(f"{e.program} after {name}", [0, 0.0])
        row[0] += 1
        row[1] += ms
    say(event="starved_by", enqueues=len(busy), starved=len(starved),
        idle=len(es) - len(busy), dropped_at_trace_stop=dropped,
        share_with_idle=100.0 * (len(starved) + len(es) - len(busy))
        / len(es),
        by={k: {"n": n, "held_ms_mean": ms / n}
            for k, (n, ms) in sorted(by.items(), key=lambda kv: -kv[1][0])})
    return 100.0 * len(starved) / len(busy)
