#!/usr/bin/env python
"""What an admission costs the rows that are running, from one run's
spans: the scheduler's own record of every read (`serving.sync`:
`interval_ms`, `rows`, `prefills`, `early`, `landed`) and of every
enqueue (`starved`, `flight`), through the helper the benchmark's
readers use (`cellbench/gap_spans.py`; PERF.md section 3).

An admission should lengthen ONE commit-to-commit interval, the one
that holds its prefill; host work that also delivers the step in
flight late shows as early reads whose tokens had `landed` and whose
interval stands over the plain one.  Printed, as one JSON line: the
gap's composition, what an admission adds to a gap, the halves' host
time, where the read of the step in flight stood (`flight`: the window
and the whole ring, beside `serving_admit_overlapped_total`), the
starved enqueues, and for every admitting call how many reads of that
call and the three after it stood behind a prefill.  With a device
trace (`--trace 1`) also `starved_vs_idle` (`scripts/starved_vs_idle.py`).

    # a Chrome trace written by `SpanTracer.export_chrome_trace`
    python scripts/admission_gaps.py trace-rank-0.json
    # one benchmark run through the harness's own `main`, read after it
    python scripts/admission_gaps.py --cell --keep chiprun_out/x.json \\
        -- --workload qwen3-8b-tp4.batch-closed --seed 7 --seconds 40
"""

from __future__ import annotations

import argparse
import bisect
import collections
import json
import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from cellbench import gap_spans, span_reader, stats   # noqa: E402

INSERT = "serving.admit.request"


def tracer_of(trace: dict):
    """A Chrome trace's complete events as the tracer the readers take
    (its clock is the trace's own)."""
    spans = [types.SimpleNamespace(
        name=e["name"], t0=e["ts"] / 1e6, dur=e["dur"] / 1e6,
        attrs=e.get("args", {}), id=e.get("args", {}).get("id"),
        parent=e.get("args", {}).get("parent"))
        for e in trace["traceEvents"] if e.get("ph") == "X"]
    return types.SimpleNamespace(finished=lambda: spans,
                                 monotonic_offset=0.0, dropped=0)


def summarize(tracer, a=float("-inf"), b=float("inf"), still=None) -> dict:
    """The window [a, b) on `time.monotonic`; ``still``: the profiler's
    standstill (`gap_spans.standstill`)."""
    reads, _ = gap_spans.reads_of(tracer, a, b, still)
    enq, _ = gap_spans.enqueues_of(tracer, a, b, still)
    fronts = span_reader.spans_in(tracer, a, b, gap_spans.FRONT)
    inserts = span_reader.spans_in(tracer, a, b, INSERT)
    steps = sorted(span_reader.spans_in(tracer, a, b, gap_spans.STEP),
                   key=lambda s: s.t0)
    nth = {s.id: i for i, s in enumerate(steps)}
    held = collections.Counter(nth[r.step] for r in reads
                               if r.prefills and r.step in nth)
    starts = [s.t0 for s in steps]
    calls = sorted({bisect.bisect_right(starts, f.t0) - 1 for f in fronts})
    early = [r for r in reads if r.early]
    busy = [e for e in enq if not e.idle]
    return dict(
        gap_spans.composition(reads), admissions=len(fronts),
        admit_gap_ms=gap_spans.admit_gap_ms(reads),
        admit_gap_share=gap_spans.admit_gap_share(reads),
        front_ms_p50=stats.percentile([s.dur * 1e3 for s in fronts], 50),
        insert_ms_p50=stats.percentile([s.dur * 1e3 for s in inserts], 50),
        flight=gap_spans.count_by(fronts, "flight"),
        early_reads=len(early),
        early_reads_late=sum(r.landed for r in early),
        early_interval_ms_p50=stats.percentile(
            [r.interval_ms for r in early], 50),
        enqueues=len(busy), idle_enqueues=len(enq) - len(busy),
        starved=dict(collections.Counter(
            e.program for e in busy if e.starved)),
        reads_behind_prefill_per_admitting_call=dict(sorted(
            collections.Counter(
                str(sum(held[i + k] for k in range(4)))
                for i in calls if i >= 0).items())))


def counters() -> dict:
    """The process's admission and read counters."""
    from triton_distributed_tpu.observability import get_registry
    snap = get_registry().snapshot()["counters"]
    return {k: v for k, v in sorted(snap.items()) if k.split("{")[0] in (
        "serving_prefills_total", "serving_prefill_chunks_total",
        "serving_admit_overlapped_total",
        "serving_prefill_unobserved_total", "serving_reads_total",
        "serving_read_rows_total", "serving_read_late_total",
        "serving_enqueues_total", "serving_enqueue_starved_total",
        "serving_decode_dispatch_total",
        "serving_decode_overlapped_total")}


def run_cell(argv, keep) -> int:
    """One benchmark run through `cellbench.run.main`; its spans are
    read once it is over, cut to its window."""
    from cellbench import run, trace_reduce
    from triton_distributed_tpu.observability import get_tracer
    seen = {}
    drive, reduce_planes = run.drive, trace_reduce.reduce_planes

    def driving(*args, **kw):
        seen["drive"] = d = drive(*args, **kw)
        return d

    def reducing(planes, *args, **kw):
        seen["planes"] = planes
        return reduce_planes(planes, *args, **kw)
    run.drive, trace_reduce.reduce_planes = driving, reducing
    sys.argv = [os.path.join(REPO, "cellbench", "run.py")] + argv
    try:
        rc = run.main()
    finally:
        run.drive, trace_reduce.reduce_planes = drive, reduce_planes
        tracer = get_tracer()
        if keep:
            tracer.export_chrome_trace(keep)
        d = seen.get("drive")
        out = dict(event="admission_gaps", dropped=tracer.dropped,
                   process=counters(), flight_of_the_ring=gap_spans.count_by(
                       [s for s in tracer.finished()
                        if s.name == gap_spans.FRONT], "flight"))
        if d is not None:
            out["window"] = summarize(tracer, d.start, d.end,
                                      gap_spans.standstill(d))
        if "planes" in seen:
            from starved_vs_idle import compare
            out["starved_vs_idle"] = compare(tracer, d, seen["planes"])
        print(json.dumps(out), flush=True)
    return rc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="?", help="a Chrome trace file")
    ap.add_argument("--cell", action="store_true",
                    help="run cellbench/run.py with the arguments "
                         "after `--` and read its spans")
    ap.add_argument("--keep", help="with --cell: write the spans here")
    argv = sys.argv[1:]
    cut = argv.index("--") if "--" in argv else len(argv)
    args = ap.parse_args(argv[:cut])
    if args.cell:
        return run_cell(argv[cut + 1:], args.keep)
    if not args.trace:
        ap.error("a trace file, or --cell")
    with open(args.trace) as f:
        trace = json.load(f)
    print(json.dumps(dict(event="admission_gaps",
                          all=summarize(tracer_of(trace)))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
