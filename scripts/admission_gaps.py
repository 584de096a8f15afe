#!/usr/bin/env python
"""What an admission costs the rows that are running: from one run's
spans, the commit-to-commit intervals around every admission.

A running row gets a token at every `serving.commit`; the interval
between two commits is the token gap every row of the batch sees.  An
admission should lengthen ONE of them (the one that holds its prefill);
an order of host work that also delivers the tokens of the step in
flight late lengthens two, and where a cell admits in a few percent of
its steps that moves `itl_p95_ms` from the plain step into the late
ones (PERF.md section 6, PR 37 / PR 38).  Printed, as one JSON line:
the median interval, and for every admission how many of the intervals
from its own call to ``--after`` calls later exceed ``--factor`` x the
median — as a histogram over the admissions — beside the host time of
an admission's two halves and where the read of the step in flight was
placed.

Usage::

    # a Chrome trace written by `SpanTracer.export_chrome_trace`
    # (`TDT_TRACE_DIR`, `scripts/launch.py --trace-dir`)
    python scripts/admission_gaps.py trace-rank-0.json

    # one benchmark run, through the harness's own `main`, read after
    # it (nothing is added to the window); the spans are kept beside
    # the run's other output with --keep
    python scripts/admission_gaps.py --cell --keep chiprun_out/x.json \\
        -- --workload qwen3-8b-tp4.batch-closed --seed 7 --seconds 40
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COMMIT = "serving.commit"
FRONT = "serving.admit.prefill"
INSERT = "serving.admit.request"
STEP = "serving.step"


def spans_of(trace: dict) -> list:
    """(name, start s, duration s, attributes) of a Chrome trace's
    complete events."""
    return [(e["name"], e["ts"] / 1e6, e["dur"] / 1e6, e.get("args", {}))
            for e in trace["traceEvents"] if e.get("ph") == "X"]


def median_ms(values) -> float | None:
    return statistics.median(values) * 1e3 if values else None


def summarize(spans: list, window=None, factor: float = 1.25,
              after: int = 3) -> dict:
    """``spans`` as `spans_of` gives them; ``window``: (start, end) on
    the spans' clock, or None for all of them."""
    if window is not None:
        a, b = window
        spans = [s for s in spans if a <= s[1] < b]
    commits = sorted(s[1] for s in spans if s[0] == COMMIT)
    steps = sorted((s[1], s[1] + s[2]) for s in spans if s[0] == STEP)
    inserts = sorted((s for s in spans if s[0] == INSERT),
                     key=lambda s: s[1])
    fronts = [s for s in spans if s[0] == FRONT]
    out = {"commits": len(commits), "admissions": len(inserts)}
    if len(commits) < 3:
        return out
    gaps = [(t1, t1 - t0) for t0, t1 in zip(commits, commits[1:])]
    median = statistics.median(g for _, g in gaps)
    long = [t for t, g in gaps if g > factor * median]
    # an admitting call: the step span that holds the admission(s);
    # its intervals: those ending from its start to `after` commits
    # past its end
    starts = [a for a, _ in steps]
    calls = sorted({steps[i] for i in (bisect.bisect_right(starts, s[1]) - 1
                                       for s in inserts)
                    if i >= 0})
    per_call = []
    for a, b in calls:
        later = [t for t in commits if t > b][:after]
        edge = later[-1] if later else b
        per_call.append(sum(a <= t <= edge for t in long))
    hist = {}
    for n in per_call:
        hist[str(n)] = hist.get(str(n), 0) + 1
    out.update(
        median_ms=median * 1e3, factor=factor,
        long_intervals=len(long),
        long_share=len(long) / len(gaps),
        admitting_calls=len(calls),
        long_per_admitting_call=dict(sorted(hist.items())),
        calls_with_more_than_one=sum(n > 1 for n in per_call),
        front_ms_p50=median_ms([s[2] for s in fronts]),
        insert_ms_p50=median_ms([s[2] for s in inserts]),
        prefill_behind_flight=sum(
            bool(s[3].get("behind_flight")) for s in fronts),
        read_flight=sum(bool(s[3].get("read_flight")) for s in inserts))
    return out


def counters() -> dict:
    """The process's admission counters, from the registry."""
    from triton_distributed_tpu.observability import get_registry
    snap = get_registry().snapshot()

    def total(name):
        return sum(v for k, v in snap["counters"].items()
                   if k.split("{")[0] == name)

    hists = snap["histograms"]
    return {
        "serving_prefills_total": total("serving_prefills_total"),
        "serving_admit_overlapped_total": {
            k.split("{")[1].rstrip("}"): v
            for k, v in snap["counters"].items()
            if k.startswith("serving_admit_overlapped_total{")},
        "serving_prefill_unobserved_total": total(
            "serving_prefill_unobserved_total"),
        "serving_prefill_ms": {
            k: (hists.get("serving_prefill_ms") or {}).get(k)
            for k in ("count", "sum")},
        "serving_decode_step_ms_mean": (
            hists.get("serving_decode_step_ms") or {}).get("mean"),
        "serving_decode_dispatch_total": total(
            "serving_decode_dispatch_total"),
        "serving_decode_overlapped_total": total(
            "serving_decode_overlapped_total")}


def run_cell(argv, keep, factor, after) -> int:
    """One benchmark run through `cellbench.run.main`; its spans are
    read once it is over, cut to its window."""
    sys.path.insert(0, REPO)
    from cellbench import run
    from triton_distributed_tpu.observability import get_tracer
    seen = {}
    drive = run.drive

    def driving(*args, **kw):
        seen["drive"] = d = drive(*args, **kw)
        return d
    run.drive = driving
    sys.argv = [os.path.join(REPO, "cellbench", "run.py")] + argv
    try:
        rc = run.main()
    finally:
        run.drive = drive
        tracer = get_tracer()
        trace = tracer.chrome_trace(include_open=False)
        if keep:
            tracer.export_chrome_trace(keep)
        d = seen.get("drive")
        window = None
        if d is not None:
            # the harness's clock is time.monotonic, a span's `ts` the
            # tracer's unix-anchored perf_counter
            off = (trace["metadata"]["clock_base_unix"]
                   - tracer.monotonic_offset)
            window = (d.start + off, d.end + off)
        print(json.dumps(dict(
            event="admission_gaps", dropped=tracer.dropped,
            window=summarize(spans_of(trace), window, factor, after),
            process=counters())), flush=True)
    return rc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="?", help="a Chrome trace file")
    ap.add_argument("--cell", action="store_true",
                    help="run cellbench/run.py with the arguments "
                         "after `--` and read its spans")
    ap.add_argument("--keep", help="with --cell: write the spans here")
    ap.add_argument("--factor", type=float, default=1.25)
    ap.add_argument("--after", type=int, default=3)
    argv = sys.argv[1:]
    cut = argv.index("--") if "--" in argv else len(argv)
    args = ap.parse_args(argv[:cut])
    if args.cell:
        return run_cell(argv[cut + 1:], args.keep, args.factor,
                        args.after)
    if not args.trace:
        ap.error("a trace file, or --cell")
    with open(args.trace) as f:
        trace = json.load(f)
    print(json.dumps(dict(
        event="admission_gaps",
        all=summarize(spans_of(trace), None, args.factor, args.after))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
