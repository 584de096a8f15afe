#!/usr/bin/env python3
"""One cell of the benchmark, once, in one fresh process.

    python3 cellbench/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell (`BENCHMARK.json` `workloads`), its configuration, its traffic
mix and its per-layer metrics are found by NAME: `cells/<cell>.json`,
`configs/<config>.json`, `traffic/<traffic>.json`,
`layer_metrics/<metric>.py`.  Nothing here lists them, so a new cell,
configuration, mix or metric is new files and new entries only
(`cellbench/README.md`).

Order of work: build the system from the seed; warm every prefill
bucket the mix can draw and both signatures of the decode step through
the scheduler itself (set-up ends here: `setup_s`); offer the traffic
for a lead-in, then measure `--seconds`; drain; read peak memory;
compare a sample of what was served with the plain reference
(`correctness.py`); print the result line LAST.  Everything else goes
on earlier lines and under `cellbench_out/`.

Without a TPU (or with fewer chips than the cell asks) the run exits
nonzero and prints no result.  `--rehearse` walks the same code on the
CPU at a tiny size, prints `"rehearsal": true` and no result line.
`--sweep r1,r2,..`, `--slots n1,n2,..` and `--calibrate s1,s2,..` are
the builder's tools (the knee; the slot count; the readings the limits
of `correct` are set from), never a result either.  `--weights fp8`
hands the program float8-rounded weights: the CONTROL, which has to
come out as not correct, and never a result.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# first, so that its clock starts with the process
from cellbench.clock import say, since_start   # noqa: E402

import argparse            # noqa: E402
import contextlib          # noqa: E402
import dataclasses         # noqa: E402
import importlib           # noqa: E402
import json                # noqa: E402
import shutil              # noqa: E402
import time                # noqa: E402

from cellbench import (correctness, model_math, stats,   # noqa: E402
                       trace_reduce, traffic_gen)

EXIT_NO_CHIP, EXIT_COMPILED_IN_WINDOW = 4, 6

#: Longest wait after the window for its requests to finish.
DRAIN_S = 180.0
#: A `--trace 1` run starts its trace this many seconds before the
#: window's end ...
TRACE_S = 20.0
#: ... and stops it at the window's end or after this many scheduler
#: steps, whichever comes first.  A trace costs by its events, not by
#: its seconds (at tp=4, 36 layers: ~10,400 kept events a step over
#: the four chips, ~20 us each to stop, read and reduce, on top of a
#: fixed 16-22 s), so a bound in steps keeps a traced run as long
#: however fast the program steps.  120: the trace's own cost at tp=4
#: stays under 45 s and the whole run under 300 s (PERF.md sections
#: 2, 3); the per-layer metrics are medians and sums per traced step
#: and read the same off fewer.
TRACE_STEPS = 120
#: Most requests compared with the reference after a run (a cell's
#: `check_requests` says otherwise): all the window finished, up to
#: this many, the longest always among them.
CHECK_REQUESTS = 16


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"cellbench: no {what} named {name!r} in "
                     f"BENCHMARK.json")


# ---------------------------------------------------------------------------
# the cell, read by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Spec:
    bench: dict
    workload: dict
    cell: dict
    config: dict
    traffic: dict
    rehearse: bool = False

    @classmethod
    def read(cls, name: str, rehearse: bool) -> "Spec":
        bench = load_json(ROOT, "BENCHMARK.json")
        wl = by_name(bench["workloads"], name, "workload")
        cell = load_json(HERE, "cells", f"{name}.json")
        cfg_entry = by_name(bench["configs"], wl["config"],
                            "configuration")
        config = load_json(ROOT, cfg_entry["file"])
        traffic = load_json(HERE, "traffic", f"{wl['traffic']}.json")
        if rehearse:
            # the same code at a size the CPU can walk: tiny sizes, the
            # mix's generator kept, its lengths and load swapped
            tiny = load_json(HERE, "rehearsal", "config.json")
            config = dict(config, **tiny)
            kind = traffic["generator"]
            traffic = load_json(HERE, "rehearsal", f"{kind}.json")
            cell = dict(cell, load=traffic["load"],
                        lead_in_s=traffic["lead_in_s"],
                        correct=traffic["correct"])
        return cls(bench, wl, cell, config, traffic, rehearse)

    def metrics(self, group: str):
        """Entries of ``group`` this cell reports."""
        name = self.workload["name"]
        return [m for m in self.bench[group]
                if name in m.get("workloads", [name])]


# ---------------------------------------------------------------------------
# compile accounting (the events JAX records around every compilation)
# ---------------------------------------------------------------------------

class CompileCounters:
    def __init__(self):
        import jax
        self.compiles = self.requests = self.hits = 0
        self.compile_s = 0.0
        #: set once set-up is over: a later compilation is named
        self.watch = False
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs
            if self.watch:
                say(event="compiled_late", seconds=secs,
                    what={k: str(v) for k, v in kw.items()})

    def snapshot(self) -> dict:
        return {"programs": self.compiles,
                "compile_s": round(self.compile_s, 3),
                "cache_requests": self.requests, "cache_hits": self.hits,
                "cache_misses": self.requests - self.hits}


# ---------------------------------------------------------------------------
# the drive loop
# ---------------------------------------------------------------------------

class Row:
    """One request as the benchmark saw it (its own clock readings)."""
    __slots__ = ("item", "handle", "submitted", "times", "rejected",
                 "done_at")

    def __init__(self, item):
        self.item = item
        self.handle = None
        self.submitted = None
        self.times = []
        self.rejected = None
        self.done_at = None

    def admitted_at(self, system):
        """When the scheduler admitted it (None: refused or queued)."""
        return (system.admitted_at(self.handle)
                if self.handle is not None else None)


@dataclasses.dataclass
class Drive:
    """What one pass of traffic left behind."""
    rows: list
    steps: list            # (t, active, admitted, used_pages, live_tokens)
    t0: float
    start: float           # window
    end: float
    trace_span: tuple = None     # (start, stop) of the traced steps
    stop_trace_s: float = 0.0    # what writing the trace took
    wrapped: int = 0

    def sample(self):
        return [r for r in self.rows
                if self.start <= r.item.due < self.end]

    def ttft_ms(self):
        """Due time -> first token, of the window's requests."""
        return [(r.times[0] - r.item.due) * 1e3 for r in self.sample()
                if r.times]


def drive(system, plan, lead_in_s: float, seconds: float,
          trace_dir: str = None) -> Drive:
    """Offer ``plan``'s traffic: single-threaded, in the process that
    owns the chip.  Between scheduler steps every request now due is
    submitted, timed from when it was DUE; token times are this
    function's own `time.monotonic()` readings taken in `on_token`."""
    import jax
    mono = time.monotonic
    finished = []

    def on_token_for(row):
        times, need = row.times, row.item.max_new

        def on_token(req, token):
            times.append(mono())
            if len(times) == need:
                finished.append(row)
        return on_token

    def span(name):
        """A host span in the profiler's own trace, while it runs."""
        return (jax.profiler.TraceAnnotation(name) if tracing
                else contextlib.nullcontext())

    rows, steps, inflight = [], [], {}
    t0 = mono()
    start, end = t0 + lead_in_s, t0 + lead_in_s + seconds
    trace_at = end - min(TRACE_S, seconds) if trace_dir else None
    tracing, trace_span, traced_steps, stop_trace_s = False, None, 0, 0.0

    def stop_trace():
        # the last step ended in a host sync: the device is idle.
        # The span ends BEFORE the stop, which writes the file.
        nonlocal tracing, trace_span, stop_trace_s
        trace_span = (trace_span, mono())
        jax.profiler.stop_trace()
        stop_trace_s = mono() - trace_span[1]
        tracing = False

    plan.start(t0)
    issuing = True
    while True:
        now = mono()
        if issuing and now >= end:
            issuing = False
        if tracing and (now >= end or traced_steps >= TRACE_STEPS):
            stop_trace()
            now = mono()
        if trace_at is not None and now >= trace_at and issuing:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            trace_span, trace_at, tracing = mono(), None, True
        if issuing:
            with span("cellbench.submit"):
                for item in plan.due(now):
                    row = Row(item)
                    row.handle, row.rejected = system.submit(
                        item.prompt, item.max_new, item.due,
                        on_token_for(row))
                    row.submitted = mono()
                    rows.append(row)
                    if row.handle is not None:
                        inflight[id(row)] = row
        if system.has_work():
            live = 0
            if tracing:
                # context positions this step's decode reads
                live = sum(len(r.item.prompt) + len(r.times)
                           for r in inflight.values()
                           if system.admitted_at(r.handle) is not None)
            with span("cellbench.step"):
                out = system.step()
            t = mono()
            steps.append((t, out["active"], out["admitted"],
                          system.used_pages(), live))
            traced_steps += tracing
            while finished:
                row = finished.pop()
                row.done_at = row.times[-1]
                inflight.pop(id(row), None)
                plan.on_finish(row.item, row.done_at)
        elif not issuing:
            break
        else:
            nxt = plan.next_due()
            with span("cellbench.wait_for_arrival"):
                time.sleep(min(max((nxt or now) - now, 0.0), 0.0005))
        if not issuing:
            if now > end + DRAIN_S:
                break
            if not any(start <= r.item.due < end
                       for r in inflight.values()):
                break
    if tracing:
        stop_trace()
    return Drive(rows, steps, t0, start, end, trace_span=trace_span,
                 stop_trace_s=stop_trace_s,
                 wrapped=getattr(plan, "wrapped", 0))


def settle(system) -> None:
    """Finish whatever is still running (lead-in leftovers of a sweep
    row, requests past the window)."""
    deadline = time.monotonic() + 120.0
    while system.has_work() and time.monotonic() < deadline:
        system.step()


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def mix_buckets(system, plan):
    """Prefill buckets the plan can draw."""
    lo, hi = plan.prompt_range
    out = []
    for i, b in enumerate(system.buckets):
        prev = system.buckets[i - 1] if i else 0
        if b >= lo and prev < hi:
            out.append(b)
    return out


def warm_up(system, plan, seed: int) -> dict:
    """Every program the window will use, through the scheduler's own
    path.  A jitted program is compiled once for each KIND of argument
    it meets (an array a step returned, one an insert returned, a page
    table fresh from the host), so the warm-up meets them all: for each
    bucket two requests admitted in one step AFTER a decode step has
    run (the first insert takes the pool from a step, the second from
    an insert), each long enough to cross page boundaries (the decode
    step then runs after an insert, after a page-table flush and
    straight after a step)."""
    import numpy as np
    rng = np.random.default_rng([int(seed), 0xBEEF])
    vocab = system.config["vocab_size"]
    buckets = mix_buckets(system, plan)
    lo, hi = plan.prompt_range
    gen = min(2 * system.page_size + 2, plan.output_max)
    n = 0

    def send(bucket):
        nonlocal n
        plen = max(min(bucket, hi, system.max_seq - gen), lo)
        handle, why = system.submit(
            rng.integers(0, vocab, plen).tolist(), gen,
            time.monotonic(), None)
        if handle is None:
            raise RuntimeError(f"warm-up request for bucket {bucket} "
                               f"refused: {why}")
        n += 1

    send(buckets[0])
    system.step()
    system.step()
    for b in buckets:
        send(b)
        send(b)
        system.step()
        system.step()
    settle(system)
    return {"buckets": buckets, "requests": n, "new_tokens": gen}


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def end_to_end(d: Drive, setup_s: float) -> dict:
    ttft = d.ttft_ms()
    all_times = [r.times for r in d.rows]
    gaps = stats.pooled_gaps(all_times, d.start, d.end)
    return {
        "ttft_p50_ms": stats.percentile(ttft, 50),
        "ttft_p95_ms": stats.percentile(ttft, 95),
        "itl_p95_ms": (stats.percentile(gaps, 95) or 0.0) * 1e3,
        "out_tokens_per_s": stats.count_in(all_times, d.start, d.end)
        / (d.end - d.start),
        "setup_s": setup_s,
    }


def failures(system, d: Drive, vocab: int):
    """(attempted, failed): requests due in the window; one refused,
    unfinished or with a token outside the vocabulary has failed."""
    sample = d.sample()
    bad = 0
    for r in sample:
        ok = (r.handle is not None and r.done_at is not None
              and system.finished_ok(r.handle, r.item.max_new)
              and all(0 <= t < vocab for t in r.handle.generated))
        bad += not ok
    return len(sample), bad


@dataclasses.dataclass
class RunView:
    """What a per-layer metric's reader is given."""
    spec: Spec
    system: object
    drive: Drive
    trace: object           # trace_reduce.Reduced or None
    peaks: dict
    math: object            # cellbench.model_math
    modules: dict           # the adapter's TRACE_MODULES

    # conveniences shared by several readers
    def window_steps(self):
        d = self.drive
        return [s for s in d.steps if d.start <= s[0] < d.end]

    def traced_steps(self):
        a, b = self.drive.trace_span
        return [s for s in self.drive.steps if a <= s[0] < b]

    def admitted_in_trace(self):
        a, b = self.drive.trace_span
        out = []
        for r in self.drive.rows:
            t = r.admitted_at(self.system)
            if t is not None and a <= t < b:
                out.append(r)
        return out

    def module(self, kind: str):
        """Device durations (s) of the programs the adapter names
        ``kind`` ("decode", "prefill", "insert")."""
        prefix = self.modules[kind]
        out = []
        for name, durs in self.trace.modules.items():
            if name.startswith(prefix):
                out.extend(durs)
        return out


def per_layer(view: RunView) -> dict:
    out = {}
    for m in view.spec.metrics("per_layer"):
        reader = importlib.import_module(
            f"cellbench.layer_metrics.{m['name']}")
        value = reader.read(view)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def device_record(devices) -> dict:
    import jax
    stats_ = [d.memory_stats() or {} for d in devices]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": max(
                (s.get("peak_bytes_in_use", 0) for s in stats_),
                default=0)}


def out_dir(args) -> str:
    d = os.path.join(ROOT, "cellbench_out", args.workload,
                     f"seed-{args.seed}-trace-{args.trace}")
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    return d


def request_rows(system, d: Drive):
    rows = []
    for r in d.rows:
        adm = r.admitted_at(system)
        rows.append({
            "index": r.item.index, "client": r.item.client,
            "prompt_len": len(r.item.prompt), "max_new": r.item.max_new,
            "due": r.item.due - d.t0, "in_window":
            bool(d.start <= r.item.due < d.end),
            "late_ms": (r.submitted - r.item.due) * 1e3,
            "queue_wait_ms": None if adm is None
            else (adm - r.item.due) * 1e3,
            "ttft_ms": (r.times[0] - r.item.due) * 1e3
            if r.times else None,
            "tokens": len(r.times), "rejected": r.rejected,
            "finished": r.done_at is not None})
    return rows


def check_sample(spec: Spec, plan, d: Drive, seed: int,
                 control: bool = False) -> dict:
    """The reference over a sample of what the window served."""
    reference = importlib.import_module(spec.config["reference"])
    done = [{"index": r.item.index, "prompt": r.item.prompt,
             "prompt_len": len(r.item.prompt),
             "tokens": list(r.handle.generated),
             "ok": r.done_at is not None}
            for r in d.sample() if r.handle is not None]
    sample = correctness.pick_sample(
        done, seed, int(spec.cell.get("check_requests", CHECK_REQUESTS)))
    seq_pad = -(-plan.total_max // 128) * 128
    out_pad = -(-plan.output_max // 8) * 8
    t0 = time.monotonic()
    res = correctness.score(reference, reference.dims_of(spec.config),
                            seed, sample, seq_pad, out_pad,
                            control=control)
    res["seconds"] = time.monotonic() - t0
    return res


def keep_planes(planes: dict, path: str, first: int = 400) -> None:
    """A small recording for `tests/test_trace_reduce.py`: each line
    cut to its first events."""
    cut = {pn: {ln: sorted(evs, key=lambda e: e[1])[:first]
                for ln, evs in lines.items()}
           for pn, lines in planes.items()}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"planes": cut}, f)


def run_cell(args) -> int:
    spec = Spec.read(args.workload, args.rehearse)
    # the program places JAX's compile cache as it is imported
    # (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache):
    # before any device is touched
    adapter = importlib.import_module(spec.config["adapter"])
    import jax
    backend = jax.default_backend()
    chips = spec.workload["chips"]
    devices = jax.devices()
    say(event="start", workload=args.workload, seed=args.seed,
        backend=backend, devices=len(devices), rehearsal=spec.rehearse,
        kind=devices[0].device_kind,
        compile_cache_dir=jax.config.jax_compilation_cache_dir)
    if not spec.rehearse and (backend != "tpu" or len(devices) < chips):
        print(f"cellbench: {args.workload} needs {chips} TPU chip(s); "
              f"JAX found backend {backend!r} with {len(devices)} "
              f"device(s).  No result.", flush=True)
        return EXIT_NO_CHIP
    if spec.rehearse and len(devices) < chips:
        chips = 1
    devices = devices[:chips]
    counters = CompileCounters()
    peaks = (model_math.load_peaks(devices[0].device_kind)
             if not spec.rehearse else {})

    if args.slots:
        return sweep_slots(args, spec, adapter, devices)
    lead_in = lead_in_of(args, spec)
    vocab = spec.config["vocab_size"]
    plan = traffic_gen.make_plan(spec.traffic, spec.cell["load"],
                                 args.seed, vocab, lead_in + args.seconds)
    # the builder's tools start from the served weights (a sweep
    # measures the program; `calibrate` hands each seed its own)
    system = adapter.System(
        spec.config, args.seed, devices,
        weights="served" if args.sweep or args.calibrate
        else args.weights)
    say(event="built", weight_bytes=system.weight_bytes,
        weights=args.weights, kv_budget_bytes=system.kv_budget_bytes,
        usable_pages=system.usable_pages, buckets=list(system.buckets))
    warmed = warm_up(system, plan, args.seed)
    setup_s = since_start()
    say(event="warm", **warmed, setup_s=setup_s,
        compile=counters.snapshot())

    if args.sweep:
        sweep(args, spec, system, counters)
    if args.calibrate:
        calibrate(args, spec, system)
    if args.sweep or args.calibrate:
        return 0

    odir = out_dir(args)
    before = counters.snapshot()
    counters.watch = True
    trace_dir = os.path.join(odir, "trace") if args.trace else None
    d = drive(system, plan, lead_in, args.seconds, trace_dir)
    compiled = counters.snapshot()["programs"] - before["programs"]
    counters.watch = False
    device = device_record(devices)      # before the reference runs
    attempted, failed = failures(system, d, vocab)
    rows = request_rows(system, d)
    with open(os.path.join(odir, "requests.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    in_window = [r for r in rows if r["in_window"]]
    win = [s for s in d.steps if d.start <= s[0] < d.end]
    period = [b[0] - a[0] for a, b in zip(win, win[1:])]
    say(event="window", seconds=args.seconds, lead_in_s=lead_in,
        requests_submitted=len(rows), attempted=attempted, failed=failed,
        steps=len(d.steps), steps_in_window=len(win),
        active_mean=sum(s[1] for s in win) / max(len(win), 1),
        admitted_in_window=sum(s[2] for s in win),
        step_period_ms={"p50": (stats.percentile(period, 50) or 0) * 1e3,
                        "mean": sum(period) / max(len(period), 1) * 1e3,
                        "max": max(period, default=0) * 1e3},
        compiled_in_run=compiled,
        plan_wrapped=d.wrapped,
        prompt_len_p50=stats.percentile(
            [r["prompt_len"] for r in in_window], 50),
        new_tokens_p50=stats.percentile(
            [r["max_new"] for r in in_window], 50),
        late_p95_ms=stats.percentile(
            [r["late_ms"] for r in in_window], 95),
        drained_s=time.monotonic() - d.end)
    if compiled:
        print(f"cellbench: {compiled} program(s) compiled after set-up "
              f"— a window that compiles measures the compiler.  No "
              f"result.", flush=True)
        return EXIT_COMPILED_IN_WINDOW

    e2e = end_to_end(d, setup_s)
    trace = None
    if args.trace:
        events = read_s = reduce_s = file_bytes = None
        if not spec.rehearse:
            xplane = trace_reduce.find_xplane(trace_dir)
            file_bytes = os.path.getsize(xplane)
            t = time.monotonic()
            planes = trace_reduce.read(xplane)
            read_s = time.monotonic() - t
            events = trace_reduce.count_events(planes)
            trace = trace_reduce.reduce_planes(planes)
            reduce_s = time.monotonic() - t - read_s
            if args.keep_planes:
                keep_planes(planes, args.keep_planes)
            del planes
    view = RunView(spec, system, d, trace, peaks, model_math,
                   adapter.TRACE_MODULES)
    if args.trace:
        say(event="traced", steps=len(view.traced_steps()),
            span_s=d.trace_span[1] - d.trace_span[0], events=events,
            file_bytes=file_bytes, stop_trace_s=d.stop_trace_s,
            read_s=read_s, reduce_s=reduce_s)
    layer = per_layer(view) if args.trace else {}

    checked = check_sample(spec, plan, d, args.seed)
    _, lines = correctness.judge(checked["program"],
                                 spec.cell["correct"])
    # every number `correct` rests on, beside its limit
    lines += [{"compared": name, "value": value, "limit": limit,
               "within": bool(within)} for name, value, limit, within in (
        ("failed", failed, 0, failed == 0),
        ("plan_wrapped", d.wrapped, 0, d.wrapped == 0),
        ("tokens_checked_at_least", checked["tokens"], 1,
         checked["tokens"] > 0))]
    for ln in lines:
        say(event="compared", **ln)
    say(event="checked", requests=checked["requests"],
        tokens=checked["tokens"], reference_s=checked["seconds"],
        plan_wrapped=d.wrapped)
    correct = all(ln["within"] for ln in lines)

    if args.trace:
        metrics = layer
        if trace is not None:
            device["busy_s"] = trace.busy_s
            device["window_s"] = d.trace_span[1] - d.trace_span[0]
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in spec.metrics("end_to_end")}
    say(event="all_metrics", end_to_end=e2e, per_layer=layer)
    result = {"correct": correct, "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace is not None:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in trace.top_ops],
            "idle_gaps": [[n, s] for n, s in trace.idle_gaps]}
        say(event="trace", devices=trace.devices,
            busy_s_per_device=trace.busy_s_per_device,
            modules={k: {"n": len(v), "sum_s": sum(v)}
                     for k, v in trace.modules.items()})
        shutil.rmtree(trace_dir, ignore_errors=True)
    # what was compared comes last: on the line, and on standard error
    result["compared"] = {ln["compared"]: {"value": ln["value"],
                                           "limit": ln["limit"]}
                          for ln in lines}
    if spec.rehearse or args.weights != "served":
        say(rehearsal=spec.rehearse, control=args.weights != "served",
            would_print=result)
        return 0
    for ln in lines:
        print(json.dumps(ln), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


# ---------------------------------------------------------------------------
# the builder's tools
# ---------------------------------------------------------------------------

def lead_in_of(args, spec: Spec) -> float:
    return (float(spec.cell["lead_in_s"]) if args.lead_in is None
            else args.lead_in)


def sweep(args, spec: Spec, system, counters) -> int:
    """The knee, once: one set-up, then each rate for ``--seconds``.
    A rate is sustained when the backlog does not grow through the
    window: queue wait in its last third about what it was in its
    first."""
    lead_in = lead_in_of(args, spec)
    for rate in [float(x) for x in args.sweep.split(",")]:
        load = dict(spec.cell["load"], rate=rate)
        plan = traffic_gen.make_plan(
            spec.traffic, load, args.seed, spec.config["vocab_size"],
            lead_in + args.seconds)
        d = drive(system, plan, lead_in, args.seconds)
        attempted, failed = failures(system, d,
                                     spec.config["vocab_size"])
        e2e = end_to_end(d, 0.0)
        third = (d.end - d.start) / 3
        waits = [[], [], []]
        for r in d.sample():
            adm = r.admitted_at(system)
            if adm is not None:
                k = min(int((r.item.due - d.start) / third), 2)
                waits[k].append((adm - r.item.due) * 1e3)
        act = [s[1] for s in d.steps if d.start <= s[0] < d.end]
        say(event="sweep_row", rate=rate, attempted=attempted,
            failed=failed, ttft_p50_ms=e2e["ttft_p50_ms"],
            ttft_p95_ms=e2e["ttft_p95_ms"],
            itl_p95_ms=e2e["itl_p95_ms"],
            out_tokens_per_s=e2e["out_tokens_per_s"],
            queue_wait_p50_ms_by_third=[stats.percentile(w, 50)
                                        for w in waits],
            active_mean=sum(act) / max(len(act), 1),
            drained_s=time.monotonic() - d.end,
            compiled=counters.snapshot()["programs"])
        settle(system)
    return 0


def calibrate(args, spec: Spec, system) -> int:
    """Readings the limits of `correct` are set from: for each seed,
    other weights under the same programs and a short window at the
    cell's own load; then the program's numbers and, on the same
    prompts and tokens, those of the reference computed in float8.
    With `--weights fp8` the PROGRAM is the control (it serves
    float8-rounded weights) and its own numbers are the reading."""
    lead_in = lead_in_of(args, spec)
    reference = importlib.import_module(spec.config["reference"])
    for seed in [int(x) for x in args.calibrate.split(",")]:
        if args.weights == "fp8":
            say(event="control_weights", seed=seed,
                changed_by=reference.fp8_change(
                    reference.dims_of(spec.config), seed))
        system.reseed(seed, args.weights)
        plan = traffic_gen.make_plan(
            spec.traffic, spec.cell["load"], seed,
            spec.config["vocab_size"], lead_in + args.seconds)
        d = drive(system, plan, lead_in, args.seconds)
        attempted, failed = failures(system, d,
                                     spec.config["vocab_size"])
        res = check_sample(spec, plan, d, seed,
                           control=args.weights == "served")
        correct, _ = correctness.judge(res["program"],
                                       spec.cell["correct"])
        say(event="calibrate_row", seed=seed, weights=args.weights,
            attempted=attempted, failed=failed, within_limits=correct,
            **res)
        settle(system)
    return 0


def sweep_slots(args, spec: Spec, adapter, devices) -> int:
    """The slot count, once, in a closed-loop cell: for each count a
    system of its own (KV pool sized to what that many slots of the
    mix's longest request can hold, callers in the cell's proportion
    to slots), warmed, then one window.  The configuration takes the
    count with the most tokens a second whose token gap keeps the cap
    PERF.md states."""
    import gc
    import jax
    base = spec.config["serving"]
    vocab = spec.config["vocab_size"]
    per_slot = spec.cell["load"]["clients"] / base["num_slots"]
    lead_in = lead_in_of(args, spec)
    chips = len(devices)
    for n in [int(x) for x in args.slots.split(",")]:
        load = dict(spec.cell["load"], clients=int(round(per_slot * n)))
        plan = traffic_gen.make_plan(spec.traffic, load, args.seed,
                                     vocab, lead_in + args.seconds)
        pool = (n * plan.total_max
                * model_math.kv_bytes_per_token(spec.config) / chips)
        config = dict(spec.config, serving=dict(
            base, num_slots=n, kv_budget_bytes_per_chip=pool))
        t0 = time.monotonic()
        system = adapter.System(config, args.seed, devices)
        warmed = warm_up(system, plan, args.seed)
        warm_s = time.monotonic() - t0
        d = drive(system, plan, lead_in, args.seconds)
        attempted, failed = failures(system, d, vocab)
        e2e = end_to_end(d, 0.0)
        win = [s for s in d.steps if d.start <= s[0] < d.end]
        period = [b[0] - a[0] for a, b in zip(win, win[1:])]
        say(event="slots_row", slots=n, clients=load["clients"],
            kv_budget_bytes_per_chip=system.kv_budget_bytes / chips,
            usable_pages=system.usable_pages, attempted=attempted,
            failed=failed, itl_p95_ms=e2e["itl_p95_ms"],
            out_tokens_per_s=e2e["out_tokens_per_s"],
            step_period_p50_ms=(stats.percentile(period, 50) or 0) * 1e3,
            active_mean=sum(s[1] for s in win) / max(len(win), 1),
            pool_peak_pages=max((s[3] for s in win), default=0),
            build_and_warm_s=warm_s, warmed=warmed,
            memory_peak_bytes=device_record(devices)[
                "memory_peak_bytes"],
            drained_s=time.monotonic() - d.end)
        system = d = plan = None
        gc.collect()
        jax.clear_caches()
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--lead-in", type=float, default=None,
                    help="builder's tools: lead-in seconds, in place "
                         "of the cell's")
    ap.add_argument("--keep-planes", help="builder's tools: with "
                    "--trace 1, write the trace's planes, cut to "
                    "their first events, to this file (a test's data)")
    ap.add_argument("--sweep", help="builder's tools: rates, comma "
                    "separated")
    ap.add_argument("--slots", help="builder's tools: slot counts, "
                    "comma separated (a closed-loop cell)")
    ap.add_argument("--calibrate", help="builder's tools: seeds, comma "
                    "separated")
    ap.add_argument("--weights", choices=("served", "fp8"),
                    default="served", help="fp8: the control (float8-"
                    "rounded weights in the program); never a result")
    return run_cell(ap.parse_args())


if __name__ == "__main__":
    sys.exit(main())
