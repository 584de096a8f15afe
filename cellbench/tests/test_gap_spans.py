"""The gap readers of PR 39 against hand-built rings: a plain read, reads
behind one and two prefills, an early read that came late, a read after
an idle period; enqueues that found the chip busy, idle for want of the
host and idle for want of arrivals; the profiler's standstill; a ring
that dropped spans, a program without the records, an empty window."""

import importlib
import itertools
import json
import types

import pytest

from cellbench import gap_spans
from cellbench import run as cb_run

OFFSET = 1000.0          # monotonic = perf_counter + OFFSET


class FakeSpan:
    _ids = itertools.count(1)

    def __init__(self, name, t0, dur, parent=None, **attrs):
        self.id = next(self._ids)
        self.name, self.t0, self.dur = name, t0, dur
        self.parent = parent.id if parent is not None else None
        self.attrs = attrs


class FakeTracer:
    monotonic_offset = OFFSET

    def __init__(self, spans, dropped=0):
        self._spans, self.dropped = spans, dropped

    def finished(self):
        return list(self._spans)


class Ring:
    """Calls of `step()` laid end to end: each dispatches, then reads
    the step before; ``admit`` puts an admission's first half (and the
    early read) in front."""

    def __init__(self):
        self.spans, self.t, self.landed = [], 10.0, None

    def call(self, interval_ms, rows=8, prefills=0, tokens=0,
             first=0, landed=0, starved=0, admit=None,
             pages=0.0005, gap=0.0002, gc=0.0, gc_in_dispatch=0.0):
        """One call whose read closes an interval of ``interval_ms``
        (None: a process's first).  ``admit``: (flight, front seconds,
        starved[, idle]) of an admission made in it."""
        self.t += gap                        # the harness between calls
        t0 = self.t
        root = FakeSpan("serving.step", t0, 0.0)
        self.spans.append(root)
        early = 0
        if admit is not None:
            flight, front, hungry, *rest = admit
            if flight == "read":
                self._read(root, interval_ms, rows, prefills, tokens,
                           first, landed, early=1)
                early, interval_ms = 1, None
            adm = FakeSpan("serving.admit", self.t, front, root)
            self.spans += [adm, FakeSpan(
                gap_spans.FRONT, self.t, front, adm, request_id=len(
                    self.spans), flight=flight, bucket=512,
                queue_wait_ms=2.0, starved=hungry,
                **({"idle": 1} if rest else {}))]
            self.t += front
            if flight == "behind":
                self._read(root, interval_ms, rows, prefills, tokens,
                           first, landed, early=1)
                early, interval_ms = 1, None
        pg = FakeSpan("serving.pages", self.t, pages, root)
        self.spans.append(pg)
        if gc:
            self.spans.append(FakeSpan("runtime.gc", self.t, gc, pg,
                                       generation=2, collected=9))
        self.t += pages
        dispatch = FakeSpan(gap_spans.DISPATCH, self.t,
                            0.001 + gc_in_dispatch, root, k=1,
                            spec=False, inflight=1, starved=starved)
        self.spans.append(dispatch)
        if gc_in_dispatch:      # a pause AFTER the enqueue was made
            self.spans.append(FakeSpan(
                "runtime.gc", self.t + 0.001, gc_in_dispatch, dispatch,
                generation=2, collected=0))
        self.t += dispatch.dur
        if not early:
            self._read(root, interval_ms, rows, prefills, tokens, first,
                       landed, early=0)
        root.dur = self.t - t0
        return root

    def _read(self, root, interval_ms, rows, prefills, tokens, first,
              landed, early):
        """The sync ends where the interval since the last landing
        says it does."""
        if self.landed is not None and interval_ms is not None:
            end = max(self.landed + interval_ms / 1e3, self.t)
        else:
            end = self.t + 0.001
        attrs = dict(rows=rows, first_tokens=first, prefills=prefills,
                     prefill_tokens=tokens, early=early, landed=landed)
        if interval_ms is not None and self.landed is not None:
            attrs["interval_ms"] = (end - self.landed) * 1e3
        self.spans.append(FakeSpan(gap_spans.READ, self.t, end - self.t,
                                   root, **attrs))
        self.spans.append(FakeSpan("serving.commit", end, 0.0003, root,
                                   tokens=rows, retired=first))
        self.landed, self.t = end, end + 0.0003


def view(tracer, start=OFFSET + 10.0, end=OFFSET + 20.0, trace_span=None,
         stop_trace_s=0.0, config=None):
    sched = types.SimpleNamespace()
    if tracer is not None:
        sched.tracer = tracer
    drive = cb_run.Drive(rows=[], steps=[], t0=OFFSET, start=start,
                         end=end, trace_span=trace_span,
                         stop_trace_s=stop_trace_s)
    return cb_run.RunView(
        spec=types.SimpleNamespace(config=config or {}),
        system=types.SimpleNamespace(sched=sched), drive=drive,
        trace=None, peaks={}, math=None, modules={})


def read(name, run):
    return importlib.import_module(
        f"cellbench.layer_metrics.{name}").read(run)


def said(capsys, event):
    return [r for r in (json.loads(ln) for ln in
                        capsys.readouterr().out.splitlines()
                        if ln.startswith("{")) if r["event"] == event]


@pytest.fixture
def batch():
    """36 plain reads of 8 rows, then an admission enqueued behind the
    step in flight: its early read is plain (and late), the read after
    it stands behind ONE prefill of 512 and commits 7 running rows and
    a first token."""
    r = Ring()
    r.call(None)
    for _ in range(36):
        r.call(8.0)
    r.call(8.4, landed=1, admit=("behind", 0.004, 0))
    r.call(40.0, prefills=1, tokens=512, first=1)
    for _ in range(10):
        r.call(8.0)
    return FakeTracer(r.spans)


def test_admit_gap_share_is_the_share_of_gaps_behind_a_prefill(
        batch, capsys):
    v = read("admit_gap_share", view(batch))
    # 48 reads with an interval: 47 of 8 gaps, one of 7 behind a prefill
    assert v == pytest.approx(100.0 * 7 / (47 * 8 + 7))
    (line,) = said(capsys, "gap_composition")
    assert line["reads"] == 48 and line["gaps"] == 47 * 8 + 7
    assert line["reads_with_prefills"] == 1
    assert line["admission_begins_at_percentile"] == pytest.approx(
        100.0 - v)
    assert line["interval_ms_p50"] == pytest.approx(8.0)
    assert line["interval_ms_p95"] == pytest.approx(8.0)
    assert line["interval_ms_p99"] == pytest.approx(40.0)
    assert "pair_ms_p95" not in line
    # (the fake ring retires a row wherever one gets its first token)
    assert line["commits_retiring"] == 1
    assert line["retiring_commit_ms_p50"] == pytest.approx(0.3)


def test_the_percentile_jumps_once_an_admission_lengthens_two_gaps(
        capsys):
    """PR 37's fault in numbers: one gap an admission keeps the 95th
    percentile on the plain step, two put it into the long ones."""
    for long_gaps, p95 in ((1, 8.0), (2, 12.0)):
        r = Ring()
        r.call(None)
        for _ in range(4):
            for _ in range(36 - long_gaps):
                r.call(8.0)
            if long_gaps == 2:          # step t delivered late as well
                r.call(12.0, prefills=1, tokens=512)
            r.call(40.0, prefills=1, tokens=512, first=1)
        read("admit_gap_share", view(FakeTracer(r.spans)))
        (line,) = said(capsys, "gap_composition")
        assert line["interval_ms_p95"] == pytest.approx(p95)


def test_admit_gap_ms_is_the_admitting_interval_less_the_plain(
        batch, capsys):
    assert read("admit_gap_ms", view(batch)) == pytest.approx(32.0)
    (line,) = said(capsys, "admit_gaps")
    assert line["reads"] == 1
    assert line["plain_ms_p50"] == pytest.approx(8.0)
    assert line["by_prefills"] == {"1": {
        "reads": 1, "prefill_tokens_mean": 512.0,
        "interval_ms_p50": pytest.approx(40.0)}}


def test_a_read_after_an_idle_period_is_no_gap(capsys):
    """An open-loop cell: the read that delivers a lone request's first
    token closes an interval of seconds that no running row saw."""
    r = Ring()
    r.call(None)
    for _ in range(5):
        r.call(8.0, rows=1)
    r.call(3000.0, rows=1, first=1, prefills=1, tokens=2048,
           admit=("none", 0.004, 1))
    for _ in range(5):
        r.call(8.0, rows=1)
    r.call(70.0, rows=2, first=1, prefills=1, tokens=2048)
    run = view(FakeTracer(r.spans))
    assert read("admit_gap_ms", run) == pytest.approx(62.0)
    assert read("admit_gap_share", run) == pytest.approx(100.0 / 11)
    (line,) = said(capsys, "gap_composition")
    assert line["interval_ms_p99"] == pytest.approx(70.0)


def test_admit_front_ms_and_its_line(batch, capsys):
    assert read("admit_front_ms", view(batch)) == pytest.approx(4.0)
    (line,) = said(capsys, "admit_front")
    assert line["admissions"] == 1
    assert line["flight"] == {"behind": 1}
    assert line["starved"] == {"0": 1}
    assert line["early_reads"] == line["early_reads_late"] == 1
    assert line["queue_wait_ms_p50"] == pytest.approx(2.0)


def test_device_starved_share_leaves_the_idle_server_out(capsys):
    r = Ring()
    r.call(None, admit=("none", 0.004, 1, 1))         # arrivals: idle
    for _ in range(17):
        r.call(8.0)
    r.call(8.0, starved=1, pages=0.009, gc=0.0085)    # the collector
    r.call(8.0, gc_in_dispatch=0.2)     # ... behind an enqueue: the
    r.call(200.0, starved=1, landed=1)  # NEXT enqueue finds the chip idle
    r.call(8.0, starved=1, gap=0.012)                 # the harness
    r.call(8.0, starved=1, admit=("read", 0.009, 1))  # read first
    v = read("device_starved_share", view(FakeTracer(r.spans)))
    # 23 dispatches and one prefill that were not idle; 4 + 1 starved
    assert v == pytest.approx(100.0 * 5 / 24)
    (line,) = said(capsys, "starved_by")
    assert line["enqueues"] == 24 and line["starved"] == 5
    assert line["idle"] == 1
    assert line["share_with_idle"] == pytest.approx(100.0 * 6 / 25)
    assert {k: v["n"] for k, v in line["by"].items()} == {
        "step after runtime.gc": 2, "step after harness": 1,
        "prefill after serving.admit.prefill[read]": 1,
        "step after serving.admit.prefill[read]": 1}
    assert line["by"]["step after runtime.gc"][
        "held_ms_mean"] == pytest.approx((8.5 + 200.0) / 2)


def test_the_profilers_standstill_is_left_out(capsys):
    """The read whose interval spans the trace's stop, and the enqueue
    after it, are the harness's own pause."""
    r = Ring()
    r.call(None)
    for _ in range(20):
        r.call(8.0)
    stop = r.t + OFFSET
    r.call(5000.0, starved=1, gap=4.99)
    for _ in range(20):
        r.call(8.0)
    run = view(FakeTracer(r.spans), trace_span=(stop - 0.1, stop),
               stop_trace_s=4.98)
    assert read("device_starved_share", run) == pytest.approx(0.0)
    (line,) = said(capsys, "starved_by")
    assert line["dropped_at_trace_stop"] == 1 and line["enqueues"] == 41
    rs, dropped = gap_spans.reads(run, "x")
    assert dropped == 1 and len(rs) == 40
    assert max(x.interval_ms for x in rs) == pytest.approx(8.0)
    # without the harness's word the pause would be a token gap
    rs, dropped = gap_spans.reads(view(FakeTracer(r.spans)), "x")
    assert dropped == 0 and max(x.interval_ms for x in rs) > 4000


def test_a_block_generating_cell_prints_pairs_too(batch, capsys):
    run = view(batch, config={"generation": {"block_length": 4}})
    read("admit_gap_share", run)
    (line,) = said(capsys, "gap_composition")
    assert line["pair_ms_p50"] == pytest.approx(16.0)
    assert line["pair_ms_p99"] == pytest.approx(48.4)


def test_weighted_percentile():
    wp = gap_spans.weighted_percentile
    assert wp([1.0, 2.0, 3.0], [1, 1, 1], 50) == 2.0
    assert wp([1.0, 2.0, 3.0], [8, 1, 1], 50) == 1.0
    assert wp([1.0, 9.0], [95, 5], 95) == 1.0
    assert wp([1.0, 9.0], [94, 6], 95) == 9.0
    assert wp([1.0], [0], 50) is None


NEW = ("admit_gap_share", "admit_gap_ms", "admit_front_ms",
       "device_starved_share")


@pytest.mark.parametrize("metric", NEW)
@pytest.mark.parametrize("case", ["no_tracer", "dropped", "empty",
                                  "parent"])
def test_nothing_to_read_is_said_and_left_out(batch, capsys, metric,
                                              case):
    if case == "no_tracer":
        run = view(None)
    elif case == "dropped":
        run = view(FakeTracer(batch.finished(), dropped=3))
    elif case == "empty":
        run = view(batch, start=OFFSET + 500.0, end=OFFSET + 600.0)
    else:
        # the parent's program: the spans without this PR's records
        spans = batch.finished()
        for s in spans:
            s.attrs = {k: v for k, v in s.attrs.items()
                       if k in ("k", "spec", "inflight", "request_id")}
        run = view(FakeTracer(spans))
    got = read(metric, run)
    if case == "parent" and metric == "admit_front_ms":
        # that span the parent has: its duration is there to read
        assert got == pytest.approx(4.0)
        return
    assert got is None
    (line,) = said(capsys, "layer_metric_absent")
    assert line["metric"] == metric
    if case == "dropped":
        assert "dropped 3 spans" in line["why"]


def test_the_benchmark_names_the_four_readers_for_every_cell():
    bench = cb_run.load_json(cb_run.ROOT, "BENCHMARK.json")
    cells = [w["name"] for w in bench["workloads"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == cells
        assert (m["source"], m["moves"], m["better"]) == (
            "program_span", "itl_p95_ms", "lower")
        assert m["layer"] == by_name["step_host_ms"]["layer"]
        importlib.import_module(f"cellbench.layer_metrics.{name}")
