"""The per-layer readers of PR 24 against hand-built runs: spans
inside and outside the window, a ring that dropped spans, a program
with no tracer, kernel names present and absent among the trace's top
operations."""

import importlib
import itertools
import json
import types

import pytest

from cellbench import run as cb_run
from cellbench import span_reader, trace_reduce

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


def a_step(t0, sync, pages=0.001, admit=None, live=10, evicted=0,
           step=1):
    """One `serving.step` of 1 ms of host work around ``sync`` seconds
    of waiting, with its phase spans."""
    spans = []
    t = t0
    root = FakeSpan("serving.step", t0, 0.0, step=step, active=8,
                    admitted=int(admit is not None), retired=0)
    spans.append(root)
    if admit is not None:
        adm = FakeSpan("serving.admit", t, admit, root, queued=4)
        one = FakeSpan("serving.admit.request", t, admit * 0.9, adm,
                       request_id=step, bucket=2048, prompt_len=1500,
                       cached_tokens=0, mode="local")
        blk = FakeSpan("serving.prefill.block", t, admit * 0.5, one,
                       request_id=step)
        spans += [adm, one, blk]
        t += admit
    spans.append(FakeSpan("serving.pages", t, pages, root, mapped=1,
                          evicted=evicted, preempted=0, flushed_rows=8,
                          live_pages=live))
    t += pages
    spans.append(FakeSpan("serving.dispatch", t, 0.0004, root, k=1,
                          spec=False))
    t += 0.0004
    spans.append(FakeSpan("serving.sync", t, sync, root))
    t += sync
    spans.append(FakeSpan("serving.commit", t, 0.0003, root, tokens=8,
                          retired=0))
    t += 0.0003
    root.dur = (t - t0) + 0.0002          # 0.2 ms of self time
    return spans


def view(tracer, trace=None, start=OFFSET + 10.0, end=OFFSET + 20.0,
         usable_pages=100):
    sched = types.SimpleNamespace()
    if tracer is not None:
        sched.tracer = tracer
    system = types.SimpleNamespace(sched=sched, usable_pages=usable_pages,
                                   num_slots=8, world=1)
    drive = cb_run.Drive(rows=[], steps=[], t0=OFFSET, start=start,
                         end=end, trace_span=(end - 5.0, end))
    return cb_run.RunView(spec=None, system=system, drive=drive,
                          trace=trace, peaks={}, math=None,
                          modules={"decode": "jit_body",
                                   "prefill": "jit_fn",
                                   "insert": "jit_insert"})


def read(name, run):
    return importlib.import_module(
        f"cellbench.layer_metrics.{name}").read(run)


def said(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]


@pytest.fixture
def ring():
    spans = []
    spans += a_step(5.0, 0.080, step=1)                   # lead-in
    spans += a_step(11.0, 0.080, pages=0.001, live=40, step=2)
    spans += a_step(12.0, 0.082, pages=0.002, live=55, step=3)
    spans += a_step(13.0, 0.081, pages=0.030, live=50, evicted=12,
                    admit=0.070, step=4)                  # a long one
    spans += a_step(25.0, 0.080, live=99, step=5)         # drain
    return FakeTracer(spans)


def test_step_host_ms_is_the_step_less_the_sync_inside_the_window(
        ring, capsys):
    v = read("step_host_ms", view(ring))
    # host work of the three window steps: 1.9 ms, 2.9 ms, 100.9 ms
    assert v == pytest.approx(2.9, abs=1e-6)
    (rep,) = [r for r in said(capsys) if r["event"] == "step_phases"]
    assert rep["steps"] == 3
    assert rep["phase_ms_p50"]["pages"] == pytest.approx(2.0)
    assert rep["phase_ms_p50"]["self"] == pytest.approx(0.2)
    longest = rep["longest"][0]
    assert longest["step"] == 4 and longest["admitted"] == 1
    assert longest["phases_ms"]["admit"] == pytest.approx(70.0)
    assert longest["phases_ms"]["sync"] == pytest.approx(81.0)


def test_self_time_and_split(ring):
    steps = span_reader.steps_of(view(ring), "x")
    assert [st.attrs["step"] for st in steps] == [2, 3, 4]
    for st in steps:
        assert st.self_s == pytest.approx(0.0002)
        assert sum(st.split_ms().values()) == pytest.approx(st.dur * 1e3)


def test_admit_host_ms_reads_the_admissions_of_the_window(ring, capsys):
    assert read("admit_host_ms", view(ring)) == pytest.approx(63.0)
    (rep,) = [r for r in said(capsys) if r["event"] == "admit_phases"]
    assert rep["admissions"] == 1
    assert rep["prefill_block_ms_p50"] == pytest.approx(35.0)
    # a window with no admission: nothing to read
    assert read("admit_host_ms", view(ring, start=OFFSET + 11.5,
                                      end=OFFSET + 12.5)) is None


def test_kv_readers(ring, capsys):
    v = read("kv_pages_host_ms", view(ring))
    assert 2.0 < v <= 30.0                 # the evicting step is the tail
    (rep,) = [r for r in said(capsys) if r["event"] == "kv_pages"]
    assert rep["evicted"] == 12 and rep["flushed_rows"] == 24
    assert read("kv_live_peak", view(ring)) == pytest.approx(55.0)


@pytest.mark.parametrize("name", ["step_host_ms", "admit_host_ms",
                                  "kv_pages_host_ms", "kv_live_peak"])
def test_span_readers_refuse_a_ring_that_dropped_spans(ring, name,
                                                       capsys):
    ring.dropped = 7
    assert read(name, view(ring)) is None
    (why,) = [r for r in said(capsys)
              if r["event"] == "layer_metric_absent"]
    assert why["metric"] == name and "dropped 7" in why["why"]


@pytest.mark.parametrize("name", ["step_host_ms", "admit_host_ms",
                                  "kv_pages_host_ms", "kv_live_peak"])
def test_span_readers_return_nothing_for_a_program_without_spans(
        name, capsys):
    assert read(name, view(None)) is None          # the parent program
    assert read(name, view(FakeTracer([]))) is None   # observability off
    assert all(r["event"] == "layer_metric_absent"
               for r in said(capsys))


def reduced(top_ops, decode_events=10):
    return trace_reduce.Reduced(
        devices=1, busy_s_per_device=[1.0], busy_s=1.0,
        modules={"jit_body": [0.08] * decode_events, "jit_fn": [0.06]},
        per_op=dict(top_ops), top_ops=top_ops[:10], idle_gaps=[])


def test_decode_attention_ms_finds_the_kernel_by_name(capsys):
    named = reduced([("flash_decode_paged custom-call tpu_custom_call", 0.69),
                     ("copy copy", 0.07),
                     ("flash_attention_fwd custom-call tpu_custom_call",
                      0.02)])
    assert read("decode_attention_ms", view(None, named)) == \
        pytest.approx(69.0)
    unnamed = reduced([("_unknown_ custom-call tpu_custom_call", 0.71),
                       ("copy copy", 0.07)])
    assert read("decode_attention_ms", view(None, unnamed)) is None
    assert read("decode_attention_ms", view(None, None)) is None
    whys = [r["why"] for r in said(capsys)
            if r["event"] == "layer_metric_absent"]
    assert len(whys) == 2 and "flash_decode_paged" in whys[0]


def test_a_kernel_under_the_tenth_row_is_still_read(capsys):
    """Planes built by hand: eleven operations take more device time
    than `flash_decode_paged` and the fused GEMMs do.  The breakdown
    prints ten rows; the readers sum over every row."""
    ops, t = [], 0.0
    for step in range(4):
        for k in range(11):
            ops.append((f"%big_{'abcdefghijk'[k]}.{step} = f32[8] "
                        f"fusion(f32[8] %p)", t, 0.010))
            t += 0.010
        for layer in range(3):
            ops.append((f'%flash_decode_paged.{layer} = bf16[8,32,128] '
                        f'custom-call(bf16[8,32,128] %q), '
                        f'custom_call_target="tpu_custom_call"',
                        t, 0.002))
            t += 0.002
        ops.append(('%ag_gemm_ll.7 = bf16[8,4096] custom-call(bf16[8,1024]'
                    ' %x), custom_call_target="tpu_custom_call"',
                    t, 0.001))
        t += 0.001
    step_s = t / 4
    planes = {"/device:TPU:0": {
        "XLA Modules": [("jit_body(1)", i * step_s, step_s)
                        for i in range(4)],
        "XLA Ops": ops}}
    trace = trace_reduce.reduce_planes(planes)
    assert len(trace.top_ops) == 10 and len(trace.per_op) == 13
    printed = [n for n, _ in trace.top_ops]
    assert not any(n.startswith(("flash_decode_paged", "ag_gemm"))
                   for n in printed)
    by_time = sorted(trace.per_op, key=trace.per_op.get, reverse=True)
    assert by_time[11].startswith("flash_decode_paged")       # twelfth
    assert read("decode_attention_ms", view(None, trace)) == \
        pytest.approx(6.0)
    assert read("comm_gemm_ms", view(None, trace)) == pytest.approx(1.0)
    rows = [r for r in said(capsys) if r["event"] == "layer_metric_rows"]
    assert [r["metric"] for r in rows] == ["decode_attention_ms",
                                           "comm_gemm_ms"]


def test_comm_gemm_ms_sums_fused_kernels_and_xla_collectives():
    ops = reduced([
        ("flash_decode_paged custom-call tpu_custom_call", 0.30),
        ("ag_gemm_ll custom-call tpu_custom_call", 0.10),
        ("gemm_rs_ll custom-call tpu_custom_call", 0.06),
        ("ag_gemm_ring custom-call tpu_custom_call", 0.02),
        ("all-reduce all-reduce", 0.01),
        ("collective-permute-start collective-permute-start", 0.01),
        ("fusion fusion", 0.05)], decode_events=20)
    assert read("comm_gemm_ms", view(None, ops)) == pytest.approx(10.0)
    none = reduced([("_unknown_ custom-call tpu_custom_call", 0.4),
                    ("fusion fusion", 0.05)])
    assert read("comm_gemm_ms", view(None, none)) is None


def test_the_benchmark_names_the_six_readers_and_the_new_cell():
    bench = cb_run.load_json(cb_run.ROOT, "BENCHMARK.json")
    new = ["step_host_ms", "admit_host_ms", "kv_pages_host_ms",
           "kv_live_peak", "decode_attention_ms", "comm_gemm_ms"]
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-6:] == new
    for n in new:
        assert callable(importlib.import_module(
            f"cellbench.layer_metrics.{n}").read)
    spec = cb_run.Spec.read("qwen3-8b-tp4.batch-closed", rehearse=False)
    assert spec.workload["chips"] == 4
    assert {m["name"] for m in spec.metrics("end_to_end")} == {
        "itl_p95_ms", "out_tokens_per_s", "setup_s"}
    assert {m["name"] for m in spec.metrics("per_layer")} >= {
        "batch_occupancy", "kv_pool_peak", "comm_gemm_ms",
        "kv_live_peak", "step_host_ms"}
