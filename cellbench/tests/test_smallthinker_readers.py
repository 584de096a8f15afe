"""The SmallThinker cell's new per-layer readers against hand-built
runs: a fragment of a device trace's operations, the program's
`serving.moe` (with `rows`) / `serving.window` /
`serving.admit.prefill` spans inside and outside the traced steps, and
a program that leaves none of what they read (the parent of the PR that
brought the family)."""

import json
import os
import types

import pytest

from cellbench import model_math_smallthinker as mm
from cellbench import run as cb_run
from cellbench.tests.test_span_readers import (
    FakeSpan, FakeTracer, read, reduced, said, view)

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "configs",
                       "smallthinker-21b-1c.json")) as f:
    CFG = json.load(f)
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}

#: Ten decode steps and one prefill program as a device trace names
#: them.
OPS = [("swa_decode_paged custom-call tpu_custom_call", 0.010),
       ("flash_decode_paged custom-call tpu_custom_call", 0.004),
       ("moe_decode_gate_up custom-call tpu_custom_call", 0.050),
       ("moe_decode_down custom-call tpu_custom_call", 0.030),
       ("moe_prefill_gate_up custom-call tpu_custom_call", 0.006),
       ("moe_prefill_down custom-call tpu_custom_call", 0.004),
       ("fusion fusion", 0.040)]

NEW = ("moe_swa_decode_step_roofline", "moe_prefill_ffn_ms",
       "moe_prefill_ffn_roofline", "moe_bytes_per_token")


def spans(pieces=((4096, 1024),), rows_said=True):
    """Two traced steps' records and one outside the traced steps."""
    out = []
    for t, rows, hit in ((16.0, 40, 500), (17.0, 20, 480), (12.0, 10, 300)):
        out.append(FakeSpan("serving.moe", t, 0.0, pairs=288.0,
                            experts_hit=float(hit), expert_load_max=0.05,
                            **({"rows": rows} if rows_said else {})))
        out.append(FakeSpan(
            "serving.window", t, 0.0, window_pages_live=150 * rows,
            full_pages_live=160 * rows, window_pages_released=2,
            recomputed_tokens=0, window_tokens_live=2300 * rows,
            full_tokens_live=2500 * rows))
    for start, tokens in pieces:
        out.append(FakeSpan("serving.admit.prefill", 16.5, 0.001,
                            bucket=1024, start=start, tokens=tokens))
    return out


def run_of(trace, tracer):
    v = view(tracer, trace)
    v.system.window_usable_pages = 48 * 257
    v.modules["prefill"] = "jit_fn"
    return cb_run.RunView(spec=types.SimpleNamespace(config=CFG),
                          system=v.system, drive=v.drive, trace=trace,
                          peaks=PEAKS, math=None, modules=v.modules)


def test_the_whole_steps_share():
    run = run_of(reduced(OPS, decode_events=10), FakeTracer(spans()))
    least = mm.decode_step_bytes(CFG, 30 * 2300, 30 * 2500, 490) / 819e9
    assert least == pytest.approx(
        (1_118_786_560 + 490 * 11_796_480 + 69_000 * 12_288
         + 75_000 * 4096) / 819e9)
    # the fragment's decode program runs 80 ms an event
    assert read("moe_swa_decode_step_roofline", run) == pytest.approx(
        100 * least / 0.08)


def test_the_prefill_experts_time_and_share(capsys):
    run = run_of(reduced(OPS), FakeTracer(spans()))
    assert read("moe_prefill_ffn_ms", run) == pytest.approx(10.0)
    # a chunk of 1024 streams 8 x 64 experts (7.4 ms) under 0.58 TFLOP
    # (2.9 ms): bandwidth bound
    by_bytes = 512 * 11_796_480 / 819e9
    assert read("moe_prefill_ffn_roofline", run) == pytest.approx(
        100 * by_bytes / 10.0e-3)
    bound = [r for r in said(capsys) if r["event"] == "roofline_bound"]
    assert bound[-1]["bound"] == "bandwidth"
    assert bound[-1]["tokens_a_piece"] == 1024
    assert bound[-1]["expert_bytes_a_piece"] == pytest.approx(
        512 * 11_796_480)
    # 8 chunks' tokens in one program would be compute bound
    run = run_of(reduced(OPS), FakeTracer(spans(((0, 8192),))))
    by_compute = 2 * 8192 * 6 * 5_898_240 * 8 / 197e12
    assert by_compute > by_bytes
    assert read("moe_prefill_ffn_roofline", run) == pytest.approx(
        100 * by_compute / 10.0e-3)


def test_expert_bytes_a_token_follow_the_rows():
    run = run_of(None, FakeTracer(spans()))
    # the window's three steps: 500 / 40, 480 / 20, 300 / 10 experts a
    # row
    assert read("moe_bytes_per_token", run) == pytest.approx(
        11_796_480 * (12.5 + 24 + 30) / 3)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_family_leaves_nothing_and_does_not_raise(
        name, capsys):
    """The parent's spans and kernels: no `serving.window`, no `rows`
    on `serving.moe`, no piece that says its tokens, no prefill expert
    kernel in the traced steps."""
    other = reduced([("flash_decode_paged custom-call tpu_custom_call",
                      0.02),
                     ("moe_decode_gate_up custom-call tpu_custom_call",
                      0.06)])
    old = [FakeSpan("serving.moe", 16.0, 0.0, pairs=8.0, experts_hit=6.0,
                    expert_load_max=0.2),
           FakeSpan("serving.admit.prefill", 16.5, 0.001, bucket=1024)]
    for trace, tracer in ((other, FakeTracer(old)), (None, None),
                          (other, None)):
        assert read(name, run_of(trace, tracer)) is None
    assert all(r["event"] == "layer_metric_absent" for r in said(capsys))
