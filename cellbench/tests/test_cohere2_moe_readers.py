"""The Cohere2-MoE family's per-layer readers against hand-built runs:
a fragment of a device trace's operations (the window layers' kernels
under their own names beside the full layers'), the program's
`serving.window` / `serving.moe` / `serving.admit.prefill` spans inside
and outside the traced steps, and a program that leaves none of them
(the parent of the PR that brought the family)."""

import json
import os
import types

import pytest

from cellbench import model_math_cohere2_moe as mm
from cellbench import run as cb_run
from cellbench.tests.test_span_readers import (
    FakeSpan, FakeTracer, read, reduced, said, view)

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "configs",
                       "command-a-plus-218b-1c.json")) as f:
    CFG = json.load(f)
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}

#: Ten decode steps and one prefill program as a device trace names
#: them: the window layers' kernels, the full layer's, the experts'.
OPS = [("swa_decode_paged custom-call tpu_custom_call", 0.020),
       ("flash_decode_paged custom-call tpu_custom_call", 0.005),
       ("moe_decode_gate_up custom-call tpu_custom_call", 0.050),
       ("moe_decode_down custom-call tpu_custom_call", 0.030),
       ("swa_prefill_attention custom-call tpu_custom_call", 0.004),
       ("flash_attention_fwd custom-call tpu_custom_call", 0.006),
       ("fusion fusion", 0.040)]

NEW = ("swa_decode_step_roofline", "swa_decode_attention_ms",
       "swa_decode_attention_roofline", "swa_prefill_attention_ms",
       "swa_prefill_attention_roofline", "window_pool_live_peak",
       "window_kv_saved_share")


def spans(pieces=((8192, 1024),)):
    """Two traced steps' records and one outside the traced steps."""
    out = []
    for t, rows, hit in ((16.0, 24, 50), (17.0, 24, 54), (12.0, 10, 30)):
        out.append(FakeSpan("serving.moe", t, 0.0, pairs=float(rows),
                            experts_hit=float(hit), expert_load_max=0.2,
                            pairs_elsewhere=7.0 * rows))
        out.append(FakeSpan(
            "serving.window", t, 0.0, window_pages_live=250 * rows,
            full_pages_live=600 * rows, window_pages_released=2,
            recomputed_tokens=0, window_tokens_live=4000 * rows,
            full_tokens_live=10000 * rows))
    for start, tokens in pieces:
        out.append(FakeSpan("serving.admit.prefill", 16.5, 0.001,
                            bucket=1024, start=start, tokens=tokens))
    return out


def run_of(trace, tracer):
    v = view(tracer, trace)
    v.system.window_usable_pages = 24 * 257
    v.modules["prefill"] = "jit_fn"
    return cb_run.RunView(spec=types.SimpleNamespace(config=CFG),
                          system=v.system, drive=v.drive, trace=trace,
                          peaks=PEAKS, math=None, modules=v.modules)


def test_the_window_kernels_are_read_apart_from_the_full_layers():
    run = run_of(reduced(OPS, decode_events=10), None)
    assert read("swa_decode_attention_ms", run) == pytest.approx(2.0)
    # the accepted reader sees the full layer's rows alone
    assert read("decode_attention_ms", run) == pytest.approx(0.5)
    assert read("moe_ffn_ms", run) == pytest.approx(8.0)
    assert read("swa_prefill_attention_ms", run) == pytest.approx(4.0)


def test_the_window_kernels_share_of_its_roofline():
    run = run_of(reduced(OPS, decode_events=10), FakeTracer(spans()))
    least = 24 * 4000 * 3 * 4096 / 819e9
    assert read("swa_decode_attention_roofline", run) == pytest.approx(
        100 * least / 2.0e-3)


def test_the_whole_steps_share_counts_tokens_inside_the_window():
    run = run_of(reduced(OPS, decode_events=10), FakeTracer(spans()))
    least = mm.decode_step_bytes(CFG, 24 * 4000, 24 * 10000, 52) / 819e9
    assert least == pytest.approx(
        (3028328448 + 52 * 100663296 + 96000 * 12288 + 240000 * 4096)
        / 819e9)
    # the fragment's decode program runs 80 ms an event
    assert read("swa_decode_step_roofline", run) == pytest.approx(
        100 * least / 0.08)


def test_the_prefill_kernels_share_is_priced_on_the_programs_pieces(
        capsys):
    run = run_of(reduced(OPS), FakeTracer(spans()))
    by_compute = 4 * 1024 * 4096 * 128 * 128 * 3 / 197e12
    assert read("swa_prefill_attention_roofline", run) == pytest.approx(
        100 * by_compute / 4.0e-3)
    bound = [r for r in said(capsys) if r["event"] == "roofline_bound"]
    assert bound[-1]["bound"] == "compute"
    assert bound[-1]["tokens_a_piece"] == 1024
    # a short prompt's whole prefill from position 0 sees a triangle
    run = run_of(reduced(OPS), FakeTracer(spans(((0, 1000),))))
    low = 4 * (1000 * 1001 // 2) * 128 * 128 * 3 / 197e12
    by_bytes = mm.window_prefill_bytes(CFG, [(0, 1000)]) / 819e9
    assert read("swa_prefill_attention_roofline", run) == pytest.approx(
        100 * max(low, by_bytes) / 4.0e-3)


def test_the_pools_counters():
    run = run_of(None, FakeTracer(spans()))
    # the window's three steps: 24, 24 and 10 rows of 250 pages
    assert read("window_pool_live_peak", run) == pytest.approx(
        100 * 250 * 24 / (24 * 257))
    # every step: 1 - (3 x 4000 + 10000) / (4 x 10000)
    assert read("window_kv_saved_share", run) == pytest.approx(45.0)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_family_leaves_nothing_and_does_not_raise(
        name, capsys):
    """The parent's spans and kernels: no `serving.window`, no piece
    that says where it starts, no kernel of the window's names."""
    other = reduced([("flash_decode_paged custom-call tpu_custom_call",
                      0.02),
                     ("moe_decode_gate_up custom-call tpu_custom_call",
                      0.06),
                     ("flash_attention_fwd custom-call tpu_custom_call",
                      0.003)])
    old = [FakeSpan("serving.moe", 16.0, 0.0, pairs=8.0, experts_hit=6.0,
                    expert_load_max=0.2),
           FakeSpan("serving.admit.prefill", 16.5, 0.001, bucket=1024)]
    for trace, tracer in ((other, FakeTracer(old)), (None, None),
                          (other, None)):
        assert read(name, run_of(trace, tracer)) is None
    assert all(r["event"] == "layer_metric_absent" for r in said(capsys))
