"""The Nemotron-H family's per-layer readers against hand-built runs:
a recorded fragment of a device trace's operations (the new kernels'
names among XLA's), the program's `serving.moe` / `serving.state`
spans inside and outside the traced steps, a program that leaves
neither, and the accepted cells' kernels, which these readers must not
count."""

import json
import os
import types

import pytest

from cellbench import model_math_nemotron_h as mm
from cellbench import run as cb_run
from cellbench.tests.test_span_readers import (
    OFFSET, FakeSpan, FakeTracer, read, reduced, said, view)

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "configs",
                       "nemotron-3-super-120b-1c.json")) as f:
    CFG = json.load(f)
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}

#: A decode step and a prefill as a device trace names them: the new
#: kernels (5 layers each), the attention kernel, XLA's own rows.
OPS = [("mamba2_decode_step custom-call tpu_custom_call", 0.090),
       ("moe_decode_relu2_up custom-call tpu_custom_call", 0.060),
       ("moe_decode_relu2_down custom-call tpu_custom_call", 0.050),
       ("flash_decode_paged custom-call tpu_custom_call", 0.004),
       ("mamba2_prefill_chunk custom-call tpu_custom_call", 0.003),
       ("moe_prefill_relu2_up custom-call tpu_custom_call", 0.010),
       ("fusion fusion", 0.040)]


def spans(live=128, hit=632):
    """Two traced steps' counters and one outside the traced steps."""
    out = []
    for t, h in ((16.0, hit), (17.0, hit + 8), (12.0, 100)):
        out.append(FakeSpan("serving.moe", t, 0.0, pairs=704.0,
                            experts_hit=float(h), expert_load_max=0.02,
                            pairs_elsewhere=2112.0))
        out.append(FakeSpan("serving.state", t, 0.0, live_slots=float(live),
                            state_bytes_live=live * 21278720.0, resets=1,
                            recomputed_tokens=0))
    return out


def run_of(trace, tracer, steps=(), rows=()):
    v = view(tracer, trace)
    drive = cb_run.Drive(rows=list(rows), steps=list(steps), t0=OFFSET,
                         start=v.drive.start, end=v.drive.end,
                         trace_span=v.drive.trace_span)
    spec = types.SimpleNamespace(config=CFG)
    v.system.num_slots = 128
    return cb_run.RunView(spec=spec, system=v.system, drive=drive,
                          trace=trace, peaks=PEAKS, math=None,
                          modules=v.modules)


def test_the_kernel_times_are_found_by_name_and_a_step():
    run = run_of(reduced(OPS, decode_events=10), None)
    assert read("ssm_decode_ms", run) == pytest.approx(9.0)
    assert read("latent_moe_ffn_ms", run) == pytest.approx(11.0)
    # one prefill in the fragment (`jit_fn`): its rows alone
    assert read("ssm_prefill_ms", run) == pytest.approx(3.0)


def test_the_accepted_cells_kernels_are_not_these(capsys):
    other = reduced([("kda_decode_step custom-call tpu_custom_call", 0.09),
                     ("moe_decode_gate_up custom-call tpu_custom_call", 0.06),
                     ("moe_decode_down custom-call tpu_custom_call", 0.05),
                     ("kda_prefill_chunk custom-call tpu_custom_call",
                      0.003)])
    run = run_of(other, FakeTracer(spans()))
    for name in ("ssm_decode_ms", "ssm_decode_roofline", "ssm_prefill_ms",
                 "ssm_prefill_roofline", "latent_moe_ffn_ms",
                 "latent_moe_ffn_roofline"):
        assert read(name, run) is None
    assert all(r["event"] == "layer_metric_absent" for r in said(capsys))
    # and the accepted reader does not count the new down-projection
    assert read("moe_ffn_ms", run_of(reduced(OPS), None)) is None


def test_the_state_kernels_share_of_its_roofline(capsys):
    run = run_of(reduced(OPS, decode_events=10), FakeTracer(spans()))
    least = mm.ssm_decode_bytes(CFG, 128) / 819e9
    assert least == pytest.approx(6.62e-3, rel=1e-3)
    assert read("ssm_decode_roofline", run) == pytest.approx(
        100 * least / 9.0e-3)
    # a program that leaves no `serving.state` span: nothing, said why
    assert read("ssm_decode_roofline",
                run_of(reduced(OPS), FakeTracer([]))) is None
    assert any("serving.state" in r.get("why", "")
               for r in said(capsys))


def test_the_latent_experts_share_and_their_hit_rate():
    run = run_of(reduced(OPS, decode_events=10), FakeTracer(spans()))
    # the traced steps hit 632 and 640 of the 5 x 128 held experts
    least = 636 * 2 * 1024 * 2688 * 2 / 819e9
    assert read("latent_moe_ffn_roofline", run) == pytest.approx(
        100 * least / 11.0e-3)
    # over the window's three steps: (632 + 640 + 100) / 3 of 640
    assert read("latent_moe_experts_hit", run) == pytest.approx(
        100 * (1372 / 3) / 640)
    assert read("latent_moe_experts_hit",
                run_of(None, FakeTracer([]))) is None


def test_the_whole_steps_share(capsys):
    # (t, active, admitted, used_pages, live_tokens) of two traced steps
    steps = [(OFFSET + 16.0, 128, 0, 5000, 70000),
             (OFFSET + 17.0, 128, 1, 5000, 72000)]
    run = run_of(reduced(OPS, decode_events=10), FakeTracer(spans()),
                 steps=steps)
    least = mm.decode_step_bytes(CFG, 128, 71000, 636) / 819e9
    assert least == pytest.approx(17.8e-3, rel=1e-2)
    # the fragment's decode program runs 80 ms an event
    assert read("ssm_decode_step_roofline", run) == pytest.approx(
        100 * least / 0.08)
    assert read("ssm_decode_step_roofline",
                run_of(None, FakeTracer(spans()), steps=steps)) is None


def test_the_chunked_kernels_share_says_its_bound(capsys):
    item = types.SimpleNamespace(prompt=list(range(513)), due=0.0)
    row = types.SimpleNamespace(
        item=item, admitted_at=lambda system: OFFSET + 16.5)
    run = run_of(reduced(OPS), FakeTracer(spans()), rows=[row])
    by_bytes = mm.ssm_prefill_bytes(CFG, 512, 1) / 819e9
    by_compute = mm.ssm_prefill_flops(CFG, 512) / 197e12
    assert by_bytes > by_compute
    assert read("ssm_prefill_roofline", run) == pytest.approx(
        100 * by_bytes / 3.0e-3)
    bound = [r for r in said(capsys) if r["event"] == "roofline_bound"]
    assert bound[0]["bound"] == "bandwidth"
    assert bound[0]["tokens_a_prefill"] == 512
    # nobody admitted in the traced steps: nothing
    assert read("ssm_prefill_roofline",
                run_of(reduced(OPS), FakeTracer(spans()))) is None
