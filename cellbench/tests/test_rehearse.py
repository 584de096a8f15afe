"""`--rehearse` end to end on the CPU, and the same run with the timed
path broken underneath: `correct` has to come out false."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RUN = os.path.join(ROOT, "cellbench", "run.py")


def lines_of(out):
    rows = []
    for ln in out.splitlines():
        if ln.startswith("{"):
            rows.append(json.loads(ln))
    return rows


@pytest.mark.parametrize("cell,trace", [
    ("qwen3-8b-1c.longprompt-steady", 0),
    ("qwen3-8b-1c.batch-closed", 1),
])
def test_rehearsal_walks_the_run_and_prints_no_result(cell, trace):
    p = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--seed",
         str(2 ** 31 + 17), "--seconds", "5", "--trace", str(trace),
         "--rehearse"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=""))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    rows = lines_of(p.stdout)
    last = rows[-1]
    assert last["rehearsal"] is True and "correct" not in last
    would = last["would_print"]
    # the driver's keys in their order, what was compared last
    assert list(would) == ["correct", "attempted", "failed", "metrics",
                           "device", "compared"]
    assert would["correct"] is True and would["failed"] == 0
    assert would["attempted"] > 0
    compared = [r for r in rows if r.get("event") == "compared"]
    assert {r["compared"] for r in compared} == set(would["compared"]) == {
        "served_gap_max", "served_gap_mean", "failed", "plan_wrapped",
        "tokens_checked_at_least"}
    assert all(set(v) == {"value", "limit"}
               for v in would["compared"].values())
    # the phase clock: every line says when, and the clock only rises
    clock = [r["since_start_s"] for r in rows]
    assert clock == sorted(clock) and clock[0] > 0
    traced = [r for r in rows if r.get("event") == "traced"]
    if trace:
        assert "batch_occupancy" in would["metrics"]
        (t,) = traced
        assert 0 < t["steps"] <= 120 and t["span_s"] > 0
        assert t["stop_trace_s"] > 0
        assert t["events"] is None and t["read_s"] is None  # rehearsal
    else:
        assert "setup_s" in would["metrics"] and not traced


def test_float8_weights_in_the_program_come_out_as_not_correct():
    """The control a run can be given: the program serves weights
    rounded to float8, the reference keeps the configuration's."""
    p = subprocess.run(
        [sys.executable, RUN, "--workload",
         "qwen3-8b-1c.longprompt-steady", "--seed", "7", "--seconds",
         "6", "--trace", "0", "--rehearse", "--weights", "fp8"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=""))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = lines_of(p.stdout)[-1]
    assert last["control"] is True and "correct" not in last
    assert last["would_print"]["correct"] is False
    assert last["would_print"]["failed"] == 0


def test_no_tpu_no_result():
    p = subprocess.run(
        [sys.executable, RUN, "--workload", "qwen3-8b-1c.longprompt-steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=""))
    assert p.returncode != 0
    assert not any("correct" in r for r in lines_of(p.stdout))


def test_a_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch, capsys):
    """Skips the look for a chip (`--rehearse`) and drives the rest of
    a run in this process, with the scheduler's decode step handing
    back every token plus one."""
    sys.path.insert(0, ROOT)
    from cellbench import run
    from cellbench.adapters import qwen3 as adapter
    init = adapter.System.__init__

    def broken_init(self, config, seed, devices, **kw):
        init(self, config, seed, devices, **kw)
        step, vocab = self.sched._step, config["vocab_size"]

        def broken(params, tokens, cache, keys, active):
            toks, cache, keys = step(params, tokens, cache, keys, active)
            return (toks + 1) % vocab, cache, keys
        self.sched._step = broken

    monkeypatch.setattr(adapter.System, "__init__", broken_init)
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "qwen3-8b-1c.longprompt-steady", "--seed", "4",
        "--seconds", "5", "--trace", "0", "--rehearse"])
    assert run.main() == 0
    last = lines_of(capsys.readouterr().out)[-1]
    assert last["rehearsal"] is True
    assert last["would_print"]["correct"] is False
    assert last["would_print"]["failed"] == 0
