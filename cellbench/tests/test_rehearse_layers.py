"""`--rehearse --trace 1` walks the readers of PR 24: every one of
the six names is printed — with a value where the program's spans
are enough, as absent (and why) where it takes a device trace."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RUN = os.path.join(ROOT, "cellbench", "run.py")


@pytest.mark.parametrize("cell,valued,absent", [
    ("qwen3-8b-tp4.batch-closed",
     {"step_host_ms", "kv_pages_host_ms", "kv_live_peak"},
     {"decode_attention_ms", "comm_gemm_ms"}),
    ("qwen3-8b-1c.longprompt-steady",
     {"step_host_ms", "admit_host_ms"}, {"decode_attention_ms"}),
])
def test_rehearsal_prints_the_new_metrics(cell, valued, absent):
    p = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--seed",
         str(2 ** 31 + 24), "--seconds", "5", "--trace", "1",
         "--rehearse"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=""))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    rows = [json.loads(ln) for ln in p.stdout.splitlines()
            if ln.startswith("{")]
    last = rows[-1]
    assert last["rehearsal"] is True
    assert valued <= set(last["would_print"]["metrics"])
    said_absent = {r["metric"] for r in rows
                   if r.get("event") == "layer_metric_absent"}
    assert absent <= said_absent
    (phases,) = [r for r in rows if r.get("event") == "step_phases"]
    assert len(phases["longest"]) == 3
    assert set(phases["phase_ms_p50"]) == {
        "admit", "pages", "dispatch", "sync", "commit", "gauges", "self"}
