"""State-space layers: device time one prefill spends in the chunked
Mamba-2 kernel — the trace's rows named `mamba2_prefill_chunk` summed
(all state-space layers), over the traced prefills."""

from cellbench.clock import say

KERNEL = "mamba2_prefill_chunk"


def read(run):
    if run.trace is None:
        say(event="layer_metric_absent", metric="ssm_prefill_ms",
            why="no device trace (--trace 0, or a rehearsal)")
        return None
    rows = [(n, s) for n, s in run.trace.per_op.items()
            if n.startswith(KERNEL)]
    prefills = len(run.module("prefill"))
    if not rows or not prefills:
        say(event="layer_metric_absent", metric="ssm_prefill_ms",
            why=f"no operation named {KERNEL}* among the "
                f"{len(run.trace.per_op)} device operations, or no "
                f"prefill in the traced steps ({prefills})")
        return None
    say(event="layer_metric_rows", metric="ssm_prefill_ms",
        prefills=prefills, rows=[[n, s] for n, s in rows])
    return sum(s for _, s in rows) / prefills * 1e3
