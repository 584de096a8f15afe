"""Kernels: device time one prefill PROGRAM (a whole prefill of a short
bucket, or a chunk) spends in the WINDOW layers' attention — the
trace's rows named `swa_prefill_attention` summed (all window layers),
over the traced prefill programs."""

from cellbench.clock import say

KERNEL = "swa_prefill_attention"


def read(run, metric: str = "swa_prefill_attention_ms"):
    if run.trace is None:
        say(event="layer_metric_absent", metric=metric,
            why="no device trace (--trace 0, or a rehearsal)")
        return None
    rows = [(n, s) for n, s in run.trace.per_op.items()
            if n.startswith(KERNEL)]
    prefills = len(run.module("prefill"))
    if not rows or not prefills:
        say(event="layer_metric_absent", metric=metric,
            why=f"no operation named {KERNEL}* among the "
                f"{len(run.trace.per_op)} device operations, or no "
                f"prefill in the traced steps ({prefills})")
        return None
    say(event="layer_metric_rows", metric=metric, prefills=prefills,
        rows=[[n, s] for n, s in rows])
    return sum(s for _, s in rows) / prefills * 1e3
