"""Expert layer: device time one prefill PROGRAM (a whole prefill of a
short bucket, or a chunk) spends in the experts' two grouped GEMMs —
the trace's rows named `moe_prefill_gate_up` and `moe_prefill_down`
summed (all layers), over the traced prefill programs.  (A decode
step's rows, `moe_decode_*`, are `moe_ffn_ms`.)"""

from cellbench.clock import say

KERNELS = ("moe_prefill_gate_up", "moe_prefill_down")


def read(run, metric: str = "moe_prefill_ffn_ms"):
    if run.trace is None:
        say(event="layer_metric_absent", metric=metric,
            why="no device trace (--trace 0, or a rehearsal)")
        return None
    rows = [(n, s) for n, s in run.trace.per_op.items()
            if n.startswith(KERNELS)]
    prefills = len(run.module("prefill"))
    if not rows or not prefills:
        say(event="layer_metric_absent", metric=metric,
            why=f"no operation named {' / '.join(KERNELS)}* among the "
                f"{len(run.trace.per_op)} device operations, or no "
                f"prefill in the traced steps ({prefills})")
        return None
    say(event="layer_metric_rows", metric=metric, prefills=prefills,
        rows=[[n, s] for n, s in rows])
    return sum(s for _, s in rows) / prefills * 1e3
