"""Linear attention: device time one prefill spends in the chunked
delta-rule kernel — the trace's rows named `kda_prefill_chunk` summed
(all delta-rule layers), over the traced prefills."""

from cellbench.clock import say


def read(run):
    if run.trace is None:
        say(event="layer_metric_absent", metric="kda_prefill_ms",
            why="no device trace (--trace 0, or a rehearsal)")
        return None
    rows = [(n, s) for n, s in run.trace.per_op.items()
            if n.startswith("kda_prefill_chunk")]
    prefills = len(run.module("prefill"))
    if not rows or not prefills:
        say(event="layer_metric_absent", metric="kda_prefill_ms",
            why=f"no operation named kda_prefill_chunk* among the "
                f"{len(run.trace.per_op)} device operations, or no "
                f"prefill in the traced steps ({prefills})")
        return None
    say(event="layer_metric_rows", metric="kda_prefill_ms",
        prefills=prefills, rows=[[n, s] for n, s in rows])
    return sum(s for _, s in rows) / prefills * 1e3
