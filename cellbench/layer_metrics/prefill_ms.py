"""Model: device time of one prefill, the median duration of the
prefill programs' events on "XLA Modules" — weighted by the buckets
the window drew, since every admission is one event."""

from cellbench import stats


def read(run):
    if run.trace is None:
        return None
    durs = run.module("prefill")
    if not durs:
        return None
    return stats.percentile(durs, 50) * 1e3
