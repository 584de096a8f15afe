"""Model: device time of one masked decode step, the median duration
of the decode program's events on the trace's "XLA Modules" line."""

from cellbench import stats


def read(run):
    if run.trace is None:
        return None
    durs = run.module("decode")
    if not durs:
        return None
    return stats.percentile(durs, 50) * 1e3
