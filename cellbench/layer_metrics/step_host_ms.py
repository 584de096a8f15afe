"""Scheduler: what the host spends on one step apart from waiting for
the device — the median over the window's steps of `serving.step`
less its `serving.sync`.  The split by phase and the three longest
steps go to stdout (`step_phases`)."""

from cellbench import span_reader, stats


def read(run):
    steps = span_reader.steps_of(run, "step_host_ms")
    if steps is None:
        return None
    span_reader.say(event="step_phases",
                    **span_reader.phase_report(steps))
    return stats.percentile([st.host_s for st in steps], 50) * 1e3
