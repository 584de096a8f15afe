"""Scheduler: share of the decode batch's rows that held a request,
mean over the window's steps of `step()["active"]` over `num_slots`."""


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    return 100.0 * sum(s[1] for s in steps) / (
        len(steps) * run.system.num_slots)
