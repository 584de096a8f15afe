"""KV (paged pool): peak share of the pool's usable pages in use
(live requests and cached prefix pages), sampled after every step of
the window."""


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    return 100.0 * max(s[3] for s in steps) / run.system.usable_pages
