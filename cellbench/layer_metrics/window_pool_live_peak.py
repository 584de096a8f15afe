"""KV: peak share of the WINDOW layers' pool that rows hold —
`window_pages_live` of the program's `serving.window` spans over that
pool's usable pages, the largest over the window's steps.  The pool is
sized by slots x (window + a page): a row holds the pages of its last
`sliding_window` tokens and gives back what lies behind them."""

from cellbench import window_spans


def read(run):
    rows = window_spans.counted(run, "window_pool_live_peak")
    usable = getattr(run.system, "window_usable_pages", 0)
    if rows is None or not usable:
        return None
    return 100.0 * max(r["window_pages_live"] for r in rows) / usable
