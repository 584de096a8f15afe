"""KV: what keeping pages by layer KIND saves — 1 less the live K/V
bytes (the tokens inside the window in the window layers, every live
token in the full layers) over what ONE page table for all layers
would hold for the same rows (every token in every layer); from the
program's `serving.window` spans, the mean over the window's steps."""

from cellbench import model_math_cohere2_moe as math
from cellbench import window_spans


def read(run):
    rows = window_spans.counted(run, "window_kv_saved_share")
    if rows is None:
        return None
    rows = [r for r in rows if r["full_tokens_live"] > 0]
    if not rows:
        return None
    cfg = run.spec.config
    return 100.0 * sum(
        math.kv_saved_share(cfg, r["window_tokens_live"],
                            r["full_tokens_live"])
        for r in rows) / len(rows)
