"""Kernels / roofline, BANDWIDTH bound, the whole step of a sparse
model with window and full attention and no shared expert: the least
time the chip could take for the traced decode steps — the weights
every step reads (attention, routers, norms, head), the experts the
step HIT, K and V of the tokens INSIDE the window in the window layers
and of every live token in the full layers
(`model_math_smallthinker.decode_step_bytes`, from the program's
`serving.moe` and `serving.window` spans) over its HBM bandwidth — as a
share of the device time those steps took."""

from cellbench import model_math_smallthinker as math
from cellbench import moe_spans, window_spans


def read(run):
    if run.trace is None:
        return None
    durs = run.module("decode")
    name = "moe_swa_decode_step_roofline"
    moe = moe_spans.counted(run, name, traced=True)
    win = window_spans.counted(run, name, traced=True)
    if not durs or moe is None or win is None:
        return None
    least = (math.decode_step_bytes(
        run.spec.config, window_spans.mean(win, "window_tokens_live"),
        window_spans.mean(win, "full_tokens_live"),
        moe_spans.mean(moe, "experts_hit"))
        / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (sum(durs) / len(durs))
