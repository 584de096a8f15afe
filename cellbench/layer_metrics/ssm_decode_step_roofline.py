"""Kernels / roofline, BANDWIDTH bound, the whole step of a
state-space hybrid: the least time the chip could take for the traced
decode steps — the weights every step reads (mixers, routers, latent
projections, shared experts, head), the HELD experts the step hit, the
recurrent state of the live rows read and written, and K/V of the live
context in the attention layers
(`model_math_nemotron_h.decode_step_bytes`) over its HBM bandwidth — as
a share of the device time those steps took."""

from cellbench import model_math_nemotron_h as math
from cellbench import moe_spans
from cellbench.layer_metrics import state_pool_live_peak


def read(run):
    if run.trace is None:
        return None
    durs = run.module("decode")
    steps = [s for s in run.traced_steps() if s[1] > 0]
    name = "ssm_decode_step_roofline"
    moe = moe_spans.counted(run, name, traced=True)
    state = state_pool_live_peak.counted(run, name, traced=True)
    if not durs or not steps or moe is None or state is None:
        return None
    live_tokens = sum(s[4] for s in steps) / len(steps)
    live_rows = sum(r["live_slots"] for r in state) / len(state)
    least = (math.decode_step_bytes(
        run.spec.config, live_rows, live_tokens,
        moe_spans.mean(moe, "experts_hit"))
        / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (sum(durs) / len(durs))
