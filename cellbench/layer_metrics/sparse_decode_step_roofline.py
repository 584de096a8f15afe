"""Kernels / roofline, BANDWIDTH bound, the whole step of a sparse
model: the least time the chip could take for the traced decode steps
— the weights every step reads (attention, dense feed-forward, routers,
shared experts, head), the routed experts the step HIT and the latent
rows of the live context (`model_math_glm4_moe_lite.decode_step_bytes`)
over its HBM bandwidth — as a share of the device time those steps
took."""

from cellbench import model_math_glm4_moe_lite as math
from cellbench import moe_spans


def read(run):
    if run.trace is None:
        return None
    durs = run.module("decode")
    steps = [s for s in run.traced_steps() if s[1] > 0]
    rows = moe_spans.counted(run, "sparse_decode_step_roofline",
                             traced=True)
    if not durs or not steps or rows is None:
        return None
    live = sum(s[4] for s in steps) / len(steps)
    least = (math.decode_step_bytes(
        run.spec.config, live, moe_spans.mean(rows, "experts_hit"))
        / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (sum(durs) / len(durs))
