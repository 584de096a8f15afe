"""Kernels / roofline, the whole block pass of a model that generates
by diffusion over blocks: the least time the chip could take for the
traced passes — the LARGER of their bytes over its HBM bandwidth (the
weights every pass reads, the experts the pass HIT, the K/V its rows
attend and the block rows it writes:
`model_math_sdar_moe.pass_bytes`) and their operations over the peak
bf16 rate (`pass_flops`) — as a share of the device time those passes
took.  Which of the two bounds it says on stdout (`roofline_bound`)."""

from cellbench import diffusion_spans, moe_spans
from cellbench import model_math_sdar_moe as math
from cellbench.clock import say


def read(run):
    if run.trace is None:
        return None
    name = "diffusion_pass_roofline"
    durs = run.module("decode")
    steps = [s for s in run.traced_steps() if s[1] > 0]
    fed = diffusion_spans.passes(run, name, traced=True)
    moe = moe_spans.counted(run, name, traced=True) if fed else None
    if not durs or not steps or fed is None or moe is None:
        return None
    cfg = run.spec.config
    live = sum(s[4] for s in steps) / len(steps)
    positions = sum(r["positions_fed"] for r in fed) / len(fed)
    by_bytes = (math.pass_bytes(cfg, live, positions,
                                moe_spans.mean(moe, "experts_hit"))
                / run.peaks["hbm_bytes_per_s"])
    by_compute = (math.pass_flops(cfg, live, positions,
                                  moe_spans.mean(moe, "pairs"))
                  / run.peaks["bf16_flops_per_s"])
    say(event="roofline_bound", metric=name,
        bound="compute" if by_compute >= by_bytes else "bandwidth",
        compute_s=by_compute, bandwidth_s=by_bytes, live_tokens=live,
        positions_fed=positions, passes=len(durs))
    return 100.0 * max(by_compute, by_bytes) / (sum(durs) / len(durs))
