"""Expert layer: share of the HELD latent experts that a decode step
touches — `experts_hit` of the program's `serving.moe` spans (held
experts with at least one row, summed over the expert layers) over the
experts held x the pattern's count of `E` layers, mean over the
window's steps.  What the step must stream of the expert weights."""

from cellbench import model_math_nemotron_h as math
from cellbench import moe_spans


def read(run):
    rows = moe_spans.counted(run, "latent_moe_experts_hit")
    if rows is None:
        return None
    cfg = run.spec.config
    return (100.0 * moe_spans.mean(rows, "experts_hit")
            / (cfg["n_routed_experts"] * math.layers(cfg, "E")))
