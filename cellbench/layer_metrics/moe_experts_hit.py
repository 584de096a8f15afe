"""Expert layer: share of a sparse layer's routed experts that a decode
step touches — `experts_hit` of the program's `serving.moe` spans
(experts with at least one row, summed over the sparse layers) over
experts x sparse layers, mean over the window's steps.  What the step
must stream of the expert weights."""

from cellbench import model_math_glm4_moe_lite as math
from cellbench import moe_spans


def read(run):
    rows = moe_spans.counted(run, "moe_experts_hit")
    if rows is None:
        return None
    cfg = run.spec.config
    return (100.0 * moe_spans.mean(rows, "experts_hit")
            / (cfg["n_routed_experts"] * math.sparse_layers(cfg)))
