"""Expert layer: expert bytes a decode step streams for each token it
yields — `experts_hit` of the program's `serving.moe` spans x an
expert's bytes over the span's `rows` (the live rows the step carried),
the mean over the window's steps.  An open loop's batch breathes: a
step at half the rows hits nearly as many experts and yields half the
tokens, and this is what that costs.  None where the program leaves no
`rows` (a program before PR 48)."""

from cellbench import model_math_smallthinker as math
from cellbench import moe_spans


def read(run):
    rows = moe_spans.counted(run, "moe_bytes_per_token")
    if rows is None:
        return None
    rows = [r for r in rows if r.get("rows", 0) > 0]
    if not rows:
        moe_spans.say(event="layer_metric_absent",
                      metric="moe_bytes_per_token",
                      why="no serving.moe span says its rows")
        return None
    cfg = run.spec.config
    return sum(math.expert_bytes(cfg, r["experts_hit"]) / r["rows"]
               for r in rows) / len(rows)
