"""Expert layer: the busiest expert's share of a step's (token, expert)
pairs in the worst sparse layer — `expert_load_max` of the program's
`serving.moe` spans, mean over the window's steps.  An even load reads
100 / experts; the packed plan holds any load, so this says how uneven
the rows of the grouped GEMMs are, not whether anything was dropped."""

from cellbench import moe_spans


def read(run):
    rows = moe_spans.counted(run, "moe_expert_load_max")
    if rows is None:
        return None
    return 100.0 * moe_spans.mean(rows, "expert_load_max")
