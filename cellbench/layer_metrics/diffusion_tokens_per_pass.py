"""Scheduler: tokens a row's pass yields, for a model that generates by
diffusion over blocks — `tokens_revealed` over `rows_denoise` +
`rows_commit` of the program's `serving.diffusion` spans, summed over
the window.  A block of B positions denoised in T passes and committed
by a pass of its own reads B / (T + 1): 1.33 at 4 and 2; a commit that
rode on the next block's first pass would read B / T."""

from cellbench import diffusion_spans


def read(run):
    rows = diffusion_spans.passes(run, "diffusion_tokens_per_pass")
    if rows is None:
        return None
    row_passes = sum(r["rows_denoise"] + r["rows_commit"] for r in rows)
    return sum(r["tokens_revealed"] for r in rows) / max(row_passes, 1)
