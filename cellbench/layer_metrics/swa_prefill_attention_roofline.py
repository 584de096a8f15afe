"""Kernels / roofline: the least time the chip could take for the
window layers' attention over the prefill programs of the traced steps
— the LARGER of the visible (query, key) pairs' operations over the
peak bf16 rate and the bytes over the HBM bandwidth
(`model_math_cohere2_moe.window_prefill_flops` / `_bytes`), priced on
where each PROGRAM's piece starts and the tokens it holds (the
program's own `serving.admit.prefill` spans: the programs' tokens, not
the admissions' prompts) — as a share of the device time
`swa_prefill_attention` took for them.  Which of the two bounds it says
on stdout (`roofline_bound`)."""

from cellbench import model_math_cohere2_moe as math
from cellbench import window_spans
from cellbench.clock import say
from cellbench.layer_metrics import swa_prefill_attention_ms

NAME = "swa_prefill_attention_roofline"


def read(run):
    ms = swa_prefill_attention_ms.read(run, NAME)
    if ms is None:
        return None
    pieces = window_spans.pieces(run, NAME)
    if pieces is None:
        return None
    cfg = run.spec.config
    prefills = len(run.module("prefill"))
    # the mean program of the span against the mean time of one
    by_compute = (math.window_prefill_flops(cfg, pieces) / len(pieces)
                  / run.peaks["bf16_flops_per_s"])
    by_bytes = (math.window_prefill_bytes(cfg, pieces) / len(pieces)
                / run.peaks["hbm_bytes_per_s"])
    say(event="roofline_bound", metric=NAME,
        bound="compute" if by_compute >= by_bytes else "bandwidth",
        compute_s=by_compute, bandwidth_s=by_bytes,
        pieces=len(pieces), prefills=prefills,
        tokens_a_piece=sum(n for _, n in pieces) / len(pieces),
        start_mean=sum(s for s, _ in pieces) / len(pieces))
    return 100.0 * max(by_compute, by_bytes) / (ms * 1e-3)
