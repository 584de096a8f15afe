"""Kernels / roofline: the least time the chip could take for the
experts' work in the prefill programs of the traced steps — the LARGER
of the programs' (token, expert) pairs' operations over the peak bf16
rate and the bytes of the experts a program reaches over the HBM
bandwidth (`model_math_smallthinker.prefill_ffn_flops` / `_bytes`: each
program streams the experts its tokens hit ONCE, whatever its length),
priced on the tokens each PROGRAM holds (the program's own
`serving.admit.prefill` spans: the programs' tokens, not the
admissions' prompts) — as a share of `moe_prefill_ffn_ms`.  Which of
the two bounds, and the experts a program re-streams as a number of
bytes, it says on stdout (`roofline_bound`)."""

from cellbench import model_math_smallthinker as math
from cellbench import window_spans
from cellbench.clock import say
from cellbench.layer_metrics import moe_prefill_ffn_ms

NAME = "moe_prefill_ffn_roofline"


def read(run):
    ms = moe_prefill_ffn_ms.read(run, NAME)
    if ms is None:
        return None
    pieces = window_spans.pieces(run, NAME)
    if pieces is None:
        return None
    cfg = run.spec.config
    n = len(pieces)
    # the mean program of the span against the mean time of one
    flops = sum(math.prefill_ffn_flops(cfg, t) for _, t in pieces) / n
    streamed = sum(math.prefill_ffn_bytes(cfg, t) for _, t in pieces) / n
    by_compute = flops / run.peaks["bf16_flops_per_s"]
    by_bytes = streamed / run.peaks["hbm_bytes_per_s"]
    say(event="roofline_bound", metric=NAME,
        bound="compute" if by_compute >= by_bytes else "bandwidth",
        compute_s=by_compute, bandwidth_s=by_bytes, pieces=n,
        prefills=len(run.module("prefill")),
        tokens_a_piece=sum(t for _, t in pieces) / n,
        expert_bytes_a_piece=streamed)
    return 100.0 * max(by_compute, by_bytes) / (ms * 1e-3)
