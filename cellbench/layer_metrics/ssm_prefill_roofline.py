"""Kernels / roofline: the least time the chip could take for the
chunked state-space recurrence over the prompts admitted in the traced
steps — the LARGER of its operations over the peak bf16 rate and its
bytes over the HBM bandwidth (`model_math_nemotron_h.ssm_prefill_flops`
/ `_bytes`, over the prompts' own tokens, not their buckets' padding) —
as a share of the device time `mamba2_prefill_chunk` took for them.
Which of the two bounds it says on stdout (`roofline_bound`)."""

from cellbench import model_math_nemotron_h as math
from cellbench.clock import say
from cellbench.layer_metrics import ssm_prefill_ms


def read(run):
    ms = ssm_prefill_ms.read(run)
    if ms is None:
        return None
    admitted = run.admitted_in_trace()
    prefills = len(run.module("prefill"))
    if not admitted:
        say(event="layer_metric_absent", metric="ssm_prefill_roofline",
            why="no request admitted in the traced steps")
        return None
    # the mean prefill of the span (a prompt's last token goes to the
    # first decode step), against the mean time of one
    tokens = (sum(len(r.item.prompt) - 1 for r in admitted)
              / len(admitted))
    cfg = run.spec.config
    by_compute = (math.ssm_prefill_flops(cfg, tokens)
                  / run.peaks["bf16_flops_per_s"])
    by_bytes = (math.ssm_prefill_bytes(cfg, tokens, 1)
                / run.peaks["hbm_bytes_per_s"])
    say(event="roofline_bound", metric="ssm_prefill_roofline",
        bound="compute" if by_compute >= by_bytes else "bandwidth",
        compute_s=by_compute, bandwidth_s=by_bytes,
        tokens_a_prefill=tokens, admitted=len(admitted),
        prefills=prefills)
    return 100.0 * max(by_compute, by_bytes) / (ms * 1e-3)
